"""The trace reduction on hand-built traces: busy union, idle share,
own time of nested operations, module times, and the gap list with the
host's side.  Runs on the CPU:  python3 -m pytest benchmark/tests -q"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import trace_reduce as tr  # noqa: E402


def planes():
    ms = 1e-3
    ops = [("while.1", 0 * ms, 10 * ms),        # spans the two below
           ("fusion.2", 1 * ms, 3 * ms),
           ("custom-call.3", 5 * ms, 4 * ms),
           ("fusion.2", 12 * ms, 2 * ms),       # after a 2 ms gap
           ("copy.4", 20 * ms, 5 * ms)]         # after a 6 ms gap
    modules = [("jit_step(123456)", 0, 14 * ms),
               ("jit_step(123456)", 20 * ms, 5 * ms),
               ("jit_other(9)", 14 * ms, 1 * ms)]
    host = [("TransferFromDevice", 14.5 * ms, 5 * ms),
            ("PjitFunction(step)", 10.1 * ms, 1.5 * ms)]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules},
            {"name": "Steps", "events": []}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": host}]},
        {"name": "/device:TPU:1", "lines": []},     # a chip that ran nothing
    ]


def test_union_merges_nested_and_touching():
    assert tr.union([(0, 10), (1, 4), (5, 9), (10, 11), (12, 14)]) == \
        [(0, 11), (12, 14)]


def test_busy_idle_window():
    r = tr.reduce(planes())
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(25e-3)
    assert r["busy_s"] == pytest.approx(17e-3)      # 10 + 2 + 5
    assert r["idle_share"] == pytest.approx(8 / 25)


def test_own_time_of_nested_ops():
    own = tr.self_times(planes()[0]["lines"][0]["events"])
    assert own["while.1"] == pytest.approx(3e-3)    # 10 - 3 - 4
    assert own["fusion.2"] == pytest.approx(5e-3)   # 3 + 2
    r = tr.reduce(planes(), top=2)
    assert [n for n, _ in r["device_ops"]] == ["fusion.2 x1", "copy.4 x1"]


def test_op_label_keeps_what_tells_operations_apart():
    line = ('%copy.148.remat = bf16[24,16,2048,128]{3,2,1,0:T(8,128)(2,1)} '
            'copy(bf16[24,16,2048,128]{3,1,2,0:T(8,128)(2,1)} %bitcast.91)')
    assert tr.op_label(line) == "copy copy bf16[24,16,2048,128]"
    call = ('%checkpoint.19 = (f32[96,2048,64]{2,1,0:T(8,128)}, f32[96,2048,'
            '64]{2,1,0}) custom-call(bf16[96,2048,64]{2,1,0} %bitcast.1452), '
            'custom_call_target="tpu_custom_call", frontend_attributes={}')
    assert tr.op_label(call) == \
        "checkpoint custom-call:tpu_custom_call f32[96,2048,64]"
    events = [(line, 0.0, 1.0), (line.replace("148", "149"), 2.0, 1.0)]
    planes_ = [{"name": "/device:TPU:0",
                "lines": [{"name": "XLA Ops", "events": events}]}]
    assert tr.reduce(planes_)["device_ops"] == \
        [["copy copy bf16[24,16,2048,128] x2", 2.0]]


def test_modules_strip_fingerprint():
    mods = tr.reduce(planes())["modules"]
    assert mods["jit_step"]["count"] == 2
    assert mods["jit_step"]["total_s"] == pytest.approx(19e-3)
    assert mods["jit_other"]["count"] == 1


def test_gaps_are_named_by_the_host():
    gaps = tr.reduce(planes())["idle_gaps"]
    assert gaps[0][0] == "TransferFromDevice"
    assert gaps[0][1] == pytest.approx(6e-3)
    assert gaps[1][0] == "PjitFunction(step)"
    assert gaps[1][1] == pytest.approx(2e-3)
    lone = planes()
    lone[1]["lines"][0]["events"] = []
    assert tr.reduce(lone)["idle_gaps"][0][0] == "unattributed"


def test_no_device_plane_gives_nothing():
    assert tr.reduce([p for p in planes() if "host" in p["name"]]) == {}


XSPACE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 2000000 } }
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 8000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step(42)" } }
}
"""


def test_loader_reads_an_xspace():
    from jax.profiler import ProfileData
    loaded = tr.load(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    r = tr.reduce(loaded)
    assert r["busy_s"] == pytest.approx(6e-6)
    assert r["window_s"] == pytest.approx(8e-6)
    assert r["idle_share"] == pytest.approx(0.25)
    assert r["modules"]["jit_step"]["total_s"] == pytest.approx(8e-6)
