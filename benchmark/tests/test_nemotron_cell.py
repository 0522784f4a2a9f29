"""The Nemotron-H training cell's files and readers, and whole runs of it
on the CPU (the rehearsal's sizes): a sound run is correct; a run whose
shared expert is left out, or whose scan passes no state from chunk to
chunk, is not; the fp8 reference in the program's place is not.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_nemotron_cell.py -q
"""
import importlib
import io
import json
import math
import os
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import calibrate  # noqa: E402
from benchmark import harness, run as bench_run, trafficgen  # noqa: E402
from benchmark.readers import nemotron as readers  # noqa: E402
from benchmark.references import nemotron_h as reference  # noqa: E402

CELL = "train_nemotron3_ep16_seq8192"
SPEC = harness.load_spec()
PROBE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "scope_probe.xplane.pb")


def last_line(seconds="1.5"):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(["--rehearse", "--workload", CELL, "--seed",
                             "2147483659", "--seconds", seconds])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), \
        out.getvalue()


def test_the_cell_states_its_cut():
    parts = harness.load_cell(SPEC, CELL)
    config, mix = parts["config"], parts["mix"]
    kw = config["model"]["kwargs"]
    assert "nemotron_h" in config["source"] and len(config["source"]) <= 200
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"]["num_hidden_layers"] == 52
    assert config["published"]["n_routed_experts"] == 128
    assert config["published"]["vocab_size"] == 131072
    # the file's top level keeps the published keys, cut where it says
    assert config["num_hidden_layers"] == len(kw["hybrid_override_pattern"])
    assert config["n_routed_experts"] == \
        kw["held_experts"][1] - kw["held_experts"][0] == 8
    assert config["vocab_size"] == kw["vocab_size"] == 131072 // 8
    assert config["hybrid_override_pattern"].startswith(
        kw["hybrid_override_pattern"])
    # every width as published; the router keeps its 128 outputs
    for ours, theirs in [("hidden_size", "hidden_size"),
                         ("n_routed_experts", None),
                         ("num_experts_per_tok", "num_experts_per_tok"),
                         ("moe_intermediate_size", "moe_intermediate_size"),
                         ("moe_shared_expert_intermediate_size",
                          "moe_shared_expert_intermediate_size"),
                         ("mamba_num_heads", "mamba_num_heads"),
                         ("mamba_head_dim", "mamba_head_dim"),
                         ("n_groups", "n_groups"),
                         ("ssm_state_size", "ssm_state_size"),
                         ("chunk_size", "chunk_size"),
                         ("conv_kernel", "conv_kernel"),
                         ("head_dim", "head_dim"),
                         ("num_attention_heads", "num_attention_heads"),
                         ("num_key_value_heads", "num_key_value_heads")]:
        want = config[theirs] if theirs else 128
        assert kw[ours] == want, ours
    for key in ("attention_positions", "A_log", "dt_bias", "optimizer"):
        assert key in config["assumed"]
    assert "16 chips" in config["deployment"]
    assert (mix["batch"], mix["seq_len"]) == (2, 8192)
    spec = reference.param_spec(kw)
    total = sum(math.prod(s) for s in spec.values())
    assert total == 666_963_456                   # 666.96 M, 10.67 GB
    batches = trafficgen.train_batches(trafficgen.load_mix(
        "pretrain_seq8192", rehearse=True), 512, 7)
    assert batches[0][0].shape == (2, 128)


def test_cell_reports_the_declared_metrics():
    layer = {m["name"] for m in
             harness.metrics_for(SPEC, "per_layer", CELL)}
    assert {"train_step_ms", "train_mfu_pct", "step_device_ms.train",
            "device_idle_pct.train", "peak_hbm_gib.train",
            "kernel_fallbacks.train", "ssd_scan_device_ms.nemo",
            "ssd_scan_roofline_pct.nemo", "expert_ffn_device_ms.nemo",
            "expert_ffn_roofline_pct.nemo", "local_pairs_per_token.nemo",
            "expert_load_max_over_mean.nemo",
            "kernel_fallbacks.nemo"} == layer
    e2e = {m["name"] for m in harness.metrics_for(SPEC, "end_to_end", CELL)}
    assert e2e == {"train_tokens_per_s", "setup_s"}


def test_scopes_are_read_from_a_recorded_chip_trace():
    """A 68 KB trace of a TPU v5e (a probe program with the scope names
    ssd_scan and expert_ffn and a Pallas kernel named grouped_matmul):
    the wire-format reader finds the paths in the events' metadata."""
    with open(PROBE, "rb") as f:
        ops = readers.device_ops(f.read())
    assert len(ops) == 50
    paths = {p for p, _, _ in ops}
    assert "jit(g_)/expert_ffn/grouped_matmul/pallas_call:" in paths
    assert any("transpose(jvp(ssd_scan))" in p for p in paths)
    own = readers.own_time_by_scope(ops, ["ssd_scan", "expert_ffn",
                                          "ssd", "absent"])
    assert own["ssd_scan"] == 29_913_828          # picoseconds, 2 runs
    assert own["expert_ffn"] == 21_835_002
    assert own["ssd"] == 0 and own["absent"] == 0  # whole names only
    # nested operations count once: a while's own time, not its span
    nested = [("a/x/b", 0, 100), ("a/x/c", 10, 30), ("a/y/d", 50, 20)]
    assert readers.own_time_by_scope(nested, ["x", "y"]) == \
        {"x": 80, "y": 20}


def test_readers_return_nothing_where_there_is_nothing(monkeypatch):
    assert readers.scope_device_ms({"trace": None}, {"scope": "x"}) is None
    monkeypatch.setattr(readers, "newest_trace", lambda: None)
    assert readers.scope_roofline_pct(
        {"trace": {"busy_s": 1}}, {"scope": "ssd_scan"}) is None
    from paddle_tpu.distributed import moe
    moe.reset_expert_totals()
    assert readers.expert_counter({}, {"field": "local_pairs_per_token"}) \
        is None
    monkeypatch.delattr(moe, "expert_totals")     # the parent's program
    assert readers.expert_counter({}, {"field": "local_pairs_per_token"}) \
        is None


def test_roofline_reads_the_probe_and_the_costs(monkeypatch):
    monkeypatch.setattr(readers, "newest_trace", lambda: PROBE)
    obs = {"trace": {"busy_s": 1}, "trace_steps": 2,
           "peaks": harness.peaks_for("TPU v5 lite")}
    ms = readers.scope_device_ms(obs, {"scope": "ssd_scan"})
    assert ms == pytest.approx(29_913_828e-9 / 2)
    params = {"scope": "ssd_scan", "config": "nemotron3-nano-ep16-train",
              "traffic": "pretrain_seq8192"}
    kw, tokens = readers._shapes(params)
    assert tokens == 16384
    scan = readers.ssd_scan_cost(kw, tokens)
    ffn = readers.expert_ffn_cost(kw, tokens)
    # bytes bound the scan (5.0 ms), operations the experts (7.5 ms)
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12
    assert scan["bytes"] / 819e9 == pytest.approx(4.98e-3, rel=0.01)
    assert ffn["flops"] / 197e12 == pytest.approx(7.47e-3, rel=0.01)
    assert ffn["flops"] / 197e12 > ffn["bytes"] / 819e9
    assert readers.scope_roofline_pct(obs, params) == pytest.approx(
        100 * scan["bytes"] / 819e9 / (ms * 1e-3))
    # and the whole step's operations: 2.14 GFLOP a token
    assert reference.train_flops_per_token(kw, 8192) == pytest.approx(
        2.14e9, rel=0.01)


def test_sound_run_is_correct_and_counts_its_pairs():
    from paddle_tpu.distributed import moe
    moe.reset_expert_totals()
    line, out = last_line()
    assert line["correct"] is True and line["rehearsal"] is True, out
    assert line["metrics"] == {}
    totals = moe.expert_totals()
    assert totals["pairs_dropped"] == 0 and len(totals["layers"]) == 2
    # 6 of 16 experts a token, 4 held: 1.5 pairs a token expected
    assert 1.2 < totals["local_pairs_per_token"] < 1.8


def no_shared_expert(x, w_up, w_down):
    import jax.numpy as jnp
    return jnp.zeros_like(x)


def scan_without_carried_state(real):
    """The chunked scan with every chunk taken for a sequence of its own:
    nothing is passed from chunk to chunk."""
    def broken(x, dt, a_neg, b_mat, c_mat, q):
        cut = lambda t: t.reshape((-1, q) + t.shape[2:])
        y = real(cut(x), cut(dt), a_neg, cut(b_mat), cut(c_mat), q)
        return y.reshape(x.shape)
    return broken


@pytest.mark.parametrize("broken", ["shared_expert", "scan_state"])
def test_broken_run_is_not_correct(monkeypatch, broken):
    if broken == "shared_expert":
        from paddle_tpu.models import nemotron_h
        monkeypatch.setattr(nemotron_h.NemotronHMLP, "_fn",
                            staticmethod(no_shared_expert))
    else:
        ssd = importlib.import_module("paddle_tpu.ops.ssd_scan")
        monkeypatch.setattr(ssd, "_chunked",
                            scan_without_carried_state(ssd._chunked))
    line, out = last_line()
    assert line["correct"] is False, out


def test_fp8_reference_fails_the_check():
    parts = harness.load_cell(SPEC, CELL, rehearse=True)
    jax, devices = harness.start_jax(1, rehearse=True)
    ctx = {"jax": jax, "devices": devices, "config": parts["config"],
           "mix": parts["mix"], "seconds": 2.0, "trace": False,
           "workload": CELL, "control_precisions": ["fp8"]}
    limits = parts["config"]["check"]["limits"]
    pairs = (("loss_gap", "loss_rel_gap"), ("grad_gap", "grad_norm_gap"),
             ("delta_gap", "delta_norm_gap"))
    seeds = [2200000000 + 7919 * i for i in range(3)]
    for row in calibrate.calibrate_train(ctx, seeds, len(seeds)):
        for reading, limit in pairs:
            assert row["program"][reading] <= limits[limit], row
        failed = [r for r, l in pairs if row["control_fp8"][r] > limits[l]]
        assert "grad_gap" in failed, row
