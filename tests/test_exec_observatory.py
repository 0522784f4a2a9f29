"""Executable observatory (ISSUE 15): per-executable cost/memory
registry, roofline attribution, HBM ledger, roofline-aware doctor,
report CLI, flight-recorder bundle GC, metrics snapshot rotation.

The overhead half of the contract (registry armed adds 0 syncs / 0
recompiles) lives in tests/test_telemetry.py's suite; this file covers
the observatory's own behavior: registration at compile time, DEFERRED
analysis (reading stats never compiles), degradation to timing-only on
broken backends/dead owners, roofline math against pinned peaks, ledger
accounting, and the offline report round-trip.
"""
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import observability as obs
from paddle_tpu.inference import InferenceEngine
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import doctor
from paddle_tpu.observability import exec_registry as er
from paddle_tpu.observability import flightrec, report
from paddle_tpu.utils import compile_counter


def tiny_model(seed=0):
    cfg = GPTConfig(vocab_size=97, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=64,
                    use_flash_attention=False)
    paddle.seed(seed)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def linear_trainer():
    from paddle_tpu.distributed import SpmdTrainer, create_mesh
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 10))
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=m.parameters())
    return SpmdTrainer(m, opt, lambda o, y: F.cross_entropy(o, y),
                       mesh=create_mesh({"dp": 1}))


def drive_engine(eng, n=8, seed=0):
    rng = np.random.RandomState(seed)
    prompt = rng.randint(1, 97, (7,)).astype(np.int32)
    rid = eng.add_request(prompt, max_new_tokens=n)
    eng.run()
    return rid


# ---------------------------------------------------------------------------
# registration + runtime pairing
# ---------------------------------------------------------------------------
def test_engine_executables_join_registry_at_compile_time():
    eng = InferenceEngine(tiny_model(), batch_slots=2,
                          prefill_buckets=[16])
    eng.warmup(buckets=[16])
    kinds = {e.kind for e in er.registry().entries(eng._exec_component)}
    assert {"prefill", "decode", "sample"} <= kinds
    # runtime pairing: decode steady-state calls accumulate
    drive_engine(eng)
    dec = [e for e in er.registry().entries(eng._exec_component)
           if e.kind == "decode"][0]
    assert dec.calls >= 7 and dec.runtime_ms > 0
    assert dec.compile_ms is not None and dec.compile_ms > 0
    # registration captured donation + sharding metadata host-side
    assert dec.meta["kv_layout"] == "dense"
    assert dec.in_shardings        # non-empty summary


@pytest.mark.slow  # tier-1 wall budget: heaviest in file
def test_spec_and_paged_kinds_registered():
    m = tiny_model()
    eng = InferenceEngine(m, batch_slots=2, kv_layout="paged",
                          kv_block_size=8, prefill_buckets=[16],
                          spec_k=2, draft_model=m)
    eng.warmup(buckets=[16])
    drive_engine(eng, n=6, seed=1)
    kinds = {e.kind for e in er.registry().entries(eng._exec_component)}
    assert "spec_verify" in kinds
    assert "prefill" in kinds and "sample" in kinds
    spec = [e for e in er.registry().entries(eng._exec_component)
            if e.kind == "spec_verify"][0]
    assert spec.meta["spec_k"] == 2


def test_trainer_train_step_registered_and_analyzed(monkeypatch):
    # the CPU has no tabled peak: pin one so the roofline math runs
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "100e9")
    monkeypatch.setenv("PADDLE_TPU_PEAK_HBM_GBPS", "10")
    tr = linear_trainer()
    x = np.random.RandomState(0).randn(8, 16).astype(np.float32)
    y = np.random.RandomState(0).randint(0, 10, size=(8,)) \
        .astype(np.int64)
    for _ in range(3):
        tr.train_step(x, y)
    es = er.registry().entries(tr._exec_component)
    assert [e.kind for e in es] == ["train_step"]
    assert es[0].calls == 2        # first call was the compile
    # stats never analyze (no compiles from a stats read) ...
    snap0 = compile_counter.snapshot()
    assert tr.stats["exec_profile"] is None
    assert snap0.new_compiles == 0
    # ... the explicit deferred analysis does, and populates the digest
    assert er.analyze_all(tr._exec_component) == 1
    prof = tr.stats["exec_profile"]
    ts = prof["train_step"]
    assert ts["flops"] and ts["bytes_accessed"]
    assert ts["bound"] in ("compute", "bandwidth")
    assert ts["mfu"] is not None and ts["mean_ms"] > 0


# ---------------------------------------------------------------------------
# degradation (satellite: timing-only instead of throwing)
# ---------------------------------------------------------------------------
def test_dead_owner_degrades_to_timing_only():
    import gc
    eng = InferenceEngine(tiny_model(), batch_slots=2,
                          prefill_buckets=[16])
    eng.warmup(buckets=[16])
    comp = eng._exec_component
    entries = er.registry().entries(comp)
    assert entries
    del eng
    gc.collect()
    before = obs.counter("exec_analysis_failures_total",
                         labels=("stage",)) \
        .labels(stage="owner_released").value
    e = entries[0]
    assert not er.registry().analyze(e)
    assert e.analysis is None and "released" in e.analysis_error
    after = obs.counter("exec_analysis_failures_total",
                        labels=("stage",)) \
        .labels(stage="owner_released").value
    assert after == before + 1
    # the snapshot still renders the entry, timing-only
    row = [r for r in er.snapshot(comp)["executables"]
           if str(e.key) == r["key"]][0]
    assert row["analyzed"] is False and row["calls"] == e.calls


def test_cost_memory_stats_guard_none_and_raise():
    from paddle_tpu import profiler

    class NoneAnalysis:
        def cost_analysis(self):
            return None

        def memory_analysis(self):
            return None

    class RaisingAnalysis:
        def cost_analysis(self):
            raise RuntimeError("deserialized executable")

        def memory_analysis(self):
            raise RuntimeError("deserialized executable")

    c = obs.counter("exec_analysis_failures_total", labels=("stage",))
    before = c.labels(stage="cost_analysis").value
    assert profiler.cost_stats(NoneAnalysis()) == {}
    assert profiler.cost_stats(RaisingAnalysis()) == {}
    assert profiler.memory_stats(NoneAnalysis()) == {}
    assert profiler.memory_stats(RaisingAnalysis()) == {}
    assert c.labels(stage="cost_analysis").value == before + 2


def test_registry_disabled_registers_nothing(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_EXEC_REGISTRY", "0")
    eng = InferenceEngine(tiny_model(), batch_slots=2,
                          prefill_buckets=[16])
    eng.warmup(buckets=[16])
    assert er.registry().entries(eng._exec_component) == []


# ---------------------------------------------------------------------------
# roofline math (pinned peaks)
# ---------------------------------------------------------------------------
def test_roofline_classification_and_attribution(monkeypatch):
    reg = er.ExecRegistry()
    # pinned peaks: 100 GFLOP/s, 10 GB/s -> ridge AI = 10
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "100e9")
    monkeypatch.setenv("PADDLE_TPU_PEAK_HBM_GBPS", "10")
    compute = er.ExecEntry("c", ("big_matmul",), "train_step",
                           "big_matmul")
    compute.analysis = {"cost": {"flops": 1e9, "bytes_accessed": 1e6},
                        "memory": {}}
    compute.calls, compute.runtime_ms = 10, 200.0     # 20ms/call
    bandwidth = er.ExecEntry("c", ("decode",), "decode", "decode")
    bandwidth.analysis = {"cost": {"flops": 1e7, "bytes_accessed": 1e8},
                          "memory": {}}
    bandwidth.calls, bandwidth.runtime_ms = 10, 200.0
    reg._entries = {("c", ("big_matmul",)): compute,
                    ("c", ("decode",)): bandwidth}
    snap = reg.snapshot("c")
    assert snap["peaks_known"] is True
    rows = {r["name"]: r for r in snap["executables"]}
    mm, dec = rows["big_matmul"], rows["decode"]
    # AI 1000 vs ridge 10 -> compute; AI 0.1 -> bandwidth
    assert mm["bound"] == "compute" and dec["bound"] == "bandwidth"
    # 1e9 flops / 20ms = 5e10 -> 50% MFU
    assert mm["mfu"] == pytest.approx(0.5, rel=1e-3)
    # 1e8 bytes / 20ms = 5e9 B/s -> 50% of the 10 GB/s roof
    assert dec["hbm_bw_frac"] == pytest.approx(0.5, rel=1e-3)
    assert dec["roof_frac"] == pytest.approx(0.5, rel=1e-3)
    # equal wall time -> equal time share; gap_share reflects each
    # entry's distance from the 45% target
    assert mm["time_share"] == pytest.approx(0.5, abs=1e-3)
    assert dec["time_share"] == pytest.approx(0.5, abs=1e-3)
    assert mm["gap_share"] == pytest.approx(0.0, abs=1e-3)  # above 45%
    assert dec["gap_share"] > 0.4                           # way below
    assert snap["overall"]["mfu"] == pytest.approx(
        (1e9 * 10 + 1e7 * 10) / 0.4 / 100e9, rel=1e-3)


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------
def test_hbm_ledger_tracks_and_drops_dead_owners(monkeypatch):
    import gc
    monkeypatch.setenv("PADDLE_TPU_HBM_BYTES", str(512 * 1024 * 1024))
    led = er.HBMLedger()

    class Owner:
        pass

    o = Owner()
    led.track(o, "params", "t0", 100 << 20)
    led.track(o, "kv_cache", "t0", 50 << 20)
    led.track(None, "static", "x", 1 << 20)
    reg = er.ExecRegistry()
    snap = led.snapshot(exec_registry=reg)
    assert snap["by_category"] == {"params": 100 << 20,
                                   "kv_cache": 50 << 20,
                                   "static": 1 << 20}
    assert snap["capacity_bytes"] == 512 * 1024 * 1024
    assert snap["headroom_frac"] == pytest.approx(
        (512 - 151) / 512, abs=0.01)
    assert snap["oom_risk"] is False
    # owner dies -> its entries fall out; the ownerless one stays
    del o
    gc.collect()
    snap = led.snapshot(exec_registry=reg)
    assert snap["by_category"] == {"static": 1 << 20}


def test_engine_feeds_ledger_params_and_kv():
    eng = InferenceEngine(tiny_model(), batch_slots=2,
                          prefill_buckets=[16])
    h = er.ledger().snapshot()
    mine = [t for t in h["tracked"]
            if t["name"] == eng.telemetry_label]
    cats = {t["category"] for t in mine}
    assert {"params", "kv_cache"} <= cats
    kv = [t for t in mine if t["category"] == "kv_cache"][0]
    # dense cache: 2 (k,v) * L * slots * seq * Hkv * D * 4B + lengths
    cfg = eng.model.cfg
    expect = 2 * cfg.num_layers * 2 * 64 * cfg.num_kv_heads * \
        cfg.head_dim * 4
    assert abs(kv["bytes"] - expect) <= 64   # lengths array slack


# ---------------------------------------------------------------------------
# roofline-aware doctor
# ---------------------------------------------------------------------------
def _decode_profile(bw_frac, bound="bandwidth", known=True):
    return {
        "decode": {"kind": "decode", "bound": bound,
                   "hbm_bw_frac": bw_frac, "achieved_hbm_gbps": 590.0,
                   "arithmetic_intensity": 1.2, "ridge_ai": 240.0,
                   "mfu": 0.04, "calls": 100, "runtime_ms": 500.0},
        "_peaks": {"peaks_known": known, "device_kind": "tpu v5e"},
    }


def test_doctor_bandwidth_bound_decode_roofline():
    v = doctor.diagnose(
        {"decode_steps": 100, "kv_dtype": None,
         "exec_profile": _decode_profile(0.72)}, kind="serve")
    names = [x["bottleneck"] for x in v]
    assert "bandwidth-bound-decode" in names
    hit = v[names.index("bandwidth-bound-decode")]
    assert hit["evidence"]["hbm_bw_frac"] == 0.72
    assert hit["evidence"]["bound"] == "bandwidth"
    # int8 KV is the first byte-saver the rule names and proposes
    assert hit["knob"].startswith("int8 KV (PADDLE_TPU_KV_DTYPE=int8)")
    assert hit["action"]["param"] == "kv_dtype"
    assert hit["action"]["candidates"] == ["int8"]
    assert hit["score"] == pytest.approx(0.72, abs=1e-4)


def test_doctor_roofline_skips_unknown_peaks():
    v = doctor.diagnose(
        {"decode_steps": 100, "kv_dtype": "int8",
         "exec_profile": _decode_profile(0.9, known=False)},
        kind="serve")
    assert "bandwidth-bound-decode" not in \
        [x["bottleneck"] for x in v]


def test_doctor_threshold_fallback_without_exec_profile():
    # pre-registry evidence still produces the advisory verdict
    v = doctor.diagnose(
        {"decode_steps": 100, "decode_hbm_bytes_per_tok": 10_000_000,
         "kv_dtype": None}, kind="serve")
    assert "bandwidth-bound-decode" in [x["bottleneck"] for x in v]


def test_doctor_measured_compute_bound_beats_byte_fallback():
    # a roofline row classifying decode COMPUTE-bound is authoritative:
    # the byte-count heuristic must not fall through and contradict it
    v = doctor.diagnose(
        {"decode_steps": 100, "decode_hbm_bytes_per_tok": 10_000_000,
         "kv_dtype": None,
         "exec_profile": _decode_profile(0.2, bound="compute")},
        kind="serve")
    assert "bandwidth-bound-decode" not in \
        [x["bottleneck"] for x in v]


def test_doctor_mfu_below_target_train_rule():
    stats = {"exec_profile": {
        "train_step": {"kind": "train_step", "bound": "compute",
                       "mfu": 0.35, "arithmetic_intensity": 300.0,
                       "ridge_ai": 240.0, "mean_ms": 120.0,
                       "gap_share": 0.2, "runtime_ms": 2400.0,
                       "calls": 20},
        "_peaks": {"peaks_known": True}}}
    v = doctor.diagnose(stats, kind="train")
    names = [x["bottleneck"] for x in v]
    assert "mfu-below-target" in names
    hit = v[names.index("mfu-below-target")]
    assert hit["evidence"]["mfu"] == 0.35
    assert hit["evidence"]["bound"] == "compute"


def test_doctor_oom_risk_rule():
    v = doctor.diagnose(
        {"hbm": {"headroom_frac": 0.03, "tracked_bytes": 15 << 30,
                 "capacity_bytes": 16 << 30,
                 "exec_temp_bytes": 400 << 20,
                 "exec_temp_worst": "trainer:s0:fused/1/1"}},
        kind="train")
    names = [x["bottleneck"] for x in v]
    assert "oom-risk" in names
    hit = v[names.index("oom-risk")]
    assert hit["evidence"]["headroom_frac"] == 0.03
    assert "exec_temp_worst" in hit["evidence"]
    # healthy headroom: silent
    assert doctor.diagnose({"hbm": {"headroom_frac": 0.4}}) == []


# ---------------------------------------------------------------------------
# snapshot -> report round-trip
# ---------------------------------------------------------------------------
def test_snapshot_and_report_round_trip(tmp_path, monkeypatch):
    # the CPU has no tabled peak: pin one so the rows carry fractions
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "100e9")
    monkeypatch.setenv("PADDLE_TPU_PEAK_HBM_GBPS", "10")
    eng = InferenceEngine(tiny_model(), batch_slots=2,
                          prefill_buckets=[16])
    eng.warmup(buckets=[16])
    drive_engine(eng, seed=3)
    er.analyze_all(eng._exec_component)
    snap = obs.snapshot()
    assert "executables" in snap and "hbm" in snap
    rows = [r for r in snap["executables"]["executables"]
            if r["component"] == eng._exec_component]
    kinds = {r["kind"] for r in rows}
    assert {"prefill", "decode", "sample"} <= kinds
    dec = [r for r in rows if r["kind"] == "decode"][0]
    for fld in ("flops", "bytes_accessed", "peak_bytes", "bound",
                "mfu", "hbm_bw_frac", "time_share"):
        assert dec.get(fld) is not None, fld

    # offline: write_snapshot -> report renders from the file only
    path = str(tmp_path / "snap.jsonl")
    obs.write_snapshot(path)
    rec = report.load_snapshot_file(path)
    assert rec is not None
    text = report.render_snapshot(rec)
    assert "decode" in text and "hbm ledger" in text
    assert "executables on" in text
    # CLI main() exits 0 on the same file
    assert report.main(["--snapshot", path]) == 0


def test_report_cli_exit_codes(tmp_path, capsys):
    missing = str(tmp_path / "nope.jsonl")
    assert report.main(["--snapshot", missing]) == 2
    garbage = tmp_path / "garbage.jsonl"
    garbage.write_text("not json\n{broken\n")
    assert report.main(["--snapshot", str(garbage)]) == 2


def test_report_cli_with_nothing_to_render(tmp_path, capsys, monkeypatch):
    # no arguments, no PADDLE_TPU_METRICS file, no flightrec bundle
    monkeypatch.delenv("PADDLE_TPU_METRICS", raising=False)
    monkeypatch.setenv("PADDLE_TPU_FLIGHTREC_DIR", str(tmp_path / "none"))
    assert report.main([]) == 2
    assert "nothing to render" in capsys.readouterr().err


def test_ledger_oom_flag_agrees_with_doctor_threshold(monkeypatch):
    # one constant: the ledger's oom_risk flag and the doctor's rule
    # must flip on the same headroom line
    assert doctor.HBM_HEADROOM_MIN == er.OOM_HEADROOM_MIN
    monkeypatch.setenv("PADDLE_TPU_HBM_BYTES", str(1000))
    led = er.HBMLedger()
    led.track(None, "params", "edge", 1000 - int(1000 * 0.07))
    snap = led.snapshot(exec_registry=er.ExecRegistry())
    assert snap["oom_risk"] is True
    assert doctor.diagnose({"hbm": snap})[0]["bottleneck"] == "oom-risk"


@pytest.mark.slow  # tier-1 wall budget: heaviest in file
def test_engine_registered_donation_matches_jit_construction():
    m = tiny_model()
    eng = InferenceEngine(m, batch_slots=2, kv_layout="paged",
                          kv_block_size=8, prefill_buckets=[16],
                          spec_k=2, draft_model=m, donate=True)
    eng.warmup(buckets=[16])
    by_kind = {e.kind: e for e in
               er.registry().entries(eng._exec_component)}
    assert by_kind["sample"].donate_argnums == ()        # never donates
    assert by_kind["spec_verify"].donate_argnums == (2, 3)  # both caches
    assert by_kind["prefill"].donate_argnums == (1,)
    assert by_kind["decode"].donate_argnums == (1,)


def test_flightrec_bundle_carries_executables(tmp_path):
    eng = InferenceEngine(tiny_model(), batch_slots=2,
                          prefill_buckets=[16])
    eng.warmup(buckets=[16])
    rec = flightrec.FlightRecorder()
    rec.record("decode_tick", dur_ms=1.0, tick=1)
    path = rec.dump("test", directory=str(tmp_path))
    assert path is not None
    bundle = flightrec.load_bundle(path)["bundle"]
    assert "executables" in bundle and "hbm" in bundle
    comps = {r["component"]
             for r in bundle["executables"]["executables"]}
    assert eng._exec_component in comps
    # the report CLI renders a bundle too
    assert report.main(["--bundle", path]) == 0


# ---------------------------------------------------------------------------
# flight-recorder bundle GC (satellite)
# ---------------------------------------------------------------------------
def test_flightrec_gc_prunes_oldest_and_tmp_orphans(tmp_path,
                                                    monkeypatch):
    base = str(tmp_path)
    monkeypatch.setenv("PADDLE_TPU_FLIGHTREC_KEEP", "3")
    now = time.time()
    for i in range(6):
        d = os.path.join(base, f"flightrec-111-{i:03d}-old")
        os.makedirs(d)
        os.utime(d, (now - 1000 + i, now - 1000 + i))
    # stale .tmp orphan (dead process) and a fresh one (live dump)
    stale = os.path.join(base, "flightrec-222-001-x.tmp")
    fresh = os.path.join(base, "flightrec-333-001-y.tmp")
    os.makedirs(stale)
    os.utime(stale, (now - 7200, now - 7200))
    os.makedirs(fresh)
    # unrelated files are never touched
    other = os.path.join(base, "notes.txt")
    with open(other, "w") as f:
        f.write("keep me")
    flightrec.gc_bundles(base)
    left = sorted(os.listdir(base))
    assert "notes.txt" in left
    assert "flightrec-333-001-y.tmp" in left          # fresh tmp kept
    assert "flightrec-222-001-x.tmp" not in left      # stale tmp gone
    committed = [n for n in left if n.startswith("flightrec-111")]
    assert committed == ["flightrec-111-003-old", "flightrec-111-004-old",
                         "flightrec-111-005-old"]     # newest 3 kept


def test_flightrec_dump_triggers_gc(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLIGHTREC_KEEP", "2")
    rec = flightrec.FlightRecorder()
    paths = [rec.dump(f"r{i}", directory=str(tmp_path))
             for i in range(4)]
    assert all(paths)
    left = [n for n in os.listdir(str(tmp_path))
            if n.startswith("flightrec-")]
    assert len(left) == 2


# ---------------------------------------------------------------------------
# metrics snapshot size rotation (satellite)
# ---------------------------------------------------------------------------
def test_snapshot_file_size_rotation(tmp_path, monkeypatch):
    from paddle_tpu.observability.metrics import Registry
    r = Registry()
    g = r.gauge("fat_gauge", "x" * 200, labels=("k",))
    for i in range(40):
        g.labels(k=f"label-{i}-{'y' * 100}").set(i)
    path = str(tmp_path / "snap.jsonl")
    monkeypatch.setenv("PADDLE_TPU_METRICS_SNAPSHOT_MAX_MB", "0.02")
    for _ in range(50):
        r.write_snapshot(path)
    size = os.path.getsize(path)
    assert size <= 0.02 * 1e6 + 1024     # bounded (one-line slack)
    with open(path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert lines and "metrics" in lines[-1]     # newest always lands
    # no .tmp orphan from the rotating writes
    assert not [p for p in os.listdir(str(tmp_path))
                if p.endswith(".tmp")]


def test_snapshot_single_fat_line_still_lands(tmp_path, monkeypatch):
    from paddle_tpu.observability.metrics import Registry
    r = Registry()
    g = r.gauge("huge", "h" * 500, labels=("k",))
    for i in range(100):
        g.labels(k=f"{i}-{'z' * 200}").set(i)
    path = str(tmp_path / "snap.jsonl")
    monkeypatch.setenv("PADDLE_TPU_METRICS_SNAPSHOT_MAX_MB", "0.001")
    r.write_snapshot(path)
    r.write_snapshot(path)
    with open(path) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert len(lines) == 1               # history dropped, state kept


def test_analyze_relowers_under_the_entrys_mesh(monkeypatch):
    """A trainer on a multi-device mesh: analyze() must re-trace under
    that mesh, so the Pallas kernels' shard_map wrappers engage again —
    without it the chip's compiler refuses the re-lowered step ("Mosaic
    kernels cannot be automatically partitioned", PR 21's first
    four-chip run)."""
    import importlib

    import jax

    import chip_smoke
    from paddle_tpu.ops import set_interpret_mode
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    seen = []
    orig = fa._mesh_partition

    def spy(*a):
        seen.append(orig(*a))
        return seen[-1]

    monkeypatch.setattr(fa, "_mesh_partition", spy)
    set_interpret_mode(True)
    try:
        _, _, tr, _ = chip_smoke.run_train(
            chip_smoke.Sizes(True), {"dp": 2, "tp": 2}, jax.devices()[:4],
            2, zero2=True)
        traced = len(seen)
        assert traced and all(p is not None for p in seen)
        entry = [e for e in er.registry().entries(tr._exec_component)
                 if e.kind == "train_step"][0]
        assert er.registry().analyze(entry), entry.analysis_error
        assert len(seen) > traced and seen[-1] is not None
        assert entry.analysis["tpu_custom_calls"] == 0    # interpreted
    finally:
        set_interpret_mode(False)
