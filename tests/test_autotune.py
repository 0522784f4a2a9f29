"""Doctor-driven autotune controller (ISSUE 16).

The perf loop, contract-tested end to end:

- doctor verdicts carry MACHINE-readable actions (op/param/env/
  candidates) and the knob-axis registry resolves them — nobody
  string-parses advice;
- the greedy coordinate-descent controller converges to a planted best
  on a synthetic K-knob surface in <= K+2 trials (vs the full grid),
  never revisits a trialed (axis, value), accepts only beyond the noise
  floor, and rolls back planted regressions / recompile storms with an
  ``autotune-rollback`` flight-recorder bundle each;
- accepted winners commit to the unified tuning table WITH provenance
  (source/run/improvement) and round-trip through the on-disk table;
- the live tier is edge-triggered (one episode per SLO signal, no
  retrigger storm), quiesce-gated, hot-applies a merged prefill-bucket
  subset with ZERO recompiles on a real warmed engine, and survives an
  episode failure without killing serving;
- BENCH_rows.jsonl compaction keeps the newest rows per (run,
  candidate) and leaves sweep-resume semantics unchanged.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:                          # `import bench`
    sys.path.insert(0, REPO)

from paddle_tpu.autotune import AutotuneController, autotune_mode
from paddle_tpu.autotune.knobs import AXES, axis_for, axis_for_action
from paddle_tpu.autotune.live import (LiveRetuner, TrainerRetuner,
                                      arm_engine, arm_trainer)
from paddle_tpu.observability import doctor, flightrec
from paddle_tpu.observability.report import render_doctor, render_tuning
from paddle_tpu.utils import tuning


@pytest.fixture
def tmp_tables(tmp_path, monkeypatch):
    """Isolate the tuning table and flightrec dumps per test."""
    monkeypatch.setenv("PADDLE_TPU_TUNING_CACHE",
                       str(tmp_path / "tuning.json"))
    monkeypatch.setenv("PADDLE_TPU_FLIGHTREC_DIR",
                       str(tmp_path / "flightrec"))
    monkeypatch.delenv("PADDLE_TPU_TUNING", raising=False)
    tuning.reset_for_tests()
    yield tmp_path
    tuning.reset_for_tests()


# ---- knob-axis registry ------------------------------------------------

def test_axis_trial_values_suggested_wins_and_skips_incumbent():
    ax = AXES["remat_policy"]
    assert ax.trial_values("off") == ["dots_no_batch", "dots", "full"]
    # a doctor action's candidate list overrides the axis defaults
    assert ax.trial_values("dots", suggested=["off", "dots"]) == ["off"]


def test_axis_for_action_behavioral_and_unknown_are_none():
    assert axis_for_action(None) is None
    assert axis_for_action({"op": None, "param": None,
                            "candidates": []}) is None
    assert axis_for_action({"param": "not-a-knob"}) is None
    assert axis_for_action({"param": "quantize"}) is AXES["quantize"]
    assert axis_for("prefill_buckets").hot_apply


# ---- doctor actions (satellite 1) --------------------------------------

def test_every_rule_carries_an_action():
    for rule in doctor.RULES:
        assert rule.action is not None, rule.bottleneck


def test_doctor_verdicts_carry_structured_actions():
    v = doctor.diagnose({"comm_fraction": 0.4}, "train")
    assert v and v[0]["bottleneck"] == "comm-bound"
    a = v[0]["action"]
    assert a == {"op": "moe_a2a_chunks", "param": "moe_a2a_chunks",
                 "env": "PADDLE_TPU_MOE_A2A_CHUNKS",
                 "candidates": [1, 2, 4, 8]}


def test_spec_k_action_candidates_halve_below_current():
    v = doctor.diagnose({"spec_acceptance_rate": 0.1, "spec_k": 8},
                        "serve")
    top = [x for x in v if x["bottleneck"] == "low-spec-acceptance"][0]
    assert top["action"]["candidates"] == [4, 2, 1]


def test_behavioral_action_has_no_param():
    v = doctor.diagnose({"host_syncs_measured": 40, "steps": 10},
                        "train")
    top = [x for x in v if x["bottleneck"] == "host-sync-bound"][0]
    assert top["action"]["param"] is None
    assert axis_for_action(top["action"]) is None


def test_render_doctor_shows_action_column():
    out = render_doctor(doctor.diagnose({"comm_fraction": 0.4}, "train"))
    assert "action" in out
    assert "moe_a2a_chunks in [1,2,4,8] ->moe_a2a_chunks" in out


# ---- tuning provenance (satellite 2) -----------------------------------

def test_record_provenance_roundtrips_through_disk(tmp_tables):
    key = ("v5e", "4096")
    tuning.record("remat_policy", key, "dots", source="autotune",
                  run="r42", improvement=0.0731)
    tuning.reset_for_tests()            # force the disk read
    assert tuning.lookup("remat_policy", key) == "dots"
    meta = tuning.provenance("remat_policy", key)
    assert meta == {"source": "autotune", "run": "r42",
                    "improvement": 0.0731}


def test_record_without_provenance_and_all_entries(tmp_tables):
    tuning.record("qmm_tiles", ("cpu", "64"), [128, 128])
    assert tuning.provenance("qmm_tiles", ("cpu", "64")) is None
    tuning.record("remat_policy", ("cpu", "1"), "off", source="sweep",
                  run="r1", improvement=0.1)
    ents = tuning.all_entries()
    assert tuning.META_OP not in ents       # meta never leaks as an op
    assert set(ents) == {"qmm_tiles", "remat_policy"}


def test_report_tuning_cli_prints_provenance(tmp_tables, capsys):
    tuning.record("remat_policy", ("cpu", "64"), "dots_no_batch",
                  source="autotune", run="r06", improvement=0.05)
    from paddle_tpu.observability.report import main as report_main
    assert report_main(["--tuning"]) == 0
    out = capsys.readouterr().out
    assert "tuning table" in out
    for frag in ("remat_policy", "autotune", "r06", "+5.00%"):
        assert frag in out
    assert "dots_no_batch" in out


# ---- controller convergence (tentpole + satellite 4) -------------------

BEST = {"quantize": "int8", "remat_policy": "off", "overlap": True,
        "prefetch_depth": 4, "scan": True}
START = {"quantize": None, "remat_policy": "dots_no_batch",
         "overlap": False, "prefetch_depth": 2, "scan": True}


def _objective(cfg):
    mfu = 0.30
    mfu += 0.05 if cfg["quantize"] == "int8" else 0.0
    mfu += 0.04 if cfg["remat_policy"] == "off" else 0.0
    mfu += 0.03 if cfg["overlap"] else 0.0
    if cfg["prefetch_depth"] == 4:
        mfu += 0.02
    elif cfg["prefetch_depth"] == 0:
        mfu -= 0.20                     # planted regression trial
    return round(mfu, 6)


def _verdicts(cfg):
    v = []
    if cfg["quantize"] != "int8":
        v.append({"bottleneck": "mfu-below-target", "score": 0.9,
                  "action": {"op": "qmm_tiles", "param": "quantize",
                             "env": "BENCH_QUANTIZE",
                             "candidates": ["int8"]}})
    if cfg["remat_policy"] != "off":
        v.append({"bottleneck": "mfu-below-target", "score": 0.8,
                  "action": {"op": "remat_policy",
                             "param": "remat_policy", "env": None,
                             "candidates": ["off"]}})
    if not cfg["overlap"]:
        v.append({"bottleneck": "comm-bound", "score": 0.7,
                  "action": {"op": None, "param": "overlap",
                             "env": "PADDLE_TPU_OVERLAP",
                             "candidates": [True]}})
    if cfg["prefetch_depth"] != 4:
        v.append({"bottleneck": "data-starved", "score": 0.6,
                  "action": {"op": None, "param": "prefetch_depth",
                             "env": "PADDLE_TPU_PREFETCH_DEPTH",
                             "candidates": [0, 4]}})
    # behavioral advice the controller must skip, ranked above the bait
    v.append({"bottleneck": "host-sync-bound", "score": 0.55,
              "action": {"op": None, "param": None, "env": None,
                         "candidates": []}})
    # bait: trialing scan=False recompile-storms (see _measure)
    v.append({"bottleneck": "mfu-below-target", "score": 0.5,
              "action": {"op": None, "param": "scan", "env": None,
                         "candidates": [False]}})
    return v


def _measure(cfg):
    return {"mfu": _objective(cfg), "doctor": _verdicts(cfg),
            "xla_compiles_measured": 7 if cfg["scan"] is False else 0}


def _controller(tmp_tables, **over):
    kw = dict(kind="train", objective_key="mfu", noise_floor=0.02,
              run_id="t-run",
              commit_keys={"remat_policy":
                           ("remat_policy", ("t", "64", "2", "32"))},
              axes=["quantize", "remat_policy", "overlap",
                    "prefetch_depth", "scan"])
    kw.update(over)
    return AutotuneController(_measure, **kw)


def test_controller_converges_in_O_knobs_not_grid(tmp_tables):
    ctl = _controller(tmp_tables)
    s = ctl.run(dict(START))
    assert {k: s["config"][k] for k in BEST} == BEST
    k = len(START)
    grid = 2 * 4 * 2 * 3 * 2
    assert s["measured_trials"] <= k + 2 < grid
    assert s["converged"] and s["accepted"] == 4
    assert s["best"] == pytest.approx(0.44)
    assert s["improvement"] > 0.4


def test_controller_never_revisits_and_accepts_beyond_noise(tmp_tables):
    ctl = _controller(tmp_tables)
    s = ctl.run(dict(START))
    pairs = [(t["axis"], repr(t["value"])) for t in s["trials"]]
    assert len(pairs) == len(set(pairs))
    for t in s["trials"]:
        if t["outcome"] == "accept":
            assert t["improvement"] > ctl.noise_floor


def test_controller_rolls_back_regression_and_storm(tmp_tables):
    ctl = _controller(tmp_tables)
    s = ctl.run(dict(START))
    rb = {t["reason"]: t for t in s["trials"]
          if t["outcome"] == "rollback"}
    assert set(rb) == {"regression", "recompile-storm"}
    assert rb["regression"]["axis"] == "prefetch_depth"
    assert rb["regression"]["value"] == 0
    assert rb["recompile-storm"]["axis"] == "scan"
    # every rollback shipped an evidence bundle
    frdir = str(tmp_tables / "flightrec")
    bundles = [b for b in flightrec.find_bundles(frdir)
               if b.endswith("autotune-rollback")]
    assert len(bundles) == 2
    info = flightrec.load_bundle(bundles[0])["bundle"]
    assert info["autotune"]["run"] == "t-run"
    assert info["autotune"]["reason"] in ("regression",
                                          "recompile-storm")


def test_controller_zero_compiles_outside_trials(tmp_tables):
    s = _controller(tmp_tables).run(dict(START))
    assert s["compiles_outside_trials"] == 0


def test_controller_commits_winner_with_provenance(tmp_tables):
    s = _controller(tmp_tables).run(dict(START))
    assert any(c["op"] == "remat_policy" for c in s["committed"])
    tuning.reset_for_tests()            # fresh process stand-in
    key = ("t", "64", "2", "32")
    assert tuning.lookup("remat_policy", key) == "off"
    meta = tuning.provenance("remat_policy", key)
    assert meta["source"] == "autotune" and meta["run"] == "t-run"
    assert meta["improvement"] > 0


def test_controller_minimize_direction(tmp_tables):
    def measure(cfg):
        ms = 10.0 - (3.0 if cfg["kv_dtype"] == "int8" else 0.0)
        return {"ttft_ms": ms, "doctor": [
            {"bottleneck": "kv-pressure", "score": 0.9,
             "action": {"op": None, "param": "kv_dtype",
                        "env": None, "candidates": ["int8"]}}]
            if cfg["kv_dtype"] == "dense" else []}
    ctl = AutotuneController(measure, kind="serve",
                             objective_key="ttft_ms", maximize=False,
                             noise_floor=0.02, axes=["kv_dtype"])
    s = ctl.run({"kv_dtype": "dense"})
    assert s["config"]["kv_dtype"] == "int8"
    assert s["improvement"] == pytest.approx(0.3)


def test_controller_error_trial_rolls_back(tmp_tables):
    calls = {"n": 0}

    def measure(cfg):
        calls["n"] += 1
        if cfg.get("overlap"):
            raise RuntimeError("watchdog: stalled")
        return {"mfu": 0.3, "doctor": [
            {"bottleneck": "comm-bound", "score": 0.7,
             "action": {"op": None, "param": "overlap", "env": None,
                        "candidates": [True]}}]}
    ctl = AutotuneController(measure, kind="train", noise_floor=0.02,
                             axes=["overlap"])
    s = ctl.run({"overlap": False})
    t = s["trials"][0]
    assert t["outcome"] == "rollback" and t["reason"] == "error"
    assert "watchdog" in t["error"]
    assert s["config"] == {"overlap": False}    # incumbent kept


def test_controller_missing_objective_is_an_error(tmp_tables):
    s = AutotuneController(lambda cfg: {"rows": []},
                           axes=["overlap"]).run({"overlap": False})
    assert "error" in s and s["measured_trials"] == 0


# ---- live tier: LiveRetuner unit (tentpole, live rails) ----------------

class FakeEngine:
    kv_layout = "dense"
    max_seq_len = 64
    batch_slots = 2

    def __init__(self, buckets=(8, 16, 64)):
        self.buckets = sorted(buckets)
        self._queue = []
        self.num_active = 0


def test_notify_slo_edge_trigger_no_retrigger_storm():
    r = LiveRetuner(FakeEngine())
    healthy = {"regressed": False, "breached": False}
    bad = {"regressed": True, "breached": False, "p99_ms": 99.0}
    assert r.notify_slo(healthy) is False
    assert r.notify_slo(bad) is True        # edge: schedules ONE episode
    for _ in range(10):                     # still-regressed rescrapes
        assert r.notify_slo(bad) is False   # do NOT retrigger
    assert r._pending
    assert r.notify_slo(healthy) is False   # healthy resets the latch


def test_notify_slo_cooldown_bounds_episode_rate():
    import time as _time
    r = LiveRetuner(FakeEngine(), cooldown_s=3600.0)
    r._last_episode_t = _time.monotonic()   # an episode just ran
    bad = {"regressed": True}
    assert r.notify_slo(bad) is False       # inside cooldown: suppressed
    r2 = LiveRetuner(FakeEngine(), cooldown_s=0.0)
    r2._last_episode_t = _time.monotonic()
    assert r2.notify_slo(bad) is True


def test_on_tick_quiesce_gate(monkeypatch):
    eng = FakeEngine()
    r = LiveRetuner(eng)
    ran = []
    monkeypatch.setattr(r, "_episode", lambda: ran.append(1))
    assert r.on_tick() is False             # nothing pending: O(1) no-op
    r.notify_slo({"regressed": True})
    eng.num_active = 1
    assert r.on_tick() is False and r._pending      # busy: deferred
    eng.num_active, eng._queue = 0, ["queued"]
    assert r.on_tick() is False and r._pending      # queued: deferred
    eng._queue = []
    assert r.on_tick() is True and not r._pending   # quiesced: runs
    assert ran == [1]


def test_episode_hot_applies_merged_subset(tmp_tables, monkeypatch):
    eng = FakeEngine([8, 16, 64])
    r = LiveRetuner(eng)
    # bucket 8's executable measures SLOWER than 16's (the live
    # regression story): pad-up rule drops it, mean cost improves
    times = {8: 2.0, 16: 1.0, 64: 5.0}
    monkeypatch.setattr(r, "_time_buckets", lambda bs: dict(times))
    r._pending = True
    assert r.on_tick() is True
    assert eng.buckets == [16, 64]          # hot-applied subset
    assert r.applied and r.applied[0]["improvement"] > 0.02
    # winner persisted with live-autotune provenance
    tuning.reset_for_tests()
    assert tuning.lookup("prefill_buckets", ("cpu", 64)) == [16, 64]
    meta = tuning.provenance("prefill_buckets", ("cpu", 64))
    assert meta["source"] == "autotune" and meta["run"] == "live-1"


def test_episode_within_noise_is_a_noop(tmp_tables, monkeypatch):
    eng = FakeEngine([8, 64])
    r = LiveRetuner(eng)
    # healthy bucket spacing: merging would RAISE the mean cost, so the
    # incumbent list must survive
    monkeypatch.setattr(r, "_time_buckets",
                        lambda bs: {8: 1.0, 64: 5.0})
    r._pending = True
    r.on_tick()
    assert eng.buckets == [8, 64] and not r.applied


def test_episode_error_rolls_back_and_serving_survives(tmp_tables,
                                                       monkeypatch):
    eng = FakeEngine()
    r = LiveRetuner(eng)

    def boom(bs):
        raise RuntimeError("no free blocks for trial")
    monkeypatch.setattr(r, "_time_buckets", boom)
    r._pending = True
    assert r.on_tick() is True              # the failure is CONTAINED
    assert eng.buckets == [8, 16, 64]       # incumbent kept
    frdir = str(tmp_tables / "flightrec")
    bundles = [b for b in flightrec.find_bundles(frdir)
               if b.endswith("autotune-rollback")]
    assert len(bundles) == 1
    info = flightrec.load_bundle(bundles[0])["bundle"]
    assert info["autotune"]["tier"] == "live"


def test_merge_matches_offline_pad_up_rule():
    # same keep rule as bench.py's _sweep_prefill_buckets: keep b iff
    # times[b] < times[next_kept] / 1.25
    times = {8: 1.0, 16: 1.1, 32: 2.0, 64: 5.0}
    kept = LiveRetuner._merge([8, 16, 32, 64], times)
    ref = [64]
    for b in (32, 16, 8):
        if times[b] < times[ref[0]] / 1.25:
            ref.insert(0, b)
    assert kept == ref == [16, 32, 64]


def test_arm_gating_follows_env(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_AUTOTUNE", raising=False)
    assert autotune_mode() == "off"
    assert arm_engine(FakeEngine()) is None
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "once")
    assert arm_engine(FakeEngine()) is None
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "live")
    assert autotune_mode() == "live"
    assert isinstance(arm_engine(FakeEngine()), LiveRetuner)


# ---- live tier: trainer advisory ---------------------------------------

class FakeTrainer:
    _timings = {"dispatch_ms": 100.0, "sync_ms": 900.0,
                "data_wait_ms": 0.0, "steps_timed": 64}


def test_trainer_retuner_one_advisory_per_regression(tmp_tables,
                                                     monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "live")
    r = arm_trainer(FakeTrainer())
    assert isinstance(r, TrainerRetuner)
    r.window, r.cooldown_steps = 4, 0
    fired = [r.on_step(10.0) for _ in range(8)]     # healthy baseline
    assert not any(fired)
    fired = [r.on_step(30.0) for _ in range(8)]     # sustained 3x
    assert sum(fired) == 1                  # ONE episode, latch holds
    assert r.episodes == 1
    advice = r.last_advice
    assert advice and advice[0]["bottleneck"] == "host-sync-bound"
    assert advice[0]["action"]["param"] is None     # behavioral


# ---- live tier: real engine contract (zero-recompile hot-apply) --------

@pytest.fixture(scope="module")
def live_engine():
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.inference import InferenceEngine
    os.environ["PADDLE_TPU_AUTOTUNE"] = "live"
    try:
        paddle.seed(0)
        m = GPTForCausalLM(GPTConfig(
            vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
            max_seq_len=64, use_flash_attention=False))
        m.eval()
        eng = InferenceEngine(m, batch_slots=2, prefill_buckets=[8, 16])
        eng.warmup(eng.buckets)
        yield eng
    finally:
        os.environ.pop("PADDLE_TPU_AUTOTUNE", None)
        # an episode that merges [8, 16] into [16] records it for
        # (cpu, 64) in the process's tuning cache: every later engine of
        # this worker with max_seq_len 64 and default buckets would then
        # refuse a 20-token prompt
        tuning.reset_for_tests()


def test_live_engine_is_armed_and_episode_is_compile_free(live_engine):
    from paddle_tpu.utils import compile_counter
    eng = live_engine
    r = eng._retuner
    assert isinstance(r, LiveRetuner)
    assert r.notify_slo({"regressed": True, "p99_ms": 50.0})
    old = list(eng.buckets)
    with compile_counter.assert_no_recompiles(
            "live autotune episode", traces=True):
        ran = r.on_tick()               # engine.step() calls this hook
    assert ran and r.episodes == 1
    # hot-apply contract: the (possibly) merged list is a SUBSET of the
    # warmed buckets with the capacity bucket intact
    assert set(eng.buckets) <= set(old)
    assert eng.buckets[-1] == old[-1]


def test_live_engine_still_serves_after_episode(live_engine):
    out = live_engine.generate(np.arange(5, dtype=np.int32),
                               max_new_tokens=4)
    assert len(np.asarray(out).reshape(-1)) > 0


def test_slo_monitor_feeds_retuner_listener():
    from paddle_tpu.observability.slo import SLOMonitor
    r = LiveRetuner(FakeEngine())
    mon = SLOMonitor(ttft_p99_ms=1.0,
                     baseline_ttft_p99_ms=1.0).add_listener(r.notify_slo)
    for _ in range(8):
        mon.observe(100.0)              # way over target AND baseline
    verdict = mon.check()
    assert verdict["breached"] and verdict["regressed"]
    assert r._pending                   # the signal reached the retuner


# ---- rows compaction (satellite 3) -------------------------------------

def test_compact_rows_keeps_newest_per_key_resume_unchanged(
        tmp_path, monkeypatch):
    import bench
    path = str(tmp_path / "rows.jsonl")
    monkeypatch.setenv("BENCH_ROWS_FILE", path)
    monkeypatch.setenv("BENCH_RUN", "r-compact")
    monkeypatch.setenv("BENCH_RESUME", "1")
    base = dict(kind="train", run="r-compact", config="gpt3-tiny",
                batch=2, seq=64, use_flash=False, remat=False,
                remat_policy=None, scan_layers=True, overlap=True,
                quantize=None)
    with open(path, "w") as f:
        for i in range(40):             # 40 rewrites of the SAME key
            f.write(json.dumps({**base, "mfu": float(i),
                                "pad": "x" * 256}) + "\n")
        f.write(json.dumps({**base, "quantize": "int8",
                            "mfu": 7.0}) + "\n")
    before = bench._measured_rows("train")
    assert len(before) == 2
    assert before[bench._train_row_key(base)]["mfu"] == 39.0
    assert bench._compact_rows(path, max_bytes=4096, keep_per_key=4)
    # newest N per (run, candidate) survive; resume sees the SAME rows
    with open(path) as f:
        kept = [json.loads(l) for l in f]
    dup = [r for r in kept if r.get("quantize") is None]
    assert len(dup) <= 4
    assert dup[-1]["mfu"] == 39.0
    after = bench._measured_rows("train")
    assert set(after) == set(before)
    assert after[bench._train_row_key(base)]["mfu"] == 39.0
    # int8 row (different candidate key) survived the purge
    assert any(r.get("quantize") == "int8" for r in kept)


def test_compact_rows_noop_under_budget(tmp_path):
    import bench
    path = str(tmp_path / "rows.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "smoke", "metric": "m"}) + "\n")
    assert bench._compact_rows(path, max_bytes=1 << 20) is False


# ---- bench sweep resume ------------------------------------------------

def _bench_module():
    import importlib
    import bench
    return importlib.reload(bench)


def test_bench_resume_matches_persisted_rows(tmp_path, monkeypatch):
    """_persist_row tags rows with the run id and _measured_rows only
    returns rows whose (run, candidate identity) matches — the rerun
    after a late transient failure re-measures only the tail."""
    rows = tmp_path / "rows.jsonl"
    monkeypatch.setenv("BENCH_ROWS_FILE", str(rows))
    monkeypatch.setenv("BENCH_RUN", "r06")
    monkeypatch.delenv("BENCH_RECOMPUTE", raising=False)
    monkeypatch.delenv("BENCH_QUANTIZE", raising=False)
    monkeypatch.delenv("BENCH_SCAN_LAYERS", raising=False)
    monkeypatch.delenv("PADDLE_TPU_OVERLAP", raising=False)
    bench = _bench_module()
    row = {"config": "gpt3-125m", "batch": 8, "seq": 2048,
           "use_flash": True, "remat": False, "remat_policy": "off",
           "scan_layers": True, "overlap": True, "quantize": "int8",
           "mfu": 0.40, "step_ms": 10.0, "pathological": False}
    bench._persist_row(row, kind="train")
    measured = bench._measured_rows("train")
    spec = dict(config="gpt3-125m", batch=8, seq=2048, flash=True,
                remat=False, quantize="int8")
    assert bench._candidate_key(spec) in measured
    assert measured[bench._candidate_key(spec)]["mfu"] == 0.40
    # a different candidate (fp) must NOT match
    other = dict(spec, quantize="off")
    assert bench._candidate_key(other) not in measured
    # rows from another run are invisible
    monkeypatch.setenv("BENCH_RUN", "r07")
    assert bench._measured_rows("train") == {}
    # no run id => resume disabled entirely
    monkeypatch.setenv("BENCH_RUN", "")
    assert bench._measured_rows("train") == {}


def test_bench_resume_serve_rows(tmp_path, monkeypatch):
    rows = tmp_path / "rows.jsonl"
    monkeypatch.setenv("BENCH_ROWS_FILE", str(rows))
    monkeypatch.setenv("BENCH_RUN", "r06")
    bench = _bench_module()
    row = {"config": "gpt3-125m", "batch_slots": 8, "kv_dtype": "dense",
           "prompt_len": 128, "gen_tokens": 64, "value": 900.0}
    bench._persist_row(row, kind="serve")
    measured = bench._measured_rows("serve")
    # tp (ISSUE 18), ep (ISSUE 19) and prefill_chunk (ISSUE 20) joined
    # the candidate key: a row without the columns resumes as the
    # tp=1/ep=1/monolithic candidate; a tp=2, ep=2 or chunked row is a
    # DIFFERENT point
    key = ("serve", "gpt3-125m", 8, "dense", 128, 64, 1, 1, 0)
    assert key in measured and measured[key]["value"] == 900.0
    assert ("serve", "gpt3-125m", 8, "int8", 128, 64, 1, 1, 0) \
        not in measured
    assert ("serve", "gpt3-125m", 8, "dense", 128, 64, 2, 1, 0) \
        not in measured
    assert ("serve", "gpt3-125m", 8, "dense", 128, 64, 1, 2, 0) \
        not in measured
    assert ("serve", "gpt3-125m", 8, "dense", 128, 64, 1, 1, 64) \
        not in measured


# ---- tuning-table nearest-shape fallbacks ------------------------------

def test_qmm_tiles_nearest_shape_fallback(tmp_tables):
    from paddle_tpu.ops.quantized_matmul import get_qmm_tiles
    kind = tuning.device_kind()
    tuning.record("qmm_tiles", (kind, 1024, 512, 256, "int8"),
                   [64, 128, 128])
    # exact hit
    assert get_qmm_tiles(1024, 512, 256) == (64, 128, 128)
    # near miss (m bucket 2048, same n/k): nearest entry serves,
    # clamped — NOT the (256, 256, 256) hard defaults
    assert get_qmm_tiles(2048, 512, 256) == (64, 128, 128)
    # different n/k within log-distance still beats hard defaults
    assert get_qmm_tiles(1024, 256, 256) == (64, 128, 128)


def test_flash_blocks_nearest_seq_from_unified_table(tmp_tables,
                                                     monkeypatch):
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    monkeypatch.delenv("PADDLE_TPU_FLASH_AUTOTUNE_CACHE", raising=False)
    monkeypatch.setenv("PADDLE_TPU_FLASH_AUTOTUNE", "1")
    kind = tuning.device_kind()
    saved = dict(fa._SWEEP_CACHE)
    fa._SWEEP_CACHE.clear()
    fa._SWEEP_STORE_STATE["loaded"] = False
    try:
        tuning.record("flash_blocks", (kind, 1024, 64, True),
                       [256, 256])
        # seq 512 has no exact entry anywhere on CPU: the swept 1024
        # entry is the nearest and must serve (defaults are 512/512)
        assert fa.get_block_sizes(512, 64, True) == (256, 256)
    finally:
        fa._SWEEP_CACHE.clear()
        fa._SWEEP_CACHE.update(saved)
        fa._SWEEP_STORE_STATE["loaded"] = False


def test_tuned_remat_policy_consumed(tmp_tables):
    from paddle_tpu.distributed.spmd import tuned_remat_policy

    class _Cfg:
        hidden_size, num_layers, max_seq_len = 128, 2, 64

    class _Model:
        cfg = _Cfg()

    kind = tuning.device_kind()
    assert tuned_remat_policy(_Model()) is None
    tuning.record("remat_policy", (kind, 128, 2, 64), "dots_no_batch")
    assert tuned_remat_policy(_Model()) == "dots_no_batch"
    # nearest shape serves a near-miss model
    _Cfg.hidden_size = 256
    assert tuned_remat_policy(_Model()) == "dots_no_batch"
    # 'off' entries mean "winner ran without remat": ignored
    tuning.record("remat_policy", (kind, 256, 2, 64), "off")
    assert tuned_remat_policy(_Model()) is None


# ---- bench CLI wiring (satellite 6 + acceptance) -----------------------

def test_bench_autotune_smoke_cli(tmp_path):
    """`python bench.py --autotune --smoke` end to end: the controller
    drives real bench_train measurements on CPU and exits 0 with the
    one-line summary row (zero compiles outside trial windows)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "BENCH_RUN": "pytest-autotune",
           "BENCH_ROWS_FILE": str(tmp_path / "rows.jsonl")}
    p = subprocess.run([sys.executable, "bench.py", "--autotune",
                        "--smoke"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=560)
    assert p.returncode == 0, p.stdout + p.stderr
    row = json.loads(p.stdout.strip().splitlines()[-1])
    assert row["metric"] == "autotune_train_mfu"
    assert row["run"] == "pytest-autotune"
    assert row["compiles_outside_trials"] == 0
    # the summary row itself persisted for the next resume
    kinds = [json.loads(l).get("kind")
             for l in open(tmp_path / "rows.jsonl")]
    assert "autotune" in kinds
