"""Flight recorder + stall watchdog + perf doctor (ISSUE 14).

Done criteria exercised here:
- a subprocess killed mid-train (SIGTERM fault) and a NAN-rollback run
  both leave a VALID flight-recorder bundle whose Chrome trace
  validates;
- the ring is bounded: memory does not grow with step count;
- a deterministically injected stall (PADDLE_FAULT_HANG) is detected
  by the watchdog within the configured window and the bundle carries
  all-thread stacks;
- the perf doctor emits the expected knob verdict on synthetic
  comm-bound / host-sync-bound / data-starved fixtures, stays silent
  on a clean one, and its field rides trainer/engine stats and the
  loadgen reports;
- straggler detection flags tick-time skew vs the fleet median.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import observability as obs
from paddle_tpu.distributed import SpmdTrainer, create_mesh
from paddle_tpu.inference import InferenceEngine
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import doctor, flightrec, watchdog
from paddle_tpu.observability.flightrec import (FlightRecorder,
                                                find_bundles,
                                                load_bundle)
from paddle_tpu.observability.watchdog import Watchdog, detect_stragglers
from paddle_tpu.testing import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    # the per-process dump cap is shared with every other test file
    # (in-process SIGTERM tests dump too); these tests assert on dumps,
    # so they start from a clean budget
    flightrec.recorder().dumps = 0
    yield
    faults.reset()


def _linear_trainer(seed=0, **kw):
    paddle.seed(seed)
    m = nn.Linear(6, 3)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=m.parameters())
    return SpmdTrainer(m, opt, lambda o, y: F.mse_loss(o, y),
                       mesh=create_mesh({"dp": 1}), **kw)


def _batch(seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(4, 6).astype(np.float32),
            rng.randn(4, 3).astype(np.float32))


def tiny_model(seed=0):
    cfg = GPTConfig(vocab_size=97, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=64,
                    use_flash_attention=False)
    paddle.seed(seed)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


# ---------------------------------------------------------------------------
# ring + bundle mechanics
# ---------------------------------------------------------------------------
def test_ring_is_bounded_memory_does_not_grow_with_steps():
    rec = FlightRecorder(ring=32, events=8)
    for i in range(10_000):
        rec.record("step", dur_ms=1.0, step=i)
        if i % 100 == 0:
            rec.note_event("mark", i=i)
    assert len(rec.ring) == 32
    assert len(rec.events) == 8
    # the ring holds the TAIL (the last steps before death)
    assert rec.ring[-1]["step"] == 9_999
    assert rec.ring[0]["step"] == 9_968


def test_dump_is_atomic_and_loads_back(tmp_path):
    rec = FlightRecorder(ring=16)
    for i in range(20):
        rec.record("tick", dur_ms=0.5, tick=i)
    rec.note_event("checkpoint_save", path="/x")
    path = rec.dump("unittest", directory=str(tmp_path))
    assert path is not None and os.path.isdir(path)
    # no .tmp staging orphan survives the rename
    assert [p for p in os.listdir(tmp_path) if p.endswith(".tmp")] == []
    doc = load_bundle(path)
    assert doc["bundle"]["reason"] == "unittest"
    assert len(doc["bundle"]["ring"]) == 16
    assert any(e["kind"] == "checkpoint_save"
               for e in doc["bundle"]["events"])
    # every live thread left a stack in the bundle
    assert doc["bundle"]["stacks"]
    # the chrome trace validates and carries the ring-synthesized spans
    n = obs.validate_chrome_trace(doc["trace"])
    assert n > 0
    names = {e["name"] for e in doc["trace"]["traceEvents"]}
    assert "tick" in names
    assert find_bundles(str(tmp_path)) == [path]


def test_dump_cap_bounds_bundle_count(tmp_path):
    rec = FlightRecorder(ring=4)
    paths = [rec.dump("spam", directory=str(tmp_path))
             for _ in range(flightrec._MAX_DUMPS + 5)]
    written = [p for p in paths if p]
    assert len(written) == flightrec._MAX_DUMPS


def test_disabled_recorder_records_and_dumps_nothing(tmp_path,
                                                    monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLIGHTREC", "0")
    before = len(flightrec.recorder().ring)
    flightrec.record("tick", tick=1)
    assert len(flightrec.recorder().ring) == before
    assert flightrec.dump("off", directory=str(tmp_path)) is None
    assert os.listdir(tmp_path) == []


def test_trainer_and_engine_feed_the_ring():
    rec = flightrec.recorder()
    tr = _linear_trainer()
    x, y = _batch()
    for _ in range(3):
        tr.train_step(x, y)
    kinds = [e["kind"] for e in rec.ring]
    assert kinds.count("train_step") >= 3
    eng = InferenceEngine(tiny_model(), batch_slots=2,
                          prefill_buckets=[16])
    eng.warmup(buckets=[16])
    rng = np.random.RandomState(0)
    eng.add_request(rng.randint(1, 97, (5,)).astype(np.int32),
                    max_new_tokens=4)
    eng.run()
    kinds = [e["kind"] for e in rec.ring]
    assert "decode_tick" in kinds


class _BombNet(nn.Layer):
    """Loss explodes when an input row carries the sentinel value — a
    DATA-keyed anomaly (rollback rewinds the step counter, so a
    step-keyed injection would re-arm forever; same construction as
    test_resilience)."""

    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(4, 2)

    def forward(self, x):
        out = self.fc(x)
        mask = (x > 900.0).astype("float32").max()
        return out * (1.0 + mask * 3.0e38)


def test_rollback_leaves_a_bundle(tmp_path, monkeypatch):
    """anomaly_policy='rollback' on a poisoned batch: the rollback dump
    trigger fires IN-PROCESS with the pre-rewind state."""
    monkeypatch.setenv("PADDLE_TPU_FLIGHTREC_DIR", str(tmp_path))
    paddle.seed(13)
    model = _BombNet()
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=model.parameters())
    tr = SpmdTrainer(model, opt, lambda o, y: F.mse_loss(o, y),
                     mesh=create_mesh({"dp": 1}),
                     anomaly_policy="rollback")
    rng = np.random.RandomState(9)
    bomb = np.full((4, 4), 1000.0, np.float32)
    for i in range(3):
        x = bomb if i == 1 else rng.randn(4, 4).astype(np.float32)
        tr.train_step(x, rng.randn(4, 2).astype(np.float32))
    assert tr.stats["rollback_steps"] == 1
    bundles = find_bundles(str(tmp_path), reason="rollback")
    assert len(bundles) == 1
    doc = load_bundle(bundles[0])
    assert any(e["kind"] == "anomaly_rollback"
               for e in doc["bundle"]["events"])
    obs.validate_chrome_trace(doc["trace"])


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------
def test_watchdog_detects_injected_train_stall(tmp_path, monkeypatch):
    """PADDLE_FAULT_HANG stalls the train thread; the watchdog fires
    within the configured window and the bundle carries every thread's
    stack (the stalled one shows the injected sleep)."""
    monkeypatch.setenv("PADDLE_TPU_WATCHDOG_S", "0.25")
    monkeypatch.setenv("PADDLE_TPU_FLIGHTREC_DIR", str(tmp_path))
    monkeypatch.setenv("PADDLE_FAULT_HANG", "2:1.2")
    tr = _linear_trainer()
    x, y = _batch()
    for _ in range(3):
        tr.train_step(x, y)
    wd = tr.watchdog
    assert wd is not None
    try:
        # >= 1: a slow first-step compile on a loaded CI host may trip
        # the 0.25s window once on its own; the LAST stall is the hang
        assert wd.stalls >= 1
        assert wd.last_stall["label"] == "spmd_train"
        # detection happened within ~1.25x the window, i.e. DURING the
        # 1.2s hang, not after it (age at detection < hang length)
        assert wd.last_stall["age_s"] < 1.2
        stacks = "".join(s for frames in wd.last_stall["stacks"].values()
                         for s in frames)
        assert "maybe_hang" in stacks
        bundles = find_bundles(str(tmp_path), reason="stall")
        assert bundles
        doc = load_bundle(bundles[-1])
        assert doc["bundle"]["stall"]["label"] == "spmd_train"
        assert doc["bundle"]["stacks"]
        obs.validate_chrome_trace(doc["trace"])
    finally:
        wd.disarm()


def test_watchdog_detects_decode_tick_stall(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_WATCHDOG_S", "0.25")
    monkeypatch.setenv("PADDLE_TPU_FLIGHTREC_DIR", str(tmp_path))
    monkeypatch.setenv("PADDLE_FAULT_HANG", "3:1.0")
    eng = InferenceEngine(tiny_model(), batch_slots=2,
                          prefill_buckets=[16])
    eng.warmup(buckets=[16])
    rng = np.random.RandomState(0)
    eng.add_request(rng.randint(1, 97, (5,)).astype(np.int32),
                    max_new_tokens=8)
    eng.run()
    wd = eng.watchdog
    assert wd is not None
    try:
        assert wd.stalls >= 1
        assert find_bundles(str(tmp_path), reason="stall")
    finally:
        wd.disarm()


def test_watchdog_idle_engine_is_not_a_stall(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_WATCHDOG_S", "0.4")
    eng = InferenceEngine(tiny_model(), batch_slots=2,
                          prefill_buckets=[16])
    eng.warmup(buckets=[16])
    rng = np.random.RandomState(0)
    eng.add_request(rng.randint(1, 97, (5,)).astype(np.int32),
                    max_new_tokens=4)
    eng.run()
    wd = eng.watchdog
    assert wd is not None
    try:
        # the run's last tick left the engine empty -> watchdog parked:
        # sitting idle for > timeout must NOT count as a stall
        time.sleep(1.0)
        assert wd.stalls == 0
        # traffic re-arms it
        eng.add_request(rng.randint(1, 97, (5,)).astype(np.int32),
                        max_new_tokens=2)
        eng.run()
        assert wd.stalls == 0
    finally:
        wd.disarm()


def test_watchdog_parked_by_save_and_beaten_by_eval(tmp_path,
                                                    monkeypatch):
    """A finished training loop must not read as a stall: the final
    checkpoint save parks the trainer's watchdog, and eval steps
    heartbeat it — a train -> save -> (slow tail) sequence stays
    clean."""
    monkeypatch.setenv("PADDLE_TPU_WATCHDOG_S", "0.3")
    tr = _linear_trainer()
    x, y = _batch()
    for _ in range(2):
        tr.train_step(x, y)
    wd = tr.watchdog
    assert wd is not None
    try:
        tr.save(str(tmp_path / "ck"))      # snapshot parks the watchdog
        time.sleep(0.8)                    # post-training tail > window
        assert wd.stalls == 0
        tr.eval_step(x)                    # eval heartbeats, no false arm
        time.sleep(0.1)
        assert wd.stalls == 0
    finally:
        wd.disarm()


def test_watchdog_custom_callback_and_rearm():
    fired = []
    wd = Watchdog(0.1, label="t", on_stall=fired.append,
                  poll_s=0.02).arm()
    try:
        wd.beat()
        time.sleep(0.3)
        assert len(fired) == 1          # once per episode, not per poll
        assert fired[0]["label"] == "t"
        wd.beat()                       # new episode
        time.sleep(0.3)
        assert len(fired) == 2
    finally:
        wd.disarm()


def test_watchdog_validates_args():
    with pytest.raises(ValueError):
        Watchdog(0)
    with pytest.raises(ValueError):
        Watchdog(1.0, on_stall="explode")
    assert watchdog.watchdog_seconds() is None


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------
def test_detect_stragglers_flags_skew_vs_median():
    v = detect_stragglers([10.0, 11.0, 10.5, 40.0], factor=1.75)
    assert v["stragglers"] == [3]
    assert v["median_ms"] == pytest.approx(10.75)
    assert v["ratio"][3] == pytest.approx(40.0 / 10.75, abs=1e-3)
    # healthy fleet: nobody flagged
    assert detect_stragglers([10.0, 11.0, 12.0])["stragglers"] == []
    # None (no ticks) replicas are skipped, indexes stay aligned;
    # flagging is leave-one-out, so a 2-valid-replica fleet still
    # catches its slow member (overall median would hide it)
    v = detect_stragglers([None, 10.0, 50.0])
    assert v["stragglers"] == [2] and v["per_replica_ms"][0] is None
    # empty / all-None / single-replica input: empty verdict, no crash
    assert detect_stragglers([])["stragglers"] == []
    assert detect_stragglers([None, None])["median_ms"] is None
    assert detect_stragglers([99.0])["stragglers"] == []


def test_fleet_aggregator_surfaces_stragglers():
    class _R:
        def __init__(self, ms):
            self.request_stats = {}
            self._queue = []
            self.num_active = 0
            self._request_stats_cap = 16
            self._timings = {"decode_ms": ms * 10, "decode_steps": 10}

    agg = obs.FleetAggregator([_R(10.0), _R(11.0), _R(60.0)])
    out = agg.scrape()
    assert out["straggler"]["stragglers"] == [2]
    assert agg.stragglers()["stragglers"] == [2]
    snap = obs.metrics.snapshot()
    series = {s["labels"]["replica"]: s["value"]
              for s in snap["fleet_tick_ms"]["series"]}
    assert series["2"] == pytest.approx(60.0)
    # a replica with a PARTIAL timing surface (decode_steps but no
    # decode_ms) reads as None, not a KeyError out of scrape()
    broken = _R(10.0)
    del broken._timings["decode_ms"]
    agg2 = obs.FleetAggregator([broken, _R(12.0)])
    assert agg2.scrape()["straggler"]["per_replica_ms"][0] is None


# ---------------------------------------------------------------------------
# perf doctor
# ---------------------------------------------------------------------------
def test_doctor_comm_bound_fixture():
    v = doctor.diagnose(
        {"comm_fraction": 0.41,
         "comm_by_op": {"all-reduce": {"count": 4, "bytes": 1 << 20},
                        "all-gather": {"count": 2, "bytes": 1 << 10}}},
        kind="train")
    assert v and v[0]["bottleneck"] == "comm-bound"
    assert v[0]["evidence"]["comm_fraction"] == 0.41
    assert v[0]["evidence"]["top_op"] == "all-reduce"
    assert "PADDLE_TPU_OVERLAP" in v[0]["knob"]
    assert "a2a_chunks" in v[0]["knob"]


def test_doctor_host_sync_bound_fixture():
    v = doctor.diagnose({"host_syncs_measured": 20, "steps": 10},
                        kind="train")
    assert v and v[0]["bottleneck"] == "host-sync-bound"
    assert v[0]["evidence"]["syncs_per_step"] == 2.0
    assert "lazy" in v[0]["knob"]


def test_doctor_data_starved_fixture():
    v = doctor.diagnose({"data_wait_ms": 600.0, "dispatch_ms": 400.0},
                        kind="train")
    assert v and v[0]["bottleneck"] == "data-starved"
    assert "PADDLE_TPU_PREFETCH_DEPTH" in v[0]["knob"]


def test_doctor_clean_run_yields_no_verdict():
    assert doctor.diagnose(
        {"comm_fraction": 0.03, "data_wait_ms": 5.0,
         "dispatch_ms": 5000.0, "sync_ms": 2.0,
         "host_syncs_measured": 1, "steps": 20,
         "h2d_ms": 10.0}, kind="train") == []


def test_doctor_ranks_multiple_verdicts_by_score():
    v = doctor.diagnose(
        {"comm_fraction": 0.3, "data_wait_ms": 900.0,
         "dispatch_ms": 100.0}, kind="train")
    assert [x["bottleneck"] for x in v] == ["data-starved", "comm-bound"]
    assert v[0]["score"] >= v[1]["score"]


def test_doctor_serve_rules_kv_pressure_and_spec():
    v = doctor.diagnose(
        {"block_occupancy": 0.95, "preemptions": 7,
         "spec_acceptance_rate": 0.1, "prefix_hit_rate": 0.02,
         "prefix_queries": 100}, kind="serve")
    names = [x["bottleneck"] for x in v]
    assert "kv-pressure" in names
    assert "low-spec-acceptance" in names
    assert "prefix-cold" in names
    kv = v[names.index("kv-pressure")]
    assert "PADDLE_TPU_KV_BLOCKS" in kv["knob"]


def test_doctor_tolerates_garbage_and_missing_keys():
    assert doctor.diagnose({}) == []
    assert doctor.diagnose({"comm_fraction": None,
                            "data_wait_ms": "nan?"}) == []


def test_doctor_field_rides_trainer_and_engine_stats():
    tr = _linear_trainer()
    x, y = _batch()
    tr.train_step(x, y)
    assert isinstance(tr.stats["doctor"], list)
    eng = InferenceEngine(tiny_model(), batch_slots=2,
                          prefill_buckets=[16])
    eng.warmup(buckets=[16])
    assert isinstance(eng.stats["doctor"], list)
    # JSON-safe: a stats consumer dumps it
    json.dumps(tr.stats["doctor"])
    json.dumps(eng.stats["doctor"])


@pytest.mark.slow  # tier-1 wall budget: heaviest in file
def test_doctor_and_straggler_in_loadgen_reports():
    from paddle_tpu.inference.loadgen import (MultiTenantWorkload,
                                              SharedPrefixWorkload,
                                              run_fleet_loadtest,
                                              run_loadtest)
    from paddle_tpu.inference.router import Router
    m = tiny_model()
    eng = InferenceEngine(m, batch_slots=2, prefill_buckets=[16])
    eng.warmup(buckets=[16])
    wl = SharedPrefixWorkload(97, seed=0, prefix_len=4, tail_len=(2, 4),
                              max_new=(2, 3))
    rep = run_loadtest(eng, num_requests=4, rate_rps=200.0, workload=wl)
    assert isinstance(rep["doctor"], list)
    # fleet twin
    reps = []
    for _ in range(2):
        e = InferenceEngine(m, batch_slots=2, prefill_buckets=[16],
                            kv_layout="paged", kv_block_size=8)
        e.warmup(buckets=[16])
        reps.append(e)
    router = Router(reps, policy="round_robin")
    wl2 = MultiTenantWorkload(97, seed=0, num_tenants=2, prefix_len=4,
                              tail_len=(2, 4), max_new=(2, 3))
    frep = run_fleet_loadtest(router, num_requests=6, rate_rps=200.0,
                              workload=wl2)
    assert isinstance(frep["doctor"], list)
    assert "stragglers" in frep["straggler"]
    assert len(frep["straggler"]["per_replica_ms"]) == 2
    json.dumps(frep["doctor"])
    json.dumps(frep["straggler"])


# ---- a verdict's action: plain data beside the knob's sentence ---------

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


def test_render_doctor_shows_action_column():
    from paddle_tpu.observability.report import render_doctor
    out = render_doctor(doctor.diagnose({"comm_fraction": 0.4}, "train"))
    assert "action" in out
    assert "moe_a2a_chunks in [1,2,4,8] ->moe_a2a_chunks" in out


# ---------------------------------------------------------------------------
# subprocess kill-and-dump e2e (the tentpole's black-box acceptance)
# ---------------------------------------------------------------------------
_SUBPROC = """
import sys
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import SpmdTrainer, create_mesh, \
    PreemptionGuard

mode = sys.argv[1]


class BombNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(4, 2)

    def forward(self, x):
        out = self.fc(x)
        mask = (x > 900.0).astype("float32").max()
        return out * (1.0 + mask * 3.0e38)


paddle.seed(7)
model = BombNet()
opt = paddle.optimizer.Adam(learning_rate=1e-2,
                            parameters=model.parameters())
tr = SpmdTrainer(
    model, opt, lambda o, y: F.mse_loss(o, y),
    mesh=create_mesh({"dp": 1}),
    anomaly_policy="rollback" if mode == "rollback" else "raise")
rng = np.random.RandomState(0)
bomb = np.full((4, 4), 1000.0, np.float32)
with PreemptionGuard() as g:
    for i in range(6):
        x = bomb if (mode == "rollback" and i == 2) \\
            else rng.randn(4, 4).astype(np.float32)
        tr.train_step(x, rng.randn(4, 2).astype(np.float32))
        if g.preempted:
            print("PREEMPTED", tr._step_count, flush=True)
            sys.exit(0)
print("DONE", tr._step_count, "ROLLBACKS",
      tr.stats["rollback_steps"] if mode == "rollback" else 0,
      flush=True)
"""


def _run_child(tmp_path, mode, extra_env):
    script = tmp_path / "child.py"
    script.write_text(_SUBPROC)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["PADDLE_TPU_FLIGHTREC_DIR"] = str(tmp_path / "black_box")
    for k in ("PADDLE_FAULT_NAN_STEP", "PADDLE_FAULT_SIGTERM_STEP",
              "PADDLE_FAULT_HANG", "PADDLE_TPU_WATCHDOG_S"):
        env.pop(k, None)
    env.update(extra_env)
    p = subprocess.run([sys.executable, str(script), mode], env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr
    return p, str(tmp_path / "black_box")


@pytest.mark.slow  # tier-1 wall budget: heaviest in file
def test_subprocess_sigterm_leaves_valid_bundle(tmp_path):
    """A trainer killed mid-run by the fault harness's SIGTERM leaves
    an explainable black box: valid bundle JSON, validating Chrome
    trace, the preemption event, and the last steps in the ring."""
    p, bb = _run_child(tmp_path, "sigterm",
                       {"PADDLE_FAULT_SIGTERM_STEP": "3"})
    assert "PREEMPTED 3" in p.stdout
    bundles = find_bundles(bb, reason="sigterm")
    assert len(bundles) == 1, os.listdir(bb)
    doc = load_bundle(bundles[0])
    assert doc["bundle"]["reason"] == "sigterm"
    assert any(e["kind"] == "preemption" for e in doc["bundle"]["events"])
    # the dump runs INSIDE the signal handler, mid-step-3: the ring
    # holds the completed steps (1, 2) — the in-flight one records only
    # at its end, after the handler returned
    steps = [e["step"] for e in doc["bundle"]["ring"]
             if e["kind"] == "train_step"]
    assert steps and steps[-1] == 2
    assert obs.validate_chrome_trace(doc["trace"]) > 0
    # no half-written staging dirs
    assert [n for n in os.listdir(bb) if n.endswith(".tmp")] == []


def test_subprocess_nan_rollback_leaves_valid_bundle(tmp_path):
    p, bb = _run_child(tmp_path, "rollback", {})
    assert "DONE" in p.stdout and "ROLLBACKS 1" in p.stdout
    bundles = find_bundles(bb, reason="rollback")
    assert len(bundles) == 1, os.listdir(bb)
    doc = load_bundle(bundles[0])
    ev = [e for e in doc["bundle"]["events"]
          if e["kind"] == "anomaly_rollback"]
    assert ev and ev[0]["step"] == 3
    assert obs.validate_chrome_trace(doc["trace"]) > 0
