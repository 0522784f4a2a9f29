"""Unified telemetry layer (ISSUE 13): registry, spans, capture, SLO.

The two invariants that make telemetry shippable on a serving hot path:

1. **Telemetry-on is free of syncs and recompiles**: with the span
   tracer armed and metrics flowing, a warmed engine's decode loop
   performs EXACTLY one host sync per tick (PR-3's counter proves it —
   zero added) and zero new XLA compiles/traces.
2. **Telemetry-off allocates nothing per step**: an inactive tracer
   buffers nothing, and a disabled registry (PADDLE_TPU_METRICS=0)
   hands every caller the same shared no-op child.

Plus the export contracts: Prometheus exposition
round-trips through the parser, Chrome-trace JSON validates and holds
the per-request lifecycle, snapshot files land atomically.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import observability as obs
from paddle_tpu.distributed import async_dispatch
from paddle_tpu.inference import InferenceEngine
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability.capture import (ProfileWindow,
                                              parse_profile_spec)
from paddle_tpu.observability.metrics import Registry
from paddle_tpu.observability.slo import FleetAggregator, SLOMonitor
from paddle_tpu.utils import compile_counter


@pytest.fixture
def tracer():
    """Armed span tracer, always disarmed + cleared afterwards (the
    tracer is process-global; other test files must not inherit it)."""
    tr = obs.tracer()
    tr.clear()
    tr.start()
    yield tr
    tr.stop()
    tr.clear()


def tiny_model(seed=0):
    cfg = GPTConfig(vocab_size=97, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=64,
                    use_flash_attention=False)
    paddle.seed(seed)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_registry_counter_gauge_histogram():
    r = Registry()
    c = r.counter("reqs_total", "requests", labels=("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc()
    assert c.labels(kind="a").value == 3
    assert c.labels(kind="b").value == 1
    g = r.gauge("depth")
    g.set(7)
    g.inc()
    assert g.value == 8
    h = r.histogram("lat_ms", buckets=(10.0, 100.0))
    for v in (1, 5, 50, 500):
        h.observe(v)
    child = h.labels()
    assert child.count == 4 and child.sum == 556
    assert child.counts == [2, 1, 1]          # <=10, <=100, +Inf
    assert child.percentile(50) == 10.0


def test_registry_kind_conflict_raises():
    r = Registry()
    r.counter("x_total")
    with pytest.raises(ValueError):
        r.gauge("x_total")


def test_registry_label_child_is_cached():
    r = Registry()
    c = r.counter("y_total", labels=("k",))
    assert c.labels(k="v") is c.labels(k="v")   # lock-free after first


def test_exposition_round_trips_through_parser():
    r = Registry()
    r.counter("a_total", "with \"quotes\"",
              labels=("k",)).labels(k='va"l\nue').inc(4)
    r.gauge("b").set(2.5)
    r.histogram("h_ms", buckets=(1.0, 10.0)).observe(3.0)
    text = r.exposition()
    parsed = obs.parse_exposition(text)
    assert parsed["a_total"]["type"] == "counter"
    name, labels, value = parsed["a_total"]["samples"][0]
    assert labels == {"k": 'va"l\nue'} and value == 4
    assert parsed["b"]["samples"][0][2] == 2.5
    hist = parsed["h_ms"]
    assert hist["type"] == "histogram"
    by_name = {}
    for name, labels, value in hist["samples"]:
        by_name.setdefault(name, []).append((labels, value))
    # cumulative buckets: 0 at le=1, 1 at le=10 and +Inf; sum/count ride
    assert [v for _, v in by_name["h_ms_bucket"]] == [0, 1, 1]
    assert by_name["h_ms_sum"][0][1] == 3.0
    assert by_name["h_ms_count"][0][1] == 1


def test_exposition_escapes_hostile_label_values_and_help():
    """ISSUE 14 satellite: backslashes, quotes, and newlines in label
    values AND in metric help text must render escaped and round-trip
    through the parser — a raw newline in a HELP line used to split
    into a garbage sample line and break the whole scrape."""
    hostiles = ['back\\slash', 'a"b', 'nl\nx', 'end\\', 'mix\\"q\n,=}{',
                'tab\tv', '{br}ace']
    for h in hostiles:
        r = Registry()
        r.counter("t_total", 'help with\nnewline, \\ and "quotes"',
                  labels=("k",)).labels(k=h).inc(2)
        r.histogram("h_ms", "hist\nhelp", labels=("k",),
                    buckets=(1.0, 10.0)).labels(k=h).observe(3.0)
        text = r.exposition()
        # the exposition itself must not contain a raw-newline-split
        # garbage line (every line is a comment or parses as a sample)
        parsed = obs.parse_exposition(text)
        name, labels, value = parsed["t_total"]["samples"][0]
        assert labels == {"k": h} and value == 2
        hist = {n: v for n, lbl, v in parsed["h_ms"]["samples"]
                if lbl.get("k") == h and n == "h_ms_count"}
        assert hist["h_ms_count"] == 1


def test_slo_monitor_without_a_baseline_reads_no_regression():
    """The baseline is the caller's to pass; a monitor built without one
    gives a clean no-baseline verdict however slow the window is."""
    mon = SLOMonitor()
    assert mon.baseline_ttft_p99_ms is None
    mon.observe(5000.0)
    v = mon.check()
    assert v["regressed"] is False
    assert v["baseline_ttft_p99_ms"] is None


def test_snapshot_jsonl_is_atomic(tmp_path):
    r = Registry()
    r.counter("c_total").inc(5)
    path = str(tmp_path / "m.jsonl")
    r.write_snapshot(path)
    r.counter("c_total").inc()
    r.write_snapshot(path, extra={"step": 2})
    # no temp orphan, every line parses, history preserved
    assert [p for p in os.listdir(tmp_path) if p.endswith(".tmp")] == []
    lines = [json.loads(ln) for ln in open(path) if ln.strip()]
    assert len(lines) == 2
    assert lines[0]["metrics"]["c_total"]["series"][0]["value"] == 5
    assert lines[1]["metrics"]["c_total"]["series"][0]["value"] == 6
    assert lines[1]["step"] == 2


def test_disabled_registry_is_shared_noop(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_METRICS", "0")
    c1 = obs_metrics.counter("never_registered_total")
    c2 = obs_metrics.gauge("never_registered_gauge")
    # every disabled factory hands back the SAME null metric whose
    # children are the SAME null child: no per-call-site state at all
    assert c1 is c2
    assert c1.labels(any="x") is c2.labels(other="y")
    c1.inc()
    c2.labels(a="b").observe(3.0)
    assert "never_registered_total" not in obs_metrics.snapshot()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
def test_span_records_only_when_active():
    tr = obs.tracer()
    tr.clear()
    assert not tr.active
    with obs.span("idle"):
        pass
    assert len(tr) == 0          # off = nothing buffered
    tr.start()
    try:
        with obs.span("busy", args={"n": 1}):
            pass
    finally:
        tr.stop()
    assert len(tr) == 1
    ev = tr.chrome_trace()["traceEvents"][-1]
    assert ev["name"] == "busy" and ev["ph"] == "X"
    assert ev["args"] == {"n": 1}
    tr.clear()


def test_tracer_capacity_drops_not_grows():
    from paddle_tpu.observability.spans import SpanTracer
    tr = SpanTracer(capacity=3)
    tr.start()
    for i in range(5):
        tr.complete(f"e{i}", 0.0, 1.0)
    assert len(tr) == 3 and tr.dropped == 2
    assert tr.chrome_trace()["otherData"]["dropped_events"] == 2


def test_chrome_trace_validates_and_labels_request_tracks(tracer):
    from paddle_tpu.observability.spans import PID_REQUESTS
    tracer.complete("queued", 0.0, 5.0, pid=PID_REQUESTS, tid=42,
                    cat="request")
    tracer.instant("preempt", pid=PID_REQUESTS, tid=42)
    doc = tracer.chrome_trace()
    assert obs.validate_chrome_trace(doc) == len(doc["traceEvents"])
    names = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert any(e["name"] == "thread_name" and e["tid"] == 42
               and e["args"]["name"] == "request 42" for e in names)


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError):
        obs.validate_chrome_trace({"traceEvents": [{"name": "x"}]})
    with pytest.raises(ValueError):
        obs.validate_chrome_trace(
            {"traceEvents": [{"name": "x", "ph": "X", "pid": 1,
                              "tid": 1, "ts": "soon", "dur": 1}]})


def test_record_event_feeds_span_buffer(tracer):
    from paddle_tpu.profiler import RecordEvent
    with RecordEvent("phase_x"):
        pass
    assert any(e["name"] == "phase_x"
               for e in tracer.chrome_trace()["traceEvents"])


# ---------------------------------------------------------------------------
# capture control
# ---------------------------------------------------------------------------
def test_parse_profile_spec():
    assert parse_profile_spec("2:5") == (2, 5, "/tmp/paddle_tpu_profile")
    assert parse_profile_spec("0:3:/x/y") == (0, 3, "/x/y")
    for bad in ("5", "5:2", "-1:3", "a:b"):
        with pytest.raises(ValueError):
            parse_profile_spec(bad)


def test_profile_window_start_stop(monkeypatch):
    calls = []
    import paddle_tpu.profiler as prof
    monkeypatch.setattr(prof, "start_profiler",
                        lambda d: calls.append(("start", d)) or d)
    monkeypatch.setattr(prof, "stop_profiler",
                        lambda **kw: calls.append(("stop", None)))
    w = ProfileWindow(2, 4, log_dir="/tmp/cap", kind="train")
    for step in range(6):
        w.on_step(step)
    assert calls == [("start", "/tmp/cap"), ("stop", None)]
    assert w.done and not w.active
    # window entirely in the past: never starts
    calls.clear()
    w2 = ProfileWindow(1, 2)
    w2.on_step(10)
    assert calls == [] and w2.done


def test_profile_window_from_env(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_PROFILE", raising=False)
    assert ProfileWindow.from_env() is None
    monkeypatch.setenv("PADDLE_TPU_PROFILE", "3:7")
    w = ProfileWindow.from_env(kind="serve")
    assert (w.start, w.stop) == (3, 7) and w.log_dir.endswith("serve")


# ---------------------------------------------------------------------------
# trainer wiring (StepTimer satellite)
# ---------------------------------------------------------------------------
def test_spmd_trainer_step_timer_and_registry(tracer):
    from paddle_tpu.distributed import SpmdTrainer, create_mesh
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 10))
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    tr = SpmdTrainer(model, opt,
                     lambda out, y: F.cross_entropy(out, y),
                     mesh=create_mesh({"dp": 1}))
    c0 = tr._m_steps.value
    rng = np.random.RandomState(0)
    x = rng.randn(8, 16).astype(np.float32)
    y = rng.randint(0, 10, size=(8,)).astype(np.int64)
    for _ in range(3):
        tr.train_step(x, y)
    st = tr.stats
    # the once-orphaned profiler.StepTimer is live: wall time in stats…
    assert st["step_time_ms"] is not None and st["step_time_ms"] > 0
    assert st["step_time_mean_ms"] > 0
    # …and mirrored into the registry
    assert tr._m_steps.value == c0 + 3
    assert tr._m_step_ms.value == pytest.approx(st["step_time_ms"],
                                                abs=1e-3)
    # train phase spans landed while the tracer was armed
    names = {e["name"] for e in obs.tracer().chrome_trace()["traceEvents"]}
    assert {"train_step", "train_step/h2d", "train_step/launch"} <= names


# ---------------------------------------------------------------------------
# comm_stats graceful degradation (satellite)
# ---------------------------------------------------------------------------
def test_comm_stats_degrades_instead_of_raising():
    from paddle_tpu.utils import comm_stats

    class BrokenCompiled:
        def as_text(self):
            raise RuntimeError("no HLO text on this backend")

    before = obs_metrics.counter(
        "comm_stats_failures_total", labels=("stage",)).labels(
        stage="analyze_compiled").value
    out = comm_stats.analyze_compiled(BrokenCompiled())
    assert out["unavailable"] and out["count"] == 0 and out["bytes"] == 0
    assert out["by_op"] == {} and out["comm_ms"] == 0.0
    assert "no HLO text" in out["error"]
    after = obs_metrics.counter(
        "comm_stats_failures_total", labels=("stage",)).labels(
        stage="analyze_compiled").value
    assert after == before + 1
    # a trainer storing this breakdown reports zeros, not a crash
    assert comm_stats.empty_breakdown()["unavailable"]


def test_comm_stats_analyze_jit_failure_returns_none():
    import jax
    from paddle_tpu.utils import comm_stats

    def f(a, b):
        return a @ b

    # mismatched shapes: lowering raises inside, caller gets None
    bad = (jax.ShapeDtypeStruct((3, 4), np.float32),
           jax.ShapeDtypeStruct((3, 4), np.float32))
    assert comm_stats.analyze_jit(jax.jit(f), *bad) is None


# ---------------------------------------------------------------------------
# overhead suite (the tentpole invariants)
# ---------------------------------------------------------------------------
def _decode_n(eng, prompt, n):
    """Admit one request and decode it to completion, returning the
    (sync delta, tick delta) the run cost."""
    s0 = async_dispatch.host_sync_count()
    t0 = eng._timings["decode_steps"]
    rid = eng.add_request(prompt, max_new_tokens=n)
    out = eng.run()[rid]
    assert len(out) == n
    return (async_dispatch.host_sync_count() - s0,
            eng._timings["decode_steps"] - t0)


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_telemetry_on_adds_zero_syncs_and_zero_recompiles(layout, tracer):
    """THE overhead contract: spans armed + metrics flowing, a warmed
    engine decodes with exactly 1 sync per tick + 1 per admission
    (telemetry adds ZERO) and zero new XLA compiles or traces."""
    m = tiny_model()
    kw = dict(kv_block_size=8) if layout == "paged" else {}
    eng = InferenceEngine(m, batch_slots=2, kv_layout=layout,
                          prefill_buckets=[16], **kw)
    eng.warmup(buckets=[16])
    rng = np.random.RandomState(0)
    prompt = rng.randint(1, 97, (7,)).astype(np.int32)
    # the executable observatory is ARMED (entries registered at
    # warmup) — the sync/recompile budget below therefore proves the
    # registry + HBM ledger add nothing to the hot path (ISSUE 15)
    from paddle_tpu.observability import exec_registry as er
    kinds = {e.kind for e in er.registry().entries(eng._exec_component)}
    assert {"prefill", "decode", "sample"} <= kinds
    with compile_counter.assert_no_recompiles(
            f"{layout} decode with telemetry on"):
        syncs, ticks = _decode_n(eng, prompt, 8)
    # 1 admission sample + 1 per decode tick — nothing else
    assert syncs == ticks + 1, \
        f"telemetry added host syncs: {syncs} for {ticks} ticks"
    # runtime pairing happened (registry saw every tick) without a
    # single extra sync or compile
    dec = [e for e in er.registry().entries(eng._exec_component)
           if e.kind == "decode"][0]
    assert dec.calls >= ticks
    # reading the ledger + stats (exec_profile/hbm/doctor) is dict math
    s0 = async_dispatch.host_sync_count()
    with compile_counter.assert_no_recompiles("stats read"):
        st = eng.stats
        er.ledger().snapshot()
    assert async_dispatch.host_sync_count() == s0
    assert "exec_profile" in st and "hbm" in st
    # the request left a full lifecycle on its track
    from paddle_tpu.observability.spans import PID_REQUESTS
    req_spans = {e["name"] for e in tracer.chrome_trace()["traceEvents"]
                 if e.get("pid") == PID_REQUESTS and e["ph"] == "X"}
    assert {"queued", "prefill", "decode"} <= req_spans


@pytest.mark.slow  # tier-1 wall budget: heaviest in file
def test_telemetry_on_spec_decode_zero_recompiles(tracer):
    """Spec engine (target-as-draft harness): spans on, one sync per
    spec tick, zero recompiles, accept counts in the tick args."""
    m = tiny_model()
    eng = InferenceEngine(m, batch_slots=2, kv_layout="paged",
                          kv_block_size=8, prefill_buckets=[16],
                          spec_k=2, draft_model=m)
    eng.warmup(buckets=[16])
    rng = np.random.RandomState(1)
    prompt = rng.randint(1, 97, (6,)).astype(np.int32)
    with compile_counter.assert_no_recompiles(
            "spec decode with telemetry on"):
        syncs, ticks = _decode_n(eng, prompt, 6)
    assert syncs == ticks + 1
    events = tracer.chrome_trace()["traceEvents"]
    spec_ticks = [e for e in events if e["name"] == "tick"
                  and "k" in e.get("args", {})]
    commits = [e for e in events if e["name"] == "tick/commit"]
    assert spec_ticks and len(commits) == len(spec_ticks)
    assert all("committed" in e["args"] for e in commits)
    # the spec tick joined the observatory as its own kind (ISSUE 15)
    from paddle_tpu.observability import exec_registry as er
    kinds = {e.kind for e in er.registry().entries(eng._exec_component)}
    assert "spec_verify" in kinds


def test_exec_registry_armed_trainer_step_budget():
    """SpmdTrainer half of the ISSUE-15 overhead contract: with the
    registry + ledger armed (always), a warmed trainer's steps stay
    recompile-free and the lazy loop performs zero per-step syncs —
    registration/pairing is pure host dict work."""
    from paddle_tpu.distributed import SpmdTrainer, create_mesh
    from paddle_tpu.observability import exec_registry as er
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                          nn.Linear(32, 10))
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    tr = SpmdTrainer(model, opt, lambda o, y: F.cross_entropy(o, y),
                     mesh=create_mesh({"dp": 1}))
    rng = np.random.RandomState(0)
    x = rng.randn(8, 16).astype(np.float32)
    y = rng.randint(0, 10, size=(8,)).astype(np.int64)
    tr.train_step(x, y)                        # warmup/compile
    assert [e.kind for e in er.registry().entries(tr._exec_component)] \
        == ["train_step"]
    s0 = async_dispatch.host_sync_count()
    with compile_counter.assert_no_recompiles("registry-armed steps"):
        for _ in range(4):
            tr.train_step(x, y)                # lazy: no readbacks
    assert async_dispatch.host_sync_count() == s0
    e = er.registry().entries(tr._exec_component)[0]
    assert e.calls >= 4
    # ledger tracked the trainer state without touching the device
    cats = {t["category"] for t in er.ledger().snapshot()["tracked"]
            if t["name"] == tr.telemetry_label}
    assert "params" in cats
    assert async_dispatch.host_sync_count() == s0


def test_exec_registry_snapshot_to_report_round_trip(tmp_path):
    """Registry round-trip through observability.snapshot() → the
    report CLI renderer: what a warmed engine registered must come back
    out of the offline snapshot file."""
    from paddle_tpu.observability import exec_registry as er
    from paddle_tpu.observability import report
    eng = InferenceEngine(tiny_model(), batch_slots=2,
                          prefill_buckets=[16])
    eng.warmup(buckets=[16])
    rng = np.random.RandomState(5)
    rid = eng.add_request(rng.randint(1, 97, (5,)).astype(np.int32),
                          max_new_tokens=4)
    eng.run()
    er.analyze_all(eng._exec_component)
    snap = obs.snapshot()
    mine = [r for r in snap["executables"]["executables"]
            if r["component"] == eng._exec_component]
    assert {"prefill", "decode", "sample"} <= {r["kind"] for r in mine}
    path = str(tmp_path / "snap.jsonl")
    obs.write_snapshot(path)
    rec = report.load_snapshot_file(path)
    text = report.render_snapshot(rec)
    assert eng._exec_component in text and "hbm ledger" in text
    assert report.main(["--snapshot", path]) == 0


def test_telemetry_off_buffers_nothing():
    """Disabled path: tracer inactive -> the decode loop appends no
    events (no per-step span allocation at all)."""
    tr = obs.tracer()
    assert not tr.active
    tr.clear()
    m = tiny_model()
    eng = InferenceEngine(m, batch_slots=2, prefill_buckets=[16])
    eng.warmup(buckets=[16])
    rng = np.random.RandomState(2)
    rid = eng.add_request(rng.randint(1, 97, (5,)).astype(np.int32),
                          max_new_tokens=4)
    eng.run()
    assert len(tr) == 0


# ---------------------------------------------------------------------------
# fleet aggregation + SLO
# ---------------------------------------------------------------------------
class _FakeReplica:
    def __init__(self, recs):
        self.request_stats = recs
        self._queue = []
        self.num_active = 0
        self.blocks_in_use = 3
        self._request_stats_cap = 4096


def test_fleet_aggregator_scrapes_new_records_once():
    recs = {1: {"ttft_ms": 10.0, "tokens": 5, "timed_out": False},
            2: {"ttft_ms": 99.0, "tokens": 2, "timed_out": True}}
    agg = FleetAggregator([_FakeReplica(recs)])
    assert agg.scrape()["new_requests"] == 2
    assert agg.scrape()["new_requests"] == 0     # seen-set dedupes
    snap = obs_metrics.snapshot()
    series = {tuple(sorted(s["labels"].items())): s
              for s in snap["fleet_requests_total"]["series"]}
    assert series[(("outcome", "ok"), ("replica", "0"))]["value"] >= 1
    assert series[(("outcome", "timed_out"),
                   ("replica", "0"))]["value"] >= 1


def test_slo_monitor_threshold_and_regression():
    mon = SLOMonitor(ttft_p99_ms=50.0, baseline_ttft_p99_ms=20.0,
                     regression_factor=2.0)
    for _ in range(20):
        mon.observe(10.0)
    v = mon.check()
    assert not v["breached"] and not v["regressed"]
    for _ in range(50):
        mon.observe(120.0)               # way past threshold + 2x20
    v = mon.check()
    assert v["breached"] and v["regressed"]
    assert mon.breaches >= 1 and mon.regressions >= 1


def test_router_scrape_metrics_and_counters():
    from paddle_tpu.inference.router import Router
    ra, rb = _FakeReplica({}), _FakeReplica({})
    r = Router([ra, rb], policy="round_robin")
    r.route(np.asarray([1, 2, 3], np.int32))
    r.route(np.asarray([4, 5], np.int32))
    assert r._m_routed.value >= 2
    assert r.scrape_metrics()["new_requests"] == 0
    ra.request_stats[7] = {"ttft_ms": 5.0, "tokens": 3,
                           "timed_out": False}
    assert r.scrape_metrics()["new_requests"] == 1


# ---------------------------------------------------------------------------
# the acceptance shot: one snapshot, three tiers
# ---------------------------------------------------------------------------
def test_one_snapshot_returns_train_serve_and_fleet_metrics():
    """ISSUE 13 acceptance: a live run touching trainer + engine +
    fleet aggregation answers from ONE metrics.snapshot() call."""
    # train tier (SpmdTrainer ran in this process in the test above;
    # run one more step to be order-independent)
    from paddle_tpu.distributed import SpmdTrainer, create_mesh
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 4))
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    tr = SpmdTrainer(model, opt,
                     lambda out, y: F.cross_entropy(out, y),
                     mesh=create_mesh({"dp": 1}))
    rng = np.random.RandomState(0)
    tr.train_step(rng.randn(4, 8).astype(np.float32),
                  rng.randint(0, 4, size=(4,)).astype(np.int64))
    # serve tier
    eng = InferenceEngine(tiny_model(), batch_slots=2,
                          prefill_buckets=[16])
    eng.warmup(buckets=[16])
    rid = eng.add_request(rng.randint(1, 97, (5,)).astype(np.int32),
                          max_new_tokens=3)
    eng.run()
    # fleet tier
    FleetAggregator([eng]).scrape()

    snap = obs.snapshot()["metrics"]
    for family in ("train_steps_total", "train_step_ms",     # train
                   "serve_decode_ticks_total", "serve_ttft_ms",  # serve
                   "fleet_ttft_ms", "fleet_tokens_total",    # fleet
                   "host_syncs_total", "xla_compiles_total"):
        assert family in snap, f"{family} missing from snapshot()"
