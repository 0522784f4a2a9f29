"""The account of a step and a tick from inside the program (ISSUE 38):
named scopes over every operation, which change no operation, and the
trainer's and the engine's phases as spans of the one primitive.

The four programs are built as the benchmark builds its cells, at their
rehearsal sizes, and lowered on the CPU (nothing compiles)."""
import contextlib
import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, trafficgen, weights as W
from paddle_tpu import observability as obs
from paddle_tpu.observability import spans

TRAINER = {"fwd_bwd", "optimizer"}
# the vocabulary of PERF.md section 3, by cell
SCOPES = {
    "train_350m_seq2048": TRAINER | {
        "embed", "attn", "attn_proj", "attn_core", "mlp", "head_ce"},
    "serve_1.3b_closed": {
        "embed", "attn", "attn_proj", "decode_attn", "kv_write", "mlp",
        "head", "sample"},
    "train_nemotron3_ep16_seq8192": TRAINER | {
        "embed", "mamba", "attn", "moe", "mamba_proj", "mamba_conv",
        "ssd_scan", "mamba_gate_norm", "attn_proj", "attn_core",
        "moe_route", "expert_ffn", "shared_expert", "head_ce"},
    "train_kimi_linear_ep32_seq8192": TRAINER | {
        "embed", "kda", "mla", "moe", "dense_mlp", "kda_groups", "kda_proj",
        "kda_conv", "kda_gates", "kda_scan", "mla_proj", "mla_attn",
        "moe_route", "expert_ffn", "shared_expert", "head_ce"},
}
CELLS = sorted(SCOPES)
_PROGRAMS = {}


def _cell(workload):
    parts = harness.load_cell(harness.load_spec(), workload, rehearse=True)
    config = parts["config"]
    ref = importlib.import_module(config["reference"])
    kw = config["model"]["kwargs"]
    flat = W.make_weights(0, ref.param_spec(kw), config["init"],
                          config["dtype"])
    return config, parts["mix"], kw, flat


def _train_program(workload):
    """() -> the cell's fused step, traced anew and lowered."""
    from benchmark.drivers import train
    from paddle_tpu.distributed.mesh import compile_mesh_guard
    config, mix, kw, flat = _cell(workload)
    tr = train.build_trainer(jax, jax.devices()[:1], config, flat)
    ids, labels = trafficgen.train_batches(mix, kw["vocab_size"], 0)[0]
    batch = tr.shard_batch((ids, labels))

    def lower():
        with compile_mesh_guard(tr.mesh):
            return tr._build_fused(1, 1).lower(
                tr.params, tr.opt_state, tr.buffers,
                jnp.asarray(1e-3, jnp.float32), jnp.asarray(1, jnp.int32),
                *batch)
    return lower


def _serve_program(workload):
    """() -> the engine's decode step (``GPTModel.step`` and the
    sampler), traced anew and lowered."""
    from paddle_tpu.inference import InferenceEngine
    config, _, _, flat = _cell(workload)
    model = harness.build_model(config, flat)
    model.eval()
    eng = InferenceEngine(model, **config["driver"]["engine"])
    b = eng.batch_slots

    def lower():
        return jax.jit(eng._decode_fn).lower(
            eng.params, eng.cache, jnp.zeros((b,), jnp.int32),
            jnp.ones((b,), jnp.int32), eng._key,
            jnp.zeros((b,), jnp.float32), jnp.ones((b,), jnp.float32))
    return lower


def program(workload):
    if workload not in _PROGRAMS:
        make = _serve_program if workload.startswith("serve") \
            else _train_program
        _PROGRAMS[workload] = make(workload)
    return _PROGRAMS[workload]


@pytest.mark.parametrize("cell", CELLS)
def test_the_scopes_change_no_operation(cell, monkeypatch):
    lower = program(cell)
    with_scopes = lower().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert lower().as_text() == with_scopes


def _locations(text):
    """{#locN: name} and the lines of @main's own operations."""
    names = dict(re.findall(r'^(#loc\d*) = loc\("([^"]*)"', text, re.M))
    main = text[text.index("func.func public @main"):]
    own = [ln for ln in main[:main.index("\n  }")].splitlines()
           if re.match(r"    [%\w\"]", ln) and "return" not in ln[:12]]
    return names, own


@pytest.mark.parametrize("cell", CELLS)
def test_every_scope_of_the_vocabulary_is_in_the_lowered_step(cell):
    names, _ = _locations(program(cell)().as_text(debug_info=True))
    paths = set(names.values())
    for scope in SCOPES[cell]:
        word = re.compile(r"(^|[/(])" + scope + r"($|[/)])")
        assert any(word.search(p) for p in paths), scope


def test_no_top_level_operation_of_the_gpt_step_is_outside_the_trainers():
    """What ``jit(step)`` does itself is the forward and backward or the
    optimizer: a row of a trace under neither would have no owner.  (What
    the layer scan's body computes from constants alone, the causal mask
    among it, is hoisted out of the loop under its scope of the model.)"""
    names, own = _locations(
        program("train_350m_seq2048")().as_text(debug_info=True))
    assert len(own) > 50
    model = "|".join(SCOPES["train_350m_seq2048"] - TRAINER)
    trainers = 0
    for line in own:
        found = re.search(r"loc\((#loc\d*)\)\s*$", line)
        if not found or found.group(1) == "#loc":
            continue              # a while's head; a constant: loc(unknown)
        loc = found.group(1)
        if re.search(r"/(fwd_bwd|optimizer)(/|$)", names[loc]):
            trainers += 1
        else:
            assert re.search(rf"/({model})/", names[loc]), line
    assert trainers > 50


# ---------------------------------------------------------------------------
# the one span primitive
# ---------------------------------------------------------------------------
@pytest.fixture
def buffer():
    tr = obs.tracer()
    tr.clear()
    tr.start()
    yield tr
    tr.stop()
    tr.clear()


def test_the_primitive_is_one_class_under_two_names():
    from jax.profiler import StepTraceAnnotation, TraceAnnotation
    from paddle_tpu import profiler
    assert profiler.RecordEvent is spans.span is obs.span
    assert issubclass(spans.span, TraceAnnotation)
    assert issubclass(spans.step_span, StepTraceAnnotation)


def test_the_primitive_writes_the_buffer_only_when_armed():
    tr = obs.tracer()
    tr.clear()
    assert not tr.active
    with spans.step_span("tick", "serve", step_num=7, tick=7) as tick:
        tick.note(active=3)
        with spans.span("tick/read", "serve", tick=7):
            pass
    assert len(tr) == 0
    tr.start()
    try:
        with spans.step_span("tick", "serve", step_num=8, tick=8) as tick:
            tick.note(active=3, kv_positions=41)
            with spans.span("tick/read", "serve", tick=8):
                pass

        @spans.span("decorated", step=2)
        def fn():
            return 5
        assert fn() == 5
    finally:
        tr.stop()
    read, tick, deco = tr.chrome_trace()["traceEvents"][-3:]
    tr.clear()
    assert (read["name"], read["args"]) == ("tick/read", {"tick": 8})
    assert tick["name"] == "tick" and tick["cat"] == "serve"
    assert tick["args"] == {"step_num": 8, "tick": 8, "active": 3,
                            "kv_positions": 41}
    assert (deco["name"], deco["args"]) == ("decorated", {"step": 2})
    # the child lies inside its parent
    assert tick["ts"] <= read["ts"] and \
        read["ts"] + read["dur"] <= tick["ts"] + tick["dur"]


def _inside(child, parent):
    return parent["ts"] <= child["ts"] and \
        child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1e-3


def _host_events(tr, names):
    return [e for e in tr.chrome_trace()["traceEvents"]
            if e.get("pid") == spans.PID_HOST and e["name"] in names]


def test_three_train_steps_leave_their_phases_nested(buffer):
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.distributed import SpmdTrainer, create_mesh
    from paddle_tpu.nn import functional as F
    model = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 10))
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    trainer = SpmdTrainer(model, opt, lambda o, y: F.cross_entropy(o, y),
                          mesh=create_mesh({"dp": 1}))
    rng = np.random.RandomState(0)
    x = rng.randn(8, 16).astype(np.float32)
    y = rng.randint(0, 10, size=(8,)).astype(np.int64)
    first = trainer._step_count + 1
    for _ in range(3):
        float(trainer.train_step(x, y))
    events = _host_events(buffer, {"train_step", "train_step/h2d",
                                   "train_step/launch", "train_step/read"})
    by_step = {}
    for e in events:
        by_step.setdefault(e["args"]["step"], {})[e["name"]] = e
    assert sorted(by_step) == [first, first + 1, first + 2]
    for n, got in by_step.items():
        assert set(got) == {"train_step", "train_step/h2d",
                            "train_step/launch", "train_step/read"}
        assert got["train_step"]["args"]["step_num"] == n
        assert _inside(got["train_step/h2d"], got["train_step"])
        assert _inside(got["train_step/launch"], got["train_step"])
        assert got["train_step/h2d"]["ts"] < got["train_step/launch"]["ts"]
        # the read of the loss comes after the step returned
        assert got["train_step/read"]["ts"] >= \
            got["train_step"]["ts"] + got["train_step"]["dur"] - 1e-3
    assert not {e["name"] for e in buffer.chrome_trace()["traceEvents"]} \
        & {"h2d", "dispatch", "sync"}


def test_three_ticks_leave_their_phases_nested(buffer):
    from paddle_tpu.inference import InferenceEngine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    model = GPTForCausalLM(GPTConfig(
        vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=64))
    model.eval()
    eng = InferenceEngine(model, batch_slots=2, max_seq_len=64,
                          prefill_buckets=[16], seed=3)
    eng.add_request(np.arange(3, 12, dtype=np.int32), max_new_tokens=3)
    eng.add_request(np.arange(20, 26, dtype=np.int32), max_new_tokens=3)
    while eng.has_work:
        eng.step_or_raise()
    children = ("tick/admit", "tick/launch", "tick/read", "tick/commit")
    events = _host_events(buffer, {"tick", "prefill", *children})
    ticks = [e for e in events if e["name"] == "tick"]
    launched = [t for t in ticks if "kv_positions" in t["args"]]
    assert len(launched) == eng.stats["decode_steps"] == 2
    # both prompts (9 and 6 tokens) were admitted in the first tick, so
    # its decode step reads 9 + 6 positions and the two new tokens
    assert launched[0]["args"]["active"] == 2
    assert launched[0]["args"]["kv_positions"] == 9 + 6 + 2
    assert launched[1]["args"]["kv_positions"] == 9 + 6 + 4
    # the first call launches tick 1 behind the two prefills, reads their
    # first tokens (the first tick/read), launches tick 2 ahead (inside
    # this call's span, numbered 2) and reads tick 1; the second call
    # finds tick 2 in flight
    by_number = {
        1: ["tick/admit", "tick/launch", "tick/read", "tick/read",
            "tick/commit"],
        2: ["tick/admit", "tick/read", "tick/commit"]}
    for t in launched:
        mine = [e for e in events if e["name"] in children
                and e["args"]["tick"] == t["args"]["tick"]
                and _inside(e, t)]
        assert [e["name"] for e in sorted(mine, key=lambda e: e["ts"])] \
            == by_number[t["args"]["tick"]]
        # the children tile the parent: what lies between them is small
        assert sum(e["dur"] for e in mine) <= t["dur"]
    ahead = [e for e in events if e["name"] == "tick/launch"
             and e["args"]["tick"] == 2]
    assert len(ahead) == 1 and _inside(ahead[0], launched[0])
    prefills = [e for e in events if e["name"] == "prefill"]
    admits = [e for e in events if e["name"] == "tick/admit"]
    assert [p["args"] for p in prefills] == [
        {"bucket": 16, "prompt_tokens": 9},
        {"bucket": 16, "prompt_tokens": 6}]
    assert all(any(_inside(p, a) for a in admits) for p in prefills)
    assert "decode_tick" not in {
        e["name"] for e in buffer.chrome_trace()["traceEvents"]}
