"""Granite 4.0-H (Mamba-2 layers beside attention layers, a SwiGLU in
every layer, four scalar multipliers, a tied head) against its plain
reference (``benchmark/references/granite_hybrid.py``: the recurrence
position by position, attention as a masked softmax) at a small size on
the CPU: the model's logits; prefill at padded buckets and decoding
through ``InferenceEngine`` against ONE forward of the reference over
prompt + tokens; what two kinds of cache in one stack force on the engine
(a reused slot, slots of different lengths, the tick launched ahead, the
options refused by name, the ``tick`` span's two arguments, the gauge);
the parameter and state count at the published widths from shapes alone.

Tolerances: everything here is float32 at ``highest`` matmul precision
on both sides, so what is left is the order of summation (the chunked
dual against the sequential recurrence, flash-style blocks against one
softmax): logits of deviation about 1 agree to 2e-4."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights as W
from benchmark.references import granite_hybrid as R
from paddle_tpu.inference import InferenceEngine
from paddle_tpu.models import (GraniteHybridConfig, GraniteHybridForCausalLM,
                               HybridStateCache, KVRows, MambaState)
from paddle_tpu.observability import metrics, spans

SMALL = dict(vocab_size=384, hidden_size=64, shared_intermediate_size=96,
             num_hidden_layers=5,
             layer_types=("mamba", "mamba", "attention", "mamba", "mamba"),
             rms_norm_eps=1e-5, embedding_multiplier=12.0,
             residual_multiplier=0.22, attention_multiplier=0.0625,
             logits_scaling=8.0, num_attention_heads=4,
             num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16,
             mamba_d_state=128, mamba_n_groups=1, mamba_d_conv=4,
             mamba_chunk_size=16)
# every mixer and every feed-forward adds about as much to the stream as
# the three-fold of the embedding, so that the tied head does not hand the
# input token back and a broken mixer shows in the logits (deviation 1)
INIT = [{"match": "A_log$", "kind": "zeros"},
        {"match": "norm\\.weight$|mamba\\.D$", "kind": "ones"},
        {"match": "conv1d\\.weight$", "kind": "normal", "std": 0.29},
        {"match": "conv1d\\.bias$", "kind": "normal", "std": 0.1},
        {"match": "dt_bias$", "kind": "normal", "std": 3.0},
        {"match": "embed_tokens", "kind": "normal", "std": 1.0},
        {"match": "out_proj", "kind": "normal", "std": 12.0},
        {"match": "o_proj", "kind": "normal", "std": 30.0},
        {"match": "input_linear", "kind": "normal", "std": 0.25},
        {"match": "output_linear", "kind": "normal", "std": 6.0},
        {"match": ".", "kind": "normal", "std": 0.2}]
BUCKETS = [16, 32, 64]
TOL = 2e-4


def seeded(seed=7):
    """Seeded weights with the norms moved off 1, so that a norm left
    out or applied to the wrong tensor shows."""
    flat = W.make_weights(seed, R.param_spec(SMALL), INIT, "float32")
    key = jax.random.PRNGKey(seed)
    for i, name in enumerate(sorted(flat)):
        if name.endswith("norm.weight"):
            flat[name] = 1.0 + 0.2 * jax.random.normal(
                jax.random.fold_in(key, i), flat[name].shape)
    return flat


def model_of(flat, **over):
    model = GraniteHybridForCausalLM(GraniteHybridConfig(
        **{**SMALL, "max_seq_len": 256, **over}))
    params = dict(model.named_parameters())
    assert set(params) == set(flat)
    for name, p in params.items():
        assert tuple(p.data.shape) == tuple(flat[name].shape), name
        p.data = flat[name]
    model.eval()
    return model


def ids_of(n, seed=1):
    return np.random.default_rng(seed).integers(
        1, SMALL["vocab_size"], n).astype(np.int32)


def reference(flat, seq, **over):
    kw = {**SMALL, **over}
    return R.logits(kw, R.stack(flat, kw), np.asarray(seq, np.int32))


@pytest.mark.parametrize("length", [1, 3, 16, 45])
def test_logits_match_reference(length):
    """One token, fewer than the convolution's taps, a whole chunk and
    chunks with a tail."""
    flat = seeded()
    ids = ids_of(length, seed=length)
    got = np.asarray(model_of(flat)(ids[None]).data)[0]
    want = reference(flat, ids)
    assert 0.7 < want.std() < 1.6
    np.testing.assert_allclose(got, want, atol=TOL)


@pytest.mark.parametrize("name", ["embedding_multiplier",
                                  "residual_multiplier",
                                  "attention_multiplier", "logits_scaling"])
def test_each_multiplier_moves_the_logits(name):
    """Set to 1 in model and reference alike they still agree, and both
    are far from the published values' logits."""
    flat = seeded()
    ids = ids_of(24, seed=5)
    want = reference(flat, ids)
    moved = reference(flat, ids, **{name: 1.0})
    got = np.asarray(model_of(flat, **{name: 1.0})(ids[None]).data)[0]
    np.testing.assert_allclose(got, moved, atol=10 * TOL)
    assert np.abs(moved - want).max() > 0.3


def serve_by_hand(model, prompt, bucket, steps, slot=1, slots=3):
    """``prefill`` at a padded bucket into `slot`, then ``decode_step``
    with the other slots inactive.  Returns the logits of the last real
    prompt token and of every step, the tokens fed, and the cache."""
    cache = model.init_kv_cache(slots, 128)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    logits, cache = model.prefill(jnp.asarray(padded), cache, slot,
                                  len(prompt))
    rows, toks = [np.asarray(logits[0])], []
    active = np.zeros(slots, np.int32)
    active[slot] = 1
    for _ in range(steps):
        toks.append(int(rows[-1].argmax()))
        feed = np.zeros(slots, np.int32)
        feed[slot] = toks[-1]
        logits, cache = model.decode_step(jnp.asarray(feed), cache,
                                          jnp.asarray(active))
        rows.append(np.asarray(logits[slot]))
    return np.stack(rows[:-1]), np.asarray(toks), cache


@pytest.mark.parametrize("plen, bucket", [(32, 32), (31, 32), (2, 16),
                                          (40, 64)])
def test_prefill_then_decode_logits_match_one_reference_forward(plen,
                                                                bucket):
    """A bucket's length, one short of it, fewer than the convolution's
    taps, chunks with state passed between them: the logits of the
    prefill's last real token and of every decode step against ONE
    forward of the reference over prompt + tokens."""
    flat = seeded()
    prompt = ids_of(plen, seed=plen)
    got, toks, cache = serve_by_hand(model_of(flat), prompt, bucket, 12)
    seq = np.concatenate([prompt, toks[:-1]])
    np.testing.assert_allclose(got, reference(flat, seq)[plen - 1:],
                               atol=TOL)
    assert cache.lengths.tolist() == [0, plen + 12, 0]
    # the inactive slots' states and windows were left alone (their rows
    # take masked garbage above their length)
    for entry in cache.layers:
        if isinstance(entry, MambaState):
            assert not any(np.asarray(leaf[s]).any()
                           for leaf in entry for s in (0, 2))


def engine_of(model, **over):
    return InferenceEngine(model, **{
        "batch_slots": 3, "max_seq_len": 128, "prefill_buckets": BUCKETS,
        **over})


def deficits(flat, prompt, out):
    """How far the reference's logit of each served token lies below its
    best, from one forward over prompt + tokens."""
    seq = np.concatenate([prompt, out[:-1]])
    lg = reference(flat, seq)[len(prompt) - 1:]
    return lg.max(-1) - lg[np.arange(len(out)), out]


def test_engine_serves_the_references_tokens_over_several_buckets():
    """Mixed prompt lengths through the engine's normal entry points,
    more requests than slots, every bucket used."""
    flat = seeded()
    engine = engine_of(model_of(flat)).warmup(buckets=BUCKETS)
    prompts = [ids_of(n, seed=60 + n) for n in (5, 33, 16, 2, 64, 17)]
    rids = [engine.add_request(p, max_new_tokens=n, eos_id=None)
            for p, n in zip(prompts, (20, 12, 24, 16, 10, 18))]
    outs = engine.run()
    seen = set()
    for rid, prompt in zip(rids, prompts):
        assert float(deficits(flat, prompt, outs[rid]).max()) <= TOL
        seen.update(outs[rid].tolist())
    assert len(seen) > 40                   # the model does not repeat
    assert isinstance(engine.cache, HybridStateCache)
    paths = engine.kernel_paths[("decode", 0)]
    assert paths["decode_attention"]["composite"] == 1            # no chip
    assert engine.stats["kv_layout"] == "dense"
    assert engine.stats["prefills"] == 6


def test_bf16_weights_through_the_interpreted_kernel():
    """What the chip runs, as far as a CPU can: bf16 weights, windows and
    rows beside a float32 state, the decode attention as its kernel
    (interpreted), four KV heads of 32 packed into one row of 128.  bf16 rounds the logits by a few hundredths (deviation
    1), so the served tokens lie within 0.8 of the float32 reference's
    best; a wrong path reads over 3."""
    from paddle_tpu import ops
    kw = {**SMALL, "hidden_size": 128, "num_attention_heads": 4,
          "num_key_value_heads": 4, "attention_multiplier": 1 / 32}
    flat = W.make_weights(9, R.param_spec(kw), INIT, "bfloat16")
    model = GraniteHybridForCausalLM(GraniteHybridConfig(
        **{**kw, "max_seq_len": 256}))
    assert model.cfg.rows_packed == 4
    for name, p in model.named_parameters():
        p.data = flat[name]
    model.eval()
    ops.set_interpret_mode(True)
    try:
        engine = engine_of(model).warmup(buckets=BUCKETS)
        prompts = [ids_of(n, seed=80 + n) for n in (7, 40)]
        rids = [engine.add_request(p, max_new_tokens=16, eos_id=None)
                for p in prompts]
        outs = engine.run()
    finally:
        ops.set_interpret_mode(False)
    paths = engine.kernel_paths[("decode", 0)]
    assert paths["decode_attention"] == {"kernel": 1, "composite": 0}
    cache = engine.cache
    assert cache.layers[2].k.shape == (3, 1, 128, 128)
    assert cache.layers[0].s.dtype == jnp.float32
    assert cache.layers[0].window.dtype == cache.layers[2].k.dtype == \
        jnp.bfloat16
    for rid, prompt in zip(rids, prompts):
        seq = np.concatenate([prompt, outs[rid][:-1]])
        lg = R.logits(kw, R.stack(flat, kw), seq)[len(prompt) - 1:]
        assert float((lg.max(-1) - lg[np.arange(16), outs[rid]]).max()) \
            <= 0.8


def test_a_reused_slot_starts_from_a_zero_state_and_window():
    """One slot: a long request, then a short one in the same slot (its
    prompt shorter than the window), gives what a fresh engine gives for
    the short one alone."""
    flat = seeded()
    long_, short = ids_of(30, seed=11), ids_of(2, seed=12)
    used = engine_of(model_of(flat), batch_slots=1)
    used.add_request(long_, max_new_tokens=20, eos_id=None)
    used.run()
    rid = used.add_request(short, max_new_tokens=16, eos_id=None)
    again = used.run()[rid]
    fresh = engine_of(model_of(flat), batch_slots=1)
    rid = fresh.add_request(short, max_new_tokens=16, eos_id=None)
    np.testing.assert_array_equal(again, fresh.run()[rid])
    assert float(deficits(flat, short, again).max()) <= TOL


def _serve_five_through_two_slots(engine):
    prompts = [ids_of(n, seed=40 + n) for n in (5, 17, 32, 9, 3)]
    rids = [engine.add_request(p, max_new_tokens=n, eos_id=None)
            for p, n in zip(prompts, (10, 30, 18, 6, 12))]
    ahead = 0
    while engine.has_work:
        engine.step_or_raise()
        ahead += engine._ahead is not None
    return [engine.results[r].tolist() for r in rids], ahead


@pytest.mark.parametrize("switch", ["_may_run_ahead", "_reads_can_wait"])
def test_ticks_launched_unread_serve_the_serial_orders_tokens(switch):
    """The tick launched ahead of its read, and the tick launched behind
    an unread prefill that has just replaced a slot's state, window and
    rows: the tokens are those of an engine that does neither."""
    flat = seeded()
    serial = engine_of(model_of(flat), batch_slots=2)
    setattr(serial, switch, lambda bound: False)
    want, none = _serve_five_through_two_slots(serial)
    eager = engine_of(model_of(flat), batch_slots=2)
    got, ahead = _serve_five_through_two_slots(eager)
    assert got == want and none == 0 and ahead >= 20
    assert eager.stats["admissions_read_late"] == 5
    assert serial.stats["ticks_launched_unread"] <= \
        eager.stats["ticks_launched_unread"]


@pytest.mark.parametrize("option, kwargs", [
    ("kv_layout='paged'", {"kv_layout": "paged"}),
    ("prefix_cache", {"prefix_cache": True}),
    ("spec_k", {"spec_k": 2}),
    ("prefill_chunk", {"prefill_chunk": 16}),
    ("kv_dtype", {"kv_dtype": "int8"}),
    ("mesh", {"mesh": "a mesh"}),
])
def test_options_a_state_cannot_serve_are_refused_by_name(option, kwargs):
    model = model_of(seeded())
    with pytest.raises(ValueError) as err:
        engine_of(model, **kwargs)
    assert "GraniteHybridForCausalLM" in str(err.value)
    assert "beside its rows" in str(err.value)
    assert option in str(err.value)


def test_cache_answers_for_both_kinds():
    """What the engine asks of the cache: the span's two arguments, the
    bytes a step streams, the state held, what it is made of."""
    model = model_of(seeded())
    cache = model.init_kv_cache(3, 128)
    assert [type(e) for e in cache.layers] == [
        MambaState, MambaState, KVRows, MambaState, MambaState]
    logical = 4 * 8 * 16 * 128 * 4
    assert cache.logical_slot_bytes == logical == \
        R.state_bytes_per_slot(SMALL) == model.cfg.state_bytes_per_slot
    assert cache.holds_state and cache.has_rows and not cache.quantized
    args = cache.tick_reads(np.array([1, 0, 1]), np.array([9, 0, 4]), 1)
    assert args == {"kv_positions": 15, "state_bytes": 2 * 2 * logical}
    window = 4 * 3 * (8 * 16 + 2 * 128) * 4          # float32 weights here
    assert cache.slot_bytes == logical + window
    assert cache.held_state_bytes == 3 * (logical + window)
    rows = 2 * 1 * 100 * 2 * 16 * 4                  # k and v, one layer
    assert cache.step_bytes_per_slot(100) == rows + 2 * (logical + window)
    assert cache.capacity == 128
    leaves = jax.tree_util.tree_leaves(cache)
    assert len(leaves) == 2 * 5 + 1
    again = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(cache), leaves)
    assert again.logical_slot_bytes == logical and again.chunk == 16


def test_tick_span_carries_both_arguments_and_the_gauge_counts_state():
    flat = seeded()
    tracer = spans.tracer()
    tracer.clear()
    tracer.start()
    try:
        engine = engine_of(model_of(flat), batch_slots=2)
        engine.add_request(ids_of(9, seed=3), max_new_tokens=4, eos_id=None)
        engine.add_request(ids_of(6, seed=4), max_new_tokens=4, eos_id=None)
        engine.run()
        ticks = [e["args"] for e in tracer.chrome_trace()["traceEvents"]
                 if e["name"] == "tick" and "kv_positions" in e["args"]]
    finally:
        tracer.stop()
        tracer.clear()
    logical = engine.cache.logical_slot_bytes
    assert ticks and all(
        t["state_bytes"] == 2 * t["active"] * logical and
        t["kv_positions"] > 0 and "kv_positions_read" in t for t in ticks)
    both = [t for t in ticks if t["active"] == 2]
    assert both[0]["kv_positions"] == 9 + 6 + 2
    series = metrics.snapshot()["serve_recurrent_state_bytes"]["series"]
    mine = [s["value"] for s in series
            if s["labels"]["engine"] == engine.telemetry_label]
    assert mine == [engine.cache.held_state_bytes]
    rows = sum(e.k.size + e.v.size for e in engine.cache.layers
               if isinstance(e, KVRows)) * 4
    assert rows > 0 and mine[0] + rows + 2 * 4 == sum(
        x.size * x.dtype.itemsize
        for x in jax.tree_util.tree_leaves(engine.cache))


def test_the_published_widths_from_shapes_alone():
    """3,191,396,096 parameters, 75,497,472 B of state a slot, the cell's
    cache at 64 slots of 5,120 positions: no array is made."""
    cfg = GraniteHybridConfig(max_seq_len=5120)
    kw = {k: getattr(cfg, k) for k in SMALL}
    assert kw["layer_types"].count("attention") == 4 and \
        [i for i, k in enumerate(kw["layer_types"]) if k == "attention"] == \
        [5, 15, 25, 35]
    assert R.num_params(kw) == 3_191_396_096
    assert R.state_bytes_per_slot(kw) == cfg.state_bytes_per_slot == \
        36 * 2_097_152 == 75_497_472
    spec = R.param_spec(kw)
    assert spec["model.layers.0.mamba.in_proj.weight"] == (2048, 8512)
    assert spec["model.layers.5.self_attn.k_proj.weight"] == (2048, 512)
    assert "lm_head.weight" not in spec      # tied
    from paddle_tpu.models.granite_hybrid import empty_cache
    cache = jax.eval_shape(lambda: empty_cache(cfg, 64, 5120, jnp.bfloat16))
    assert cache.layers[5].k.shape == (64, 4, 5120, 128)     # two heads a row
    assert cache.layers[0].s.shape == (64, 64, 64, 128) and \
        cache.layers[0].s.dtype == jnp.float32
    assert cache.layers[0].window.shape == (64, 3, 4352)
    assert cache.held_state_bytes == 64 * 36 * (2_097_152 + 3 * 4352 * 2)
    rows = sum(e.k.size + e.v.size for e in cache.layers
               if isinstance(e, KVRows)) * 2
    assert rows == 64 * 5120 * 8192
