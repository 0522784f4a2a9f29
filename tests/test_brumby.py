"""Brumby (power retention in every layer) against its plain reference
(``benchmark/references/brumby.py``, the ATTENTION form) at a small size
on the CPU: the three forms of the mixer (attention, chunked, step by
step); the degree-2 expansion; the model's logits; prefill at a padded
bucket and decoding through ``InferenceEngine`` against ONE forward of
the reference over prompt + tokens; what a per-slot recurrent state
forces on the engine (a reused slot, slots of different lengths, a cache
whose size ignores ``max_seq_len``, the options that need rows refused
by name); the decode kernel interpreted against XLA's form; the
parameter and state count of the benchmark's cut from shapes alone."""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights as W
from benchmark.references import brumby as R
from paddle_tpu import ops
from paddle_tpu.inference import InferenceEngine
from paddle_tpu.models import (BrumbyConfig, BrumbyForCausalLM,
                               RecurrentStateCache)
from paddle_tpu.ops import power_retention as pr

SMALL = dict(vocab_size=384, hidden_size=64, intermediate_size=160,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6,
             rope_theta=1e6, retention_eps=1e-6, gate_bias_shift=3.0)
INIT = [{"match": "norm\\.weight$", "kind": "ones"},
        {"match": "embed_tokens", "kind": "normal", "std": 1.0},
        {"match": "g_proj\\.bias$", "kind": "normal", "std": 1.0},
        {"match": "down_proj", "kind": "normal", "std": 0.05},
        {"match": ".", "kind": "normal", "std": 0.1}]
BUCKETS = [16, 32]


def seeded(seed=7):
    """Seeded weights with the norms moved off 1, so that a norm left
    out or applied to the wrong tensor shows."""
    flat = W.make_weights(seed, R.param_spec(SMALL), INIT, "float32")
    key = jax.random.PRNGKey(seed)
    for i, name in enumerate(sorted(flat)):
        if name.endswith("norm.weight") or name.endswith("layernorm.weight"):
            flat[name] = 1.0 + 0.2 * jax.random.normal(
                jax.random.fold_in(key, i), flat[name].shape)
    return flat


def model_of(flat, **over):
    model = BrumbyForCausalLM(BrumbyConfig(
        **{**SMALL, "max_seq_len": 256, "retention_chunk": 8, **over}))
    for name, p in model.named_parameters():
        p.data = flat[name]
    model.eval()
    return model


def ids_of(n, seed=1):
    return np.random.default_rng(seed).integers(
        1, SMALL["vocab_size"], n).astype(np.int32)


def mixer_inputs(b=2, s=37, h=4, hkv=2, d=16, seed=0):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: rng.normal(size=shape).astype(np.float32)
    log_g = np.log(1 / (1 + np.exp(-draw(b, s, hkv) - 2))).astype(np.float32)
    return draw(b, s, h, d), draw(b, s, hkv, d), draw(b, s, hkv, d), log_g


def attention_form(q, k, v, log_g):
    _, ein = R._matmul("float32")
    return np.stack([np.asarray(R.retention(
        *(jnp.asarray(x[b]) for x in (q, k, v, log_g)), 1e-6, ein))
        for b in range(q.shape[0])])


@pytest.mark.parametrize("d", [8, 16, 128])
def test_phi_is_the_degree_two_expansion(d):
    rng = np.random.default_rng(d)
    u, w = (rng.normal(size=(5, d)).astype(np.float32) for _ in range(2))
    got = (pr.phi(u) * pr.phi(w)).sum(-1)
    bound = float(((u * u).sum(-1) * (w * w).sum(-1)).max())
    np.testing.assert_allclose(got, (u * w).sum(-1) ** 2, rtol=2e-5,
                               atol=1e-6 * bound)
    nb = d // 8
    assert pr.phi(u).shape == (5, pr.state_rows(d)) == \
        (5, 64 * nb * (nb + 1) // 2)
    assert pr.state_rows(128) == 8704
    with pytest.raises(ValueError, match="multiple of 8"):
        pr.state_rows(12)


@pytest.mark.parametrize("chunk", [8, 13, 64])
def test_chunked_form_matches_the_attention_form(chunk):
    """37 tokens: 8 and 13 do not divide them, 64 holds them whole."""
    q, k, v, log_g = mixer_inputs()
    y, _ = pr.power_retention_chunked(q, k, v, log_g, None, None,
                                      chunk=chunk)
    np.testing.assert_allclose(y, attention_form(q, k, v, log_g), atol=2e-5)


def test_step_by_step_matches_the_attention_form_and_the_chunks_state():
    q, k, v, log_g = mixer_inputs()
    state = pr.init_state(2, 2, 16)
    ys = []
    for t in range(q.shape[1]):
        y, state = pr.power_retention_step(q[:, t], k[:, t], v[:, t],
                                           log_g[:, t], state)
        ys.append(np.asarray(y))
    np.testing.assert_allclose(np.stack(ys, 1),
                               attention_form(q, k, v, log_g), atol=1e-4)
    _, chunked = pr.power_retention_chunked(q, k, v, log_g, None, None,
                                            chunk=13)
    np.testing.assert_allclose(chunked.s, state.s, atol=2e-5)
    np.testing.assert_allclose(chunked.z, state.z, atol=2e-5)


def test_tokens_past_the_length_leave_the_state_and_a_state_carries_on():
    """A padded window stops the state at each row's last real token,
    and a window that starts from that state continues the sequence."""
    q, k, v, log_g = mixer_inputs()
    want = attention_form(q, k, v, log_g)
    y, stopped = pr.power_retention_chunked(
        q, k, v, log_g, None, np.array([20, 37]), chunk=8)
    np.testing.assert_allclose(y[0, :20], want[0, :20], atol=2e-5)
    _, upto = pr.power_retention_chunked(
        q[:, :20], k[:, :20], v[:, :20], log_g[:, :20], None, None, chunk=8)
    np.testing.assert_allclose(stopped.s[0], upto.s[0], atol=2e-5)
    np.testing.assert_allclose(stopped.z[0], upto.z[0], atol=2e-5)
    rest, _ = pr.power_retention_chunked(
        q[:, 20:], k[:, 20:], v[:, 20:], log_g[:, 20:], upto, None, chunk=8)
    np.testing.assert_allclose(rest, want[:, 20:], atol=2e-5)
    # the step: gate 1 and no write where k and log_g are zero
    zero = jnp.zeros_like
    _, kept = pr.power_retention_step(q[:, 0], zero(k[:, 0]), v[:, 0],
                                      zero(log_g[:, 0]), upto)
    np.testing.assert_array_equal(kept.s, upto.s)
    np.testing.assert_array_equal(kept.z, upto.z)


def test_decode_kernel_matches_xlas_form():
    """The Pallas kernel, interpreted, at the published head width (its
    only one): outputs and both halves of the state, five query heads a
    KV head; the path is noted."""
    from paddle_tpu.ops import power_retention_kernel as kernel
    rng = np.random.default_rng(3)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k, v = draw(1, 10, 128), draw(1, 2, 128), draw(1, 2, 128)
    log_g = jnp.log(jax.nn.sigmoid(draw(1, 2) + 2))
    state = pr.RetentionState(draw(1, 2, 8704, 128), draw(1, 2, 128, 128))
    want_y, want = pr.step_reference(q, k, v, log_g, state, 1e-6)
    assert kernel.serves(q, k, v, state)
    assert not kernel.serves(q[..., :16], k[..., :16], v[..., :16], state)
    ops.set_interpret_mode(True)
    ops.kernel_paths.reset()
    try:
        y, got = pr.power_retention_step(q, k, v, log_g, state)
    finally:
        ops.set_interpret_mode(False)
    assert ops.kernel_paths.counts()["power_retention"] == \
        {"kernel": 1, "composite": 0}
    scale = float(jnp.abs(want_y).max())
    np.testing.assert_allclose(y, want_y, atol=1e-5 * scale)
    np.testing.assert_allclose(got.s, want.s, atol=1e-5)
    np.testing.assert_allclose(got.z, want.z, atol=1e-5)


@pytest.mark.parametrize("length", [40, 7])
def test_logits_match_reference(length):
    flat = seeded()
    ids = ids_of(length)
    got = model_of(flat)(ids[None]).data[0]
    want = R.logits(SMALL, R.stack(flat, SMALL), ids)
    np.testing.assert_allclose(got, want, atol=3e-5)


def serve_by_hand(model, prompt, bucket, steps, slot=1, slots=3):
    """The engine's two entry points driven directly: prefill at a
    padded bucket into `slot`, then `steps` decode steps each fed the
    model's own greedy token; returns the logits every token was taken
    from, the tokens and the cache."""
    cache = model.init_kv_cache(slots, 256)
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :len(prompt)] = prompt
    logits, cache = model.prefill(jnp.asarray(ids), cache, slot, len(prompt))
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
    return np.stack(rows[:-1]), toks, cache


@pytest.mark.parametrize("plen", [32, 31, 1])
def test_prefill_then_decode_logits_match_one_reference_forward(plen):
    """Prompts of a bucket's length, one short of it and one token: the
    logits of the prefill's last real token and of every decode step
    against ONE forward of the reference over prompt + tokens."""
    flat = seeded()
    prompt = ids_of(plen, seed=plen)
    got, toks, cache = serve_by_hand(model_of(flat), prompt, 32, 12)
    seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
    want = R.logits(SMALL, R.stack(flat, SMALL), seq)[plen - 1:]
    np.testing.assert_allclose(got, want, atol=5e-5)
    assert int(cache.lengths[1]) == plen + 12
    assert int(cache.lengths[0]) == 0


def engine_of(model, **over):
    return InferenceEngine(model, **{
        "batch_slots": 3, "max_seq_len": 256, "prefill_buckets": BUCKETS,
        **over})


def deficits(flat, prompt, out):
    """How far the reference's logit of each served token lies below its
    best, from one forward over prompt + tokens."""
    seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
    lg = R.logits(SMALL, R.stack(flat, SMALL), seq)[len(prompt) - 1:]
    return lg.max(-1) - lg[np.arange(len(out)), out]


@pytest.mark.parametrize("plen", [32, 31, 1])
def test_engine_serves_the_references_tokens(plen):
    flat = seeded()
    engine = engine_of(model_of(flat)).warmup(buckets=BUCKETS)
    prompt = ids_of(plen, seed=plen)
    rid = engine.add_request(prompt, max_new_tokens=24, eos_id=None)
    out = engine.run()[rid]
    assert len(out) == 24 and len(set(out.tolist())) > 12
    assert float(deficits(flat, prompt, out).max()) <= 1e-4
    paths = engine.kernel_paths[("decode", 0)]["power_retention"]
    assert paths == {"kernel": 0, "composite": 2}      # heads of 16, no chip
    assert engine.stats["kv_layout"] == "dense"


def test_a_reused_slot_starts_from_zero():
    """One slot: a long request, then a short one in the same slot,
    gives what a fresh engine gives for the short one alone."""
    flat = seeded()
    long_, short = ids_of(30, seed=11), ids_of(9, seed=12)
    used = engine_of(model_of(flat), batch_slots=1)
    used.add_request(long_, max_new_tokens=20, eos_id=None)
    used.run()
    rid = used.add_request(short, max_new_tokens=16, eos_id=None)
    again = used.run()[rid]
    fresh = engine_of(model_of(flat), batch_slots=1)
    rid = fresh.add_request(short, max_new_tokens=16, eos_id=None)
    np.testing.assert_array_equal(again, fresh.run()[rid])
    assert float(deficits(flat, short, again).max()) <= 1e-4


def test_slots_at_different_lengths_do_not_leak():
    """Three requests of different lengths decode side by side, one
    retiring early: each gets what it gets alone."""
    flat = seeded()
    prompts = [ids_of(n, seed=20 + n) for n in (5, 17, 32)]
    news = (10, 30, 18)
    together = engine_of(model_of(flat))
    rids = [together.add_request(p, max_new_tokens=n, eos_id=None)
            for p, n in zip(prompts, news)]
    outs = together.run()
    for rid, prompt, n in zip(rids, prompts, news):
        assert len(outs[rid]) == n
        assert float(deficits(flat, prompt, outs[rid]).max()) <= 1e-4


def _serve_five_through_two_slots(engine):
    """The tokens by request, and how many calls left a tick in
    flight."""
    prompts = [ids_of(n, seed=40 + n) for n in (5, 17, 32, 9, 3)]
    rids = [engine.add_request(p, max_new_tokens=n, eos_id=None)
            for p, n in zip(prompts, (10, 30, 18, 6, 12))]
    ahead = 0
    while engine.has_work:
        engine.step_or_raise()
        ahead += engine._ahead is not None
    return [engine.results[r].tolist() for r in rids], ahead


def test_a_tick_launched_ahead_serves_the_serial_orders_tokens():
    """The engine launches the next tick before it reads the one in
    flight where no request can end there: over a state that is valid at
    one position only the tokens are those of the serial order, a slot
    reused in between included."""
    flat = seeded()
    serial = engine_of(model_of(flat), batch_slots=2)
    serial._may_run_ahead = lambda bound: False
    want, none = _serve_five_through_two_slots(serial)
    got, ahead = _serve_five_through_two_slots(
        engine_of(model_of(flat), batch_slots=2))
    assert none == 0 and ahead >= 20
    assert got == want


def test_a_tick_behind_an_unread_prefill_serves_the_serial_orders_tokens():
    """A fresh slot's first token goes from the prefill's sampler to
    the tick behind it on the device, the prefill having just written
    that slot's state: the tokens are those of an engine that reads
    every token before it launches anything."""
    flat = seeded()
    serial = engine_of(model_of(flat), batch_slots=2)
    serial._reads_can_wait = lambda bound: False
    want, none = _serve_five_through_two_slots(serial)
    eng = engine_of(model_of(flat), batch_slots=2)
    got, _ = _serve_five_through_two_slots(eng)
    assert got == want
    assert none == 0 and serial.stats["ticks_launched_unread"] == 0
    assert eng.stats["admissions_read_late"] == eng.stats["prefills"] == 5
    for key in ("decode_steps", "tokens_generated"):
        assert eng.stats[key] == serial.stats[key]


def test_cache_size_ignores_max_seq_len():
    flat = seeded()
    sizes = []
    for max_len in (256, 32768):
        engine = engine_of(model_of(flat, max_seq_len=32768),
                           max_seq_len=max_len)
        assert isinstance(engine.cache, RecurrentStateCache)
        leaves = jax.tree_util.tree_leaves(engine.cache)
        sizes.append(sum(x.size * x.dtype.itemsize for x in leaves))
        assert engine.stats["decode_hbm_bytes_per_tok"] > 0
    assert sizes[0] == sizes[1]
    rows = pr.state_rows(16)
    assert sizes[0] == 3 * 4 + 2 * 3 * 2 * (rows * 16 + 16 * 16) * 4
    assert engine.cache.slot_bytes == 2 * 2 * (rows * 16 + 16 * 16) * 4
    assert engine.cache.dtype == jnp.float32 and not engine.cache.quantized


@pytest.mark.parametrize("option, kwargs", [
    ("kv_layout='paged'", {"kv_layout": "paged"}),
    ("prefix_cache", {"prefix_cache": True}),
    ("spec_k", {"spec_k": 2}),
    ("prefill_chunk", {"prefill_chunk": 16}),
    ("kv_dtype", {"kv_dtype": "int8"}),
    ("mesh", {"mesh": "a mesh"}),
])
def test_options_that_need_rows_are_refused_by_name(option, kwargs):
    model = model_of(seeded())
    with pytest.raises(ValueError) as err:
        engine_of(model, **kwargs)
    assert "BrumbyForCausalLM" in str(err.value)
    assert option in str(err.value)


def test_tick_span_carries_the_state_bytes():
    """What the roofline's reader divides by: the active slots' state as
    the mathematics counts it, read and written, and no cached position."""
    model = model_of(seeded())
    cache = model.init_kv_cache(3, 256)
    d = SMALL["head_dim"]
    logical = 2 * 2 * (d * (d + 1) // 2) * (d + 1) * 4
    assert cache.logical_slot_bytes == logical == R.state_bytes_per_slot(SMALL)
    args = cache.tick_reads(np.array([1, 0, 1]), np.array([9, 0, 4]), 1)
    assert args == {"kv_positions": 0, "state_bytes": 2 * 2 * logical}
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    gpt = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=32,
                                   num_layers=1, num_heads=2,
                                   max_seq_len=32))
    assert gpt.init_kv_cache(3, 32).tick_reads(
        np.array([1, 0, 1]), np.array([9, 0, 4]), 1) == {"kv_positions": 15}


def test_the_cut_at_the_published_widths():
    """The benchmark's cut from shapes alone (nothing is allocated): the
    issue's arithmetic, leaf by leaf."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "brumby-14b-l8-serve.json")) as f:
        config = json.load(f)
    kw = config["model"]["kwargs"]
    spec = R.param_spec(kw)
    size = lambda pre, post="": sum(
        math.prod(s) for n, s in spec.items()
        if n.startswith(pre) and n.endswith(post))
    layer = "model.layers.0."
    assert sum(size(f"{layer}self_attn.{m}_proj.") for m in "qkvo") == \
        62_914_560
    assert size(layer + "self_attn.g_proj") == 40_968
    assert size(layer + "mlp.") == 267_386_880
    assert size(layer, "norm.weight") == 10_496
    assert size(layer) == 330_352_904
    assert size("model.embed_tokens.") == size("lm_head.") == 777_912_320
    assert R.num_params(kw) == 8 * 330_352_904 + 1_555_829_760 == \
        4_198_652_992                                       # 8.40 GB in bf16
    assert round(R.num_params(kw) * 2 / 1e9, 2) == 8.40
    cfg = BrumbyConfig(**{**kw, "placeholder_params": False})
    assert cfg.state_bytes_per_slot == 8 * 34_080_768 == \
        R.state_bytes_per_slot(kw)
    # as laid out: 8,704 rows by tiles of 8 x 8 and a 128 x 128 normaliser
    cache = jax.eval_shape(lambda: RecurrentStateCache.zeros(
        8, 16, 8, 128, cfg.state_bytes_per_slot))
    held = sum(math.prod(x.shape) * 4
               for x in jax.tree_util.tree_leaves(cache.layers))
    assert held == 16 * 8 * 36_175_872
    # every number of the catalog's config, under its own key
    for key in ("head_dim", "hidden_size", "intermediate_size",
                "num_attention_heads", "num_key_value_heads", "rms_norm_eps",
                "rope_theta", "vocab_size", "num_hidden_layers"):
        assert config[key] == kw[key], key
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"]["num_hidden_layers"] == 40
    assert len(config["source"]) <= 200
