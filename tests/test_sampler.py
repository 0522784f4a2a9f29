"""The engine's sampler (ISSUE 29): vocabulary-wide work only when a row
samples, sorted logits taken from the sort itself.

The formula the sampler replaced (``argsort`` + ``take_along_axis``) is
kept here verbatim as the reference: a greedy row must read the same
``argmax`` in both branches, a sampling row the same token for the same
key, bit for bit, ties in the logits included.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.inference import InferenceEngine
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from test_inference_engine import naive_greedy

VOCAB = 257


def old_sample_from_logits(top_k, logits, key, temps, top_ps):
    """``InferenceEngine._sample_from_logits`` as it stood at PR 26."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    v = logits.shape[-1]
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    if top_k and top_k < v:
        kth = jax.lax.top_k(scaled, top_k)[0][:, -1:]
        scaled = jnp.where(scaled < kth, -1e30, scaled)
    sort_idx = jnp.argsort(-scaled, axis=-1)
    s_logits = jnp.take_along_axis(scaled, sort_idx, axis=-1)
    probs = jax.nn.softmax(s_logits, axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    s_logits = jnp.where(csum - probs < top_ps[:, None],
                         s_logits, -1e30)
    choice = jax.random.categorical(key, s_logits, axis=-1)
    sampled = jnp.take_along_axis(
        sort_idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def old_warped_probs(top_k, logits, temps, top_ps):
    """``SpecDecoder._warped_probs`` as it stood at PR 26."""
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    if top_k and top_k < v:
        kth = jax.lax.top_k(scaled, top_k)[0][:, -1:]
        scaled = jnp.where(scaled < kth, -1e30, scaled)
    sort_idx = jnp.argsort(-scaled, axis=-1)
    s_logits = jnp.take_along_axis(scaled, sort_idx, axis=-1)
    probs = jax.nn.softmax(s_logits, axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    s_logits = jnp.where(csum - probs < top_ps[:, None],
                         s_logits, -1e30)
    s_probs = jax.nn.softmax(s_logits, axis=-1)
    inv = jnp.argsort(sort_idx, axis=-1)   # unsort to token order
    return jnp.take_along_axis(s_probs, inv, axis=-1)


def tiny_model():
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=VOCAB, hidden_size=32, num_layers=2, num_heads=2,
        ffn_hidden_size=64, max_seq_len=64, initializer_range=0.2,
        use_flash_attention=False))
    model.eval()
    return model


@pytest.fixture(scope="module")
def engines():
    """One engine per static top-k (the sampler reads nothing else of
    the engine)."""
    model = tiny_model()
    return {k: InferenceEngine(model, batch_slots=2, max_seq_len=64,
                               prefill_buckets=[16], top_k=k)
            for k in (0, 1, 8)}


def logits_with_ties(n, seed):
    """Random float32 rows with planted ties: the row's maximum twice,
    a run of equal values in the middle, and a quantised tail."""
    rng = np.random.default_rng(seed)
    x = rng.normal(0.0, 3.0, (n, VOCAB)).astype(np.float32)
    for r in range(n):
        top = x[r].max() + 0.5
        x[r, rng.choice(VOCAB, 2, replace=False)] = top
        x[r, rng.choice(VOCAB, 6, replace=False)] = x[r, 3]
    x[:, VOCAB // 2:] = np.round(x[:, VOCAB // 2:] * 2) / 2
    return jnp.asarray(x)


def temps_for(mode, n):
    if mode == "greedy":
        return np.zeros(n, np.float32)
    if mode == "sampling":
        return np.linspace(0.5, 1.3, n).astype(np.float32)
    t = np.zeros(n, np.float32)       # mixed: every third row samples
    t[::3] = 0.8
    return t


@pytest.mark.parametrize("n", [1, 24])
@pytest.mark.parametrize("top_k", [0, 1, 8])
@pytest.mark.parametrize("top_p", [0.1, 0.9, 1.0])
@pytest.mark.parametrize("mode", ["greedy", "sampling", "mixed"])
def test_tokens_bit_equal_to_the_old_formula(engines, mode, top_p, top_k,
                                             n):
    eng = engines[top_k]
    temps = jnp.asarray(temps_for(mode, n))
    top_ps = jnp.full((n,), top_p, jnp.float32)
    for seed in range(4):
        logits = logits_with_ties(n, seed)
        key = jax.random.PRNGKey(100 + seed)
        new = eng._sample_jit(logits, key, temps, top_ps)
        old = old_sample_from_logits(top_k, logits, key, temps, top_ps)
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


@pytest.mark.parametrize("top_k", [0, 8])
@pytest.mark.parametrize("top_p", [0.1, 0.9, 1.0])
def test_warp_sorted_is_the_old_sort_and_gather(engines, top_k, top_p):
    """The shared warp: sorted logits and their tokens, bit for bit what
    ``argsort`` and the vocabulary-wide gather gave."""
    eng = engines[top_k]
    n = 5
    logits = logits_with_ties(n, 7)
    temps = jnp.asarray(temps_for("sampling", n))
    top_ps = jnp.full((n,), top_p, jnp.float32)
    s_logits, sort_idx = jax.jit(eng._warp_sorted)(logits, temps, top_ps)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    if top_k:
        kth = jax.lax.top_k(scaled, top_k)[0][:, -1:]
        scaled = jnp.where(scaled < kth, -1e30, scaled)
    want_idx = jnp.argsort(-scaled, axis=-1)
    np.testing.assert_array_equal(np.asarray(sort_idx),
                                  np.asarray(want_idx))
    kept = np.asarray(s_logits) > -1e29
    np.testing.assert_array_equal(
        np.asarray(s_logits)[kept],
        np.asarray(jnp.take_along_axis(scaled, want_idx, -1))[kept])
    assert kept[:, 0].all()            # the first token always survives


@pytest.mark.parametrize("top_k", [0, 8])
@pytest.mark.parametrize("top_p", [0.1, 0.9, 1.0])
def test_spec_warped_probs_equal_the_old_formula(top_k, top_p):
    model = tiny_model()
    eng = InferenceEngine(model, batch_slots=2, max_seq_len=64,
                          prefill_buckets=[16], top_k=top_k,
                          draft_model=model, spec_k=2)
    n = 6
    logits = logits_with_ties(n, 11)
    temps = jnp.asarray(temps_for("mixed", n))
    top_ps = jnp.full((n,), top_p, jnp.float32)
    new = jax.jit(eng._spec._warped_probs)(logits, temps, top_ps)
    old = old_warped_probs(top_k, logits, temps, top_ps)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


def test_sampling_stream_ignores_the_neighbours_branch():
    """``key`` is split outside the conditional: a sampling request's
    tokens are the same whether the ticks before it took the greedy
    branch or the sampling one."""
    prompt = np.arange(1, 9, dtype=np.int32)

    def sampled_tokens(neighbour_temperature):
        model = tiny_model()
        eng = InferenceEngine(model, batch_slots=2, max_seq_len=64,
                              prefill_buckets=[16], seed=5)
        other = eng.add_request(prompt[::-1].copy(), max_new_tokens=12,
                                temperature=neighbour_temperature)
        for _ in range(4):
            eng.step()
        mine = eng.add_request(prompt, max_new_tokens=6,
                               temperature=0.9, top_p=0.9)
        eng.run()
        return eng.results[mine].tolist(), eng.results[other].tolist()

    after_greedy, _ = sampled_tokens(0.0)
    after_sampling, _ = sampled_tokens(0.7)
    assert after_greedy == after_sampling


@pytest.mark.parametrize("kv_layout", ["dense", "paged"])
def test_one_sampling_and_one_greedy_request_side_by_side(kv_layout):
    model = tiny_model()
    greedy_prompt = np.arange(3, 12, dtype=np.int32)
    want = naive_greedy(model, greedy_prompt, 10)

    def run(temperature):
        kw = dict(kv_block_size=8) if kv_layout == "paged" else {}
        eng = InferenceEngine(model, batch_slots=2, max_seq_len=64,
                              prefill_buckets=[16], kv_layout=kv_layout,
                              seed=3, **kw)
        g = eng.add_request(greedy_prompt, max_new_tokens=10)
        s = eng.add_request(np.arange(20, 26, dtype=np.int32),
                            max_new_tokens=4, temperature=temperature,
                            top_p=0.95)
        eng.run()
        return eng, eng.results[g].tolist(), eng.results[s].tolist()

    eng, greedy_tokens, sampled = run(0.8)
    assert greedy_tokens == want
    assert len(sampled) == 4 and all(0 <= t < VOCAB for t in sampled)
    stats = eng.stats
    # the sampling request decodes 3 tokens after its prefill's first;
    # once it retires its slot's temperature is 0 again and the other
    # 6 ticks take the argmax alone
    assert stats["decode_steps"] == 9
    assert stats["sampled_ticks"] == 3

    eng, greedy_tokens, also_greedy = run(0.0)
    assert greedy_tokens == want
    assert eng.stats["sampled_ticks"] == 0
    assert eng.stats["decode_steps"] == 9


def test_decode_tick_event_carries_the_counter():
    model = tiny_model()
    eng = InferenceEngine(model, batch_slots=2, max_seq_len=64,
                          prefill_buckets=[16], seed=3)
    tr = obs.tracer()
    tr.clear()
    tr.start()
    try:
        eng.add_request(np.arange(3, 12, dtype=np.int32), max_new_tokens=6)
        eng.add_request(np.arange(20, 26, dtype=np.int32),
                        max_new_tokens=3, temperature=0.8)
        eng.run()
        # a tick that only admits launches nothing and carries no counter
        ticks = [e["args"]["sampled_ticks"]
                 for e in tr.chrome_trace()["traceEvents"]
                 if e["name"] == "tick" and "sampled_ticks" in e["args"]]
    finally:
        tr.stop()
        tr.clear()
    assert ticks == [1, 2, 2, 2, 2]
    assert eng.stats["sampled_ticks"] == 2
