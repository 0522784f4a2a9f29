"""Serving engine tests: static KV cache, fused decode attention,
continuous batching, and the recompile-free-decode contract.

Ground truth throughout is the ordinary full forward: prefill(k tokens)
+ N decode steps over the static cache must reproduce the logits a
single forward over the whole sequence produces (exact in f32 on CPU;
the tolerance argument covers bf16 on TPU).  The compile-count
assertions use utils.compile_counter (the PR 3-style counter
discipline: prove it, don't hand-wave it).
"""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM, StaticKVCache
from paddle_tpu.inference import InferenceEngine, default_prefill_buckets
from paddle_tpu.distributed import async_dispatch
from paddle_tpu.utils import compile_counter

da = importlib.import_module("paddle_tpu.ops.decode_attention")

TINY = dict(vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
            max_seq_len=64, use_flash_attention=False)


def tiny_model(**over):
    paddle.seed(0)
    cfg = GPTConfig(**{**TINY, **over})
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return tiny_model()


@pytest.fixture(scope="module")
def engine(model):
    """Shared 2-slot engine: engines are stateless between completed
    requests (slot lengths mask any stale cache rows), so sequential
    tests can reuse one and skip ~5 redundant compiles."""
    return InferenceEngine(model, batch_slots=2, prefill_buckets=[8])


def naive_greedy(model, prompt, n):
    """Argmax rollout with the ordinary full forward (no cache)."""
    ids = list(np.asarray(prompt).reshape(-1))
    outs = []
    for _ in range(n):
        lg = model(paddle.to_tensor(
            np.asarray([ids], np.int32))).numpy()[0, -1]
        t = int(np.argmax(lg))
        outs.append(t)
        ids.append(t)
    return outs


# ---- fused decode attention kernel ------------------------------------

@pytest.mark.parametrize("hkv", [4, 2, 1])
def test_decode_attention_kernel_matches_composite(hkv):
    """Pallas kernel (interpret mode) vs XLA composite, incl. GQA and
    per-slot length masking."""
    da.set_interpret_mode(True)
    try:
        rng = np.random.RandomState(0)
        b, s, h, d = 3, 256, 4, 64
        q = jnp.asarray(rng.randn(b, h, d).astype(np.float32) * 0.3)
        k = jnp.asarray(rng.randn(b, hkv, s, d).astype(np.float32) * 0.3)
        v = jnp.asarray(rng.randn(b, hkv, s, d).astype(np.float32) * 0.3)
        lengths = jnp.asarray([1, 100, 256], jnp.int32)
        out = da.decode_attention(q, k, v, lengths)
        ref = da._decode_composite(q, k, v, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    finally:
        da.set_interpret_mode(None)


def test_decode_attention_length_masks_tail():
    """Garbage beyond lengths[b] must not leak into the output."""
    rng = np.random.RandomState(1)
    b, s, hkv, d = 2, 128, 2, 16
    q = jnp.asarray(rng.randn(b, 4, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, hkv, s, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, hkv, s, d).astype(np.float32))
    lengths = jnp.asarray([5, 9], jnp.int32)
    base = np.asarray(da._decode_composite(q, k, v, lengths))
    poisoned_k = k.at[:, :, 10:].set(1e3)
    poisoned_v = v.at[:, :, 10:].set(-1e3)
    out = np.asarray(da._decode_composite(q, poisoned_k, poisoned_v,
                                          lengths))
    np.testing.assert_allclose(out, base, rtol=1e-6, atol=1e-6)


# ---- static cache vs full forward -------------------------------------

@pytest.mark.parametrize("kv_heads", [None, 2])
def test_prefill_plus_decode_matches_full_forward(kv_heads):
    """prefill(7 tokens) + 4 decode steps == one forward over 11 tokens
    (logit parity at every generated position; GQA covered)."""
    m = tiny_model(num_kv_heads=kv_heads)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 97, (1, 11)).astype(np.int32)
    full = np.asarray(m(paddle.to_tensor(ids)).data)        # [1, 11, V]

    cache = m.init_kv_cache(batch_slots=3)
    logits, cache = m.prefill(jnp.asarray(ids[:, :7]), cache, 1, 7)
    np.testing.assert_allclose(np.asarray(logits)[0], full[0, 6],
                               rtol=1e-4, atol=1e-4)
    for t in range(7, 11):
        toks = np.zeros(3, np.int32)
        toks[1] = ids[0, t]
        active = jnp.asarray([0, 1, 0], jnp.int32)
        lg, cache = m.decode_step(jnp.asarray(toks), cache, active)
        np.testing.assert_allclose(np.asarray(lg)[1], full[0, t],
                                   rtol=1e-4, atol=1e-4)
    assert np.asarray(cache.lengths).tolist() == [0, 11, 0]


def test_bucket_padding_is_masked():
    """Prefill through a padded bucket (prompt 5 in a 16-bucket) must
    produce the same logits as the exact-length prefill."""
    m = tiny_model()
    rng = np.random.RandomState(2)
    prompt = rng.randint(0, 97, (5,)).astype(np.int32)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :5] = prompt
    c1 = m.init_kv_cache(1)
    l1, c1 = m.prefill(jnp.asarray(prompt[None]), c1, 0, 5)
    c2 = m.init_kv_cache(1)
    l2, c2 = m.prefill(jnp.asarray(padded), c2, 0, 5)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-4, atol=1e-4)
    # and the first decode step agrees too (pad k/v stay masked)
    tok = jnp.asarray([3], jnp.int32)
    act = jnp.ones((1,), jnp.int32)
    d1, _ = m.decode_step(tok, c1, act)
    d2, _ = m.decode_step(tok, c2, act)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-4, atol=1e-4)


# ---- legacy tuple-cache API -------------------------------------------

def test_legacy_cache_fresh_matches_no_cache():
    m = tiny_model()
    attn = m.gpt.blocks[0].attn
    rng = np.random.RandomState(0)
    x = paddle.to_tensor(rng.randn(2, 5, 64).astype(np.float32))
    out_plain = attn(x)
    out_cached, triple = attn(x, cache=(None, None))
    np.testing.assert_allclose(out_plain.numpy(), out_cached.numpy(),
                               rtol=1e-5, atol=1e-5)
    k_buf, v_buf, length = triple
    assert k_buf.shape == (2, 4, 64, 16) and length == 5


def test_legacy_cache_decode_matches_full():
    """Old-style incremental decode through the tuple cache equals the
    full-sequence attention at the last position."""
    m = tiny_model()
    attn = m.gpt.blocks[0].attn
    rng = np.random.RandomState(3)
    x = rng.randn(2, 6, 64).astype(np.float32)
    full = attn(paddle.to_tensor(x)).numpy()
    out, cache = attn(paddle.to_tensor(x[:, :3]), cache=(None, None))
    for t in range(3, 6):
        out, cache = attn(paddle.to_tensor(x[:, t:t + 1]), cache=cache)
        np.testing.assert_allclose(out.numpy()[:, 0], full[:, t],
                                   rtol=1e-4, atol=1e-4)
    assert cache[0].shape == (2, 4, 64, 16)   # capacity never grew


def test_legacy_cache_adopts_dense_past():
    """A legacy 2-tuple of dense past k/v is adopted into the fixed
    buffer: next-step output equals the full-sequence reference."""
    m = tiny_model()
    attn = m.gpt.blocks[0].attn
    rng = np.random.RandomState(4)
    x = rng.randn(1, 5, 64).astype(np.float32)
    full = attn(paddle.to_tensor(x)).numpy()
    # build dense past k/v for the first 4 tokens by hand
    q, k, v = attn._qkv_arrays(paddle.to_tensor(x[:, :4]))
    out, cache = attn(paddle.to_tensor(x[:, 4:5]), cache=(k, v))
    np.testing.assert_allclose(out.numpy()[:, 0], full[:, 4],
                               rtol=1e-4, atol=1e-4)
    assert cache[2] == 5


def test_legacy_cache_overflow_raises_eagerly():
    """Eager use past capacity must raise, not silently clamp (the old
    concat cache grew unboundedly; the static buffer cannot)."""
    m = tiny_model(max_seq_len=8)
    attn = m.gpt.blocks[0].attn
    rng = np.random.RandomState(6)
    x = rng.randn(1, 6, 64).astype(np.float32)
    _, cache = attn(paddle.to_tensor(x), cache=(None, None))
    _, cache = attn(paddle.to_tensor(x[:, :2]), cache=cache)  # 8 == cap
    with pytest.raises(ValueError, match="overflow"):
        attn(paddle.to_tensor(x[:, :1]), cache=cache)


def test_legacy_cache_decode_is_recompile_free():
    """The fixed-capacity tuple cache keeps shapes static: N jitted
    decode steps = ONE trace/compile (the old concat cache recompiled
    every token)."""
    m = tiny_model()
    attn = m.gpt.blocks[0].attn
    rng = np.random.RandomState(5)
    step = jax.jit(lambda xt, cache: attn(paddle.Tensor(xt),
                                          cache=cache))
    x0 = jnp.asarray(rng.randn(1, 1, 64).astype(np.float32))
    out, cache = step(x0, (jnp.zeros((1, 4, 64, 16), jnp.float32),
                           jnp.zeros((1, 4, 64, 16), jnp.float32),
                           jnp.asarray(0, jnp.int32)))
    snap = compile_counter.snapshot()
    for _ in range(6):
        out, cache = step(
            jnp.asarray(rng.randn(1, 1, 64).astype(np.float32)), cache)
    assert snap.new_compiles == 0 and snap.new_traces == 0
    assert int(cache[2]) == 7


# ---- engine -----------------------------------------------------------

def test_engine_greedy_matches_naive_rollout(model, engine):
    rng = np.random.RandomState(0)
    prompt = rng.randint(1, 97, (5,)).astype(np.int32)
    ref = naive_greedy(model, prompt, 6)
    rid = engine.add_request(prompt, max_new_tokens=6)
    outs = engine.run()
    assert outs[rid].tolist() == ref


def test_engine_decode_is_recompile_free(model, engine):
    """THE acceptance criterion: after warmup, generating N tokens
    triggers 0 new XLA compiles AND 0 new jaxpr traces."""
    engine.warmup(buckets=[8])
    rng = np.random.RandomState(1)
    # one full request through prefill+decode to flush any lazy host-side
    # one-offs, then the counted window
    engine.add_request(rng.randint(1, 97, (4,)).astype(np.int32),
                       max_new_tokens=2)
    engine.run()
    snap = compile_counter.snapshot()
    sync0 = async_dispatch.host_sync_count()
    rid = engine.add_request(rng.randint(1, 97, (5,)).astype(np.int32),
                             max_new_tokens=10)
    outs = engine.run()
    assert len(outs[rid]) == 10
    assert snap.new_compiles == 0, \
        f"{snap.new_compiles} XLA compiles during the decode window"
    assert snap.new_traces == 0, \
        f"{snap.new_traces} jaxpr traces during the decode window"
    # sync budget: 1 per decode step (token read-back) + 1 per admission
    st = engine.stats
    syncs = async_dispatch.host_sync_count() - sync0
    assert syncs <= 10, f"{syncs} host syncs for a 10-token request"
    assert st["xla_compiles"] >= 0  # counter alive


def test_engine_continuous_batching_isolation(model, engine):
    """Admitting B mid-stream must not perturb A's tokens (slot-local
    prefill writes), and both requests complete."""
    rng = np.random.RandomState(7)
    pA = rng.randint(1, 97, (4,)).astype(np.int32)
    pB = rng.randint(1, 97, (6,)).astype(np.int32)

    ra = engine.add_request(pA, max_new_tokens=10)
    solo = engine.run()[ra].tolist()

    ra = engine.add_request(pA, max_new_tokens=10)
    for _ in range(3):
        engine.step()
    rb = engine.add_request(pB, max_new_tokens=5)
    res = engine.run()
    assert res[ra].tolist() == solo
    assert len(res[rb]) == 5
    assert res[rb].tolist() == naive_greedy(model, pB, 5)


def test_engine_queue_overflow_waits(engine):
    """More requests than slots: the queue drains as slots retire."""
    rng = np.random.RandomState(8)
    rids = [engine.add_request(rng.randint(1, 97, (3,)).astype(np.int32),
                               max_new_tokens=3) for _ in range(5)]
    res = engine.run()
    assert all(r in res for r in rids)
    assert all(len(res[r]) == 3 for r in rids)


def test_engine_eos_retirement(model, engine):
    rng = np.random.RandomState(9)
    prompt = rng.randint(1, 97, (4,)).astype(np.int32)
    first = naive_greedy(model, prompt, 1)[0]
    rid = engine.add_request(prompt, max_new_tokens=50, eos_id=first)
    res = engine.run()
    assert res[rid].tolist() == [first]       # stopped at EOS, slot freed
    assert engine.num_active == 0


def test_engine_sampling_deterministic_and_topk1_greedy(model):
    rng = np.random.RandomState(10)
    prompt = rng.randint(1, 97, (4,)).astype(np.int32)
    sampled = []
    for _ in range(2):
        eng = InferenceEngine(model, batch_slots=1, prefill_buckets=[8],
                              seed=42)
        r = eng.add_request(prompt, max_new_tokens=8, temperature=0.9,
                            top_p=0.95)
        sampled.append(eng.run()[r].tolist())
    assert sampled[0] == sampled[1]           # same seed, same stream
    eng = InferenceEngine(model, batch_slots=1, prefill_buckets=[8],
                          seed=7, top_k=1)
    r = eng.add_request(prompt, max_new_tokens=6, temperature=1.3)
    assert eng.run()[r].tolist() == naive_greedy(model, prompt, 6)


def test_engine_stats_fields(engine):
    r = engine.add_request(np.asarray([5, 6, 7], np.int32),
                           max_new_tokens=4)
    engine.run()
    st = engine.stats
    for key in ("prefill_ms", "decode_ms", "compile_ms_cold",
                "decode_steps", "tokens_generated", "slot_occupancy",
                "decode_tokens_per_sec", "xla_compiles", "jaxpr_traces",
                "batch_slots", "buckets"):
        assert key in st, key
    assert st["tokens_generated"] >= 3
    assert 0 < st["slot_occupancy"] <= 1
    assert r in engine.results


def test_generate_wrapper(model):
    rng = np.random.RandomState(11)
    prompt = rng.randint(1, 97, (5,)).astype(np.int32)
    out = model.generate(prompt, max_new_tokens=5)
    assert out.tolist() == naive_greedy(model, prompt, 5)
    both = model.generate(prompt, max_new_tokens=3, include_prompt=True)
    assert both[:5].tolist() == prompt.tolist()


def test_default_prefill_buckets(model):
    assert default_prefill_buckets(64, lo=16) == [16, 32, 64]
    assert default_prefill_buckets(100, lo=16) == [16, 32, 64, 100]
    eng = InferenceEngine(model, batch_slots=1)   # no jit runs: cheap
    with pytest.raises(ValueError):
        eng.add_request(np.ones(65, np.int32))  # beyond largest bucket


# ---- decoding wiring + EOS early-exit ---------------------------------

@pytest.fixture(scope="module")
def wiring_model():
    return tiny_model(vocab_size=50, hidden_size=32, num_heads=2)


@pytest.mark.slow  # tier-1 wall budget: heaviest in file
def test_gpt_greedy_search_matches_naive(wiring_model):
    from paddle_tpu.text import greedy_search, gpt_step_fn
    m = wiring_model
    step = gpt_step_fn(m)
    cache = m.init_kv_cache(2)
    toks = np.asarray(greedy_search(step, cache, 2, 6, bos_id=1,
                                    eos_id=0).data)
    ref = naive_greedy(m, [1], 6)
    stop = ref.index(0) + 1 if 0 in ref else 6
    assert toks[0].tolist()[:stop] == ref[:stop]
    assert toks.shape == (2, 6)


def test_gpt_beam_search_runs_over_cache_state(wiring_model):
    from paddle_tpu.text import beam_search, gpt_step_fn
    m = wiring_model
    K = 3
    cache = m.init_kv_cache(1 * K)
    seqs, scores = beam_search(gpt_step_fn(m), cache, 1, K, 5,
                               bos_id=1, eos_id=0)
    assert seqs.shape == [1, K, 5]
    sc = np.asarray(scores.data)[0]
    assert all(sc[i] >= sc[i + 1] for i in range(K - 1))


def _counting_lm(table):
    """LM over a fixed next-token table + a host call counter."""
    calls = []

    def step_fn(tokens, state):
        jax.debug.callback(lambda: calls.append(1))
        return jnp.asarray(table)[tokens], state

    return step_fn, calls


def test_greedy_eos_early_exit():
    """Once every row emits EOS the while-program stops: far fewer
    step_fn executions than max_len."""
    from paddle_tpu.text import greedy_search
    V, EOS, BOS = 5, 0, 1
    table = np.full((V, V), -5.0, np.float32)
    table[:, EOS] = 5.0                      # everything points at EOS
    step_fn, calls = _counting_lm(table)
    toks = np.asarray(greedy_search(step_fn, (), 3, 50, BOS, EOS).data)
    assert toks.shape == (3, 50)
    assert (toks == EOS).all()
    assert len(calls) <= 3, f"{len(calls)} steps for an instant-EOS LM"


def test_beam_eos_early_exit_matches_full_run():
    """Early exit must not change results: same sequences/scores as a
    brute-force comparison LM where EOS arrives quickly."""
    from paddle_tpu.text import beam_search
    V, EOS, BOS = 5, 0, 1
    # EOS overwhelms every alternative, so ALL K beams finish within a
    # couple of steps and the while-program exits
    table = np.full((V, V), -50.0, np.float32)
    table[:, EOS] = 0.0
    step_fn, calls = _counting_lm(table)
    seqs, scores = beam_search(step_fn, (), 1, 3, 20, BOS, EOS)
    assert np.asarray(seqs.data).shape == (1, 3, 20)
    assert len(calls) <= 5, f"no early exit: {len(calls)} steps"
    # every beam terminated with EOS and post-EOS positions are EOS
    arr = np.asarray(seqs.data)[0]
    for k in range(3):
        row = arr[k].tolist()
        assert EOS in row
        first = row.index(EOS)
        assert all(t == EOS for t in row[first:])


# ---- graceful drain + per-request deadlines (ISSUE 10 satellites) -----

def test_drain_finishes_inflight_and_returns_queued(model):
    """engine.drain(): admission stops, in-flight slots run to
    completion, still-queued requests come back to the caller, and the
    paged pool is verified leak-free."""
    eng = InferenceEngine(model, batch_slots=2, prefill_buckets=[8],
                          kv_layout="paged", kv_block_size=8)
    rng = np.random.RandomState(3)
    rids = [eng.add_request(rng.randint(1, 97, (5,)), max_new_tokens=6)
            for _ in range(5)]
    for _ in range(2):
        eng.step()                      # two admitted, three queued
    leftover = eng.drain()
    assert eng.num_active == 0
    assert len(leftover) == 3
    assert [r.rid for r in leftover] == rids[2:]   # FIFO order kept
    finished = [r for r in rids[:2] if r in eng.results]
    assert len(finished) == 2
    assert all(len(eng.results[r]) == 6 for r in finished)
    eng.check_leak_free()               # refcounts all back in the pool
    # the engine is usable again after the drain
    rid = eng.add_request(rng.randint(1, 97, (5,)), max_new_tokens=2)
    eng.run()
    assert rid in eng.results


def test_drain_timeout_force_retires_with_partial_output(model):
    eng = InferenceEngine(model, batch_slots=1, prefill_buckets=[8])
    rid = eng.add_request(np.arange(1, 6, dtype=np.int32),
                          max_new_tokens=10_000)
    eng.step()
    leftover = eng.drain(timeout_s=0.0)
    assert leftover == [] and eng.num_active == 0
    rec = eng.request_stats[rid]
    assert rec["timed_out"] and rec["tokens"] >= 1
    assert eng.stats["drain_forced_retirements"] == 1


def test_preemption_guard_drains_server(model):
    """SIGTERM mid-run: the engine finishes what it started (in-flight
    slots), parks the queue in engine.undelivered, and run() returns."""
    import os
    import signal

    from paddle_tpu.distributed import PreemptionGuard
    eng = InferenceEngine(model, batch_slots=2, prefill_buckets=[8])
    rng = np.random.RandomState(4)
    rids = [eng.add_request(rng.randint(1, 97, (5,)), max_new_tokens=8)
            for _ in range(6)]
    with PreemptionGuard() as g:
        eng.attach_preemption_guard(g)
        eng.step()
        os.kill(os.getpid(), signal.SIGTERM)
        res = eng.run()
    assert eng.num_active == 0
    assert len(eng.undelivered) == 4       # never admitted
    done = [r for r in rids if r in res]
    assert len(done) == 2 and all(len(res[r]) == 8 for r in done)
    # a later drain ACCUMULATES into undelivered (never overwrites),
    # and step_or_raise-only drivers (loadgen) drain instead of
    # busy-spinning a preempted engine forever
    with PreemptionGuard() as g2:
        eng.attach_preemption_guard(g2)
        late = eng.add_request(rng.randint(1, 97, (5,)),
                               max_new_tokens=4)
        os.kill(os.getpid(), signal.SIGTERM)
        for _ in range(10):
            if not eng.has_work:
                break
            eng.step_or_raise()
    assert not eng.has_work
    assert [r.rid for r in eng.undelivered] == rids[2:] + [late]


def test_deadline_expires_queued_and_active(model):
    """A request past its deadline is retired — queued ones without
    ever taking a slot, active ones mid-generation with their partial
    tokens — and reported timed_out instead of wedging a decode slot."""
    import time

    eng = InferenceEngine(model, batch_slots=1, prefill_buckets=[8])
    rng = np.random.RandomState(5)
    # active past-deadline: unbounded generation, 0.15 s budget
    r_active = eng.add_request(rng.randint(1, 97, (5,)),
                               max_new_tokens=10_000, deadline_s=0.15)
    # queued past-deadline: the single slot is occupied the whole time
    r_queued = eng.add_request(rng.randint(1, 97, (5,)),
                               max_new_tokens=4, deadline_s=0.0)
    time.sleep(0.01)
    while r_active not in eng.results or r_queued not in eng.results:
        eng.step_or_raise()
    ra, rq = eng.request_stats[r_active], eng.request_stats[r_queued]
    assert ra["timed_out"] and 0 < ra["tokens"] < 10_000
    assert rq["timed_out"] and rq["tokens"] == 0 \
        and rq["ttft_ms"] is None
    assert eng.stats["deadline_retirements"] == 2
    assert eng.num_active == 0
    # a deadline generous enough never fires
    out = eng.generate(rng.randint(1, 97, (5,)), max_new_tokens=3,
                       deadline_s=60.0)
    assert len(out) == 3


def test_loadtest_reports_timed_out_column(model):
    from paddle_tpu.inference.loadgen import (SharedPrefixWorkload,
                                              run_loadtest)
    eng = InferenceEngine(model, batch_slots=2, prefill_buckets=[8])
    wl = SharedPrefixWorkload(97, seed=0, shared_frac=0.0,
                              prefix_len=4, tail_len=(3, 6),
                              max_new=(2, 4))
    report = run_loadtest(eng, num_requests=6, rate_rps=200.0,
                          workload=wl, deadline_s=30.0)
    assert report["deadline_s"] == 30.0
    assert report["timed_out_requests"] == 0
    report2 = run_loadtest(eng, num_requests=6, rate_rps=200.0,
                           workload=wl, deadline_s=0.0)
    assert report2["timed_out_requests"] == 6
    assert report2["tokens_per_sec"] is not None


# ---- long-sequence serve bench (slow) ---------------------------------

@pytest.mark.slow
def test_serve_bench_long_sequence():
    """Longer-horizon engine soak: 6 requests, 512-capacity cache,
    mixed admission; asserts steady-state decode stays compile-free."""
    m = tiny_model(max_seq_len=512)
    eng = InferenceEngine(m, batch_slots=4, max_seq_len=512,
                          prefill_buckets=[32, 128])
    eng.warmup(buckets=[32])
    rng = np.random.RandomState(12)
    rids = [eng.add_request(
        rng.randint(1, 97, (rng.randint(3, 100),)).astype(np.int32),
        max_new_tokens=40) for _ in range(6)]
    for _ in range(3):
        eng.step()
    snap = compile_counter.snapshot()
    res = eng.run()
    assert snap.new_compiles == 0
    assert sorted(res) == sorted(rids)
    assert all(len(res[r]) == 40 for r in rids)
    assert eng.stats["slot_occupancy"] > 0.5


# -------- the tick launched ahead of its read (engine._tick) --------
def _serve_churn(engine, temperature, eos_id=None):
    """Eight requests of uneven length through three slots; the tokens
    by request in the order sent, and how many calls left a tick in
    flight."""
    rng = np.random.RandomState(1)
    rids = [engine.add_request(rng.randint(1, 97, rng.randint(2, 15)),
                               max_new_tokens=n, eos_id=eos_id,
                               temperature=temperature, deadline_s=600.0)
            for n in (5, 9, 3, 7, 12, 4, 6, 40)]
    ahead = 0
    while engine.has_work:
        engine.step_or_raise()
        ahead += engine._ahead is not None
        # a tick in flight is counted when it is read, with its step
        assert engine._timings["occupancy_sum"] <= \
            engine._timings["decode_steps"]
    return [engine.results[r].tolist() for r in rids], ahead


@pytest.mark.parametrize("temperature", [0.0, 0.8],
                         ids=["greedy", "sampled"])
def test_tick_ahead_serves_the_serial_orders_tokens(model, temperature):
    """Where no request can end at the tick in flight the next one is
    launched before that one is read; every tick still gets the inputs
    the serial order gives it, so the tokens are the same, sampled ones
    too (one key chain), and nothing compiles or traces after warm-up."""
    def engine():
        return InferenceEngine(model, batch_slots=3, seed=3,
                               prefill_buckets=[8, 16]).warmup([8, 16])
    serial = engine()
    serial._may_run_ahead = lambda bound: False
    want, none = _serve_churn(serial, temperature)
    eng = engine()
    snap = compile_counter.snapshot()
    got, ahead = _serve_churn(eng, temperature)
    assert none == 0 and ahead >= 30
    assert got == want
    assert (snap.new_compiles, snap.new_traces) == (0, 0)
    assert eng.stats["decode_steps"] == serial.stats["decode_steps"]
    assert eng.stats["tokens_generated"] == serial.stats["tokens_generated"]
    assert eng._timings["occupancy_sum"] == serial._timings["occupancy_sum"]


def test_tick_ahead_waits_where_a_token_could_end_a_request(model):
    """An EOS makes the next tick's inputs depend on this one's tokens:
    such a request is never run ahead of; nor is a tick that may be a
    request's last.  A call that admitted runs ahead like any other."""
    eng = InferenceEngine(model, batch_slots=2, prefill_buckets=[16])
    _, ahead = _serve_churn(eng, 0.0, eos_id=96)
    assert ahead == 0
    rid = eng.add_request(np.arange(1, 6), max_new_tokens=4)
    flights = []
    while eng.has_work:
        eng.step_or_raise()
        flights.append(eng._ahead is not None)
    # prefill + tick 1 behind it, with tick 2 behind that; tick 2 with
    # tick 3 behind it; tick 3: the last, nothing behind it
    assert flights == [True, True, False]
    assert len(eng.results[rid]) == 4


def test_tick_in_flight_is_dropped_for_a_request_retired_meanwhile(model):
    """A request retired between the launch and the read (a deadline, a
    forced drain) has its tokens already; the tick in flight gives it
    none, and none to the slot's next occupant."""
    eng = InferenceEngine(model, batch_slots=1, prefill_buckets=[8])
    first = eng.add_request(np.arange(1, 6), max_new_tokens=30)
    for _ in range(3):
        eng.step_or_raise()
    assert eng._ahead is not None
    req = eng._slots[0]
    had = len(req.generated)
    req.timed_out = True
    eng._retire(req)
    second = eng.add_request(np.arange(7, 12), max_new_tokens=5)
    eng.run()
    assert len(eng.results[first]) == had
    fresh = InferenceEngine(model, batch_slots=1, prefill_buckets=[8])
    alone = fresh.add_request(np.arange(7, 12), max_new_tokens=5)
    np.testing.assert_array_equal(eng.results[second],
                                  fresh.run()[alone])


def test_every_read_tick_is_noted_on_one_tick_span(model):
    """What the benchmark's readers count as launched ticks: a tick
    launched ahead is noted on the span of the call that reads it, one
    tick a span, with the positions it read."""
    from paddle_tpu import observability as obs
    eng = InferenceEngine(model, batch_slots=2, prefill_buckets=[8])
    tr = obs.tracer()
    tr.clear()
    tr.start()
    try:
        eng.add_request(np.arange(1, 6), max_new_tokens=6)
        eng.run()
        noted = [e["args"] for e in tr.chrome_trace()["traceEvents"]
                 if e["name"] == "tick" and "kv_positions" in e["args"]]
    finally:
        tr.stop()
        tr.clear()
    # five ticks after the prefill's token; tick i attends 5 + i positions
    assert [a["kv_positions"] for a in noted] == [6, 7, 8, 9, 10]
    assert eng.stats["decode_steps"] == 5


# -------- the admission read late: the tick behind a prefill --------
def _all_serial(engine):
    """`engine` with every read where the parent of both mechanisms had
    it: no tick ahead of a read, no tick behind an unread prefill."""
    engine._reads_can_wait = lambda bound: False
    return engine


def _late_counters(engine):
    stats = engine.stats
    return stats["admissions_read_late"], stats["ticks_launched_unread"]


@pytest.mark.parametrize("temperature", [0.0, 0.8],
                         ids=["greedy", "sampled"])
def test_admission_read_late_serves_the_serial_orders_tokens(model,
                                                             temperature):
    """Eight requests through three slots, admitted as slots come free:
    where a fresh request cannot end at its first token the tick behind
    its prefill takes that token from the sampler on the device, and
    the host reads it afterwards.  The tokens are those of an engine
    that reads everything at once, sampled ones too (one key chain),
    with the same counts, and nothing compiles or traces after
    warm-up."""
    def engine():
        return InferenceEngine(model, batch_slots=3, seed=3,
                               prefill_buckets=[8, 16]).warmup([8, 16])
    serial = _all_serial(engine())
    want, none = _serve_churn(serial, temperature)
    eng = engine()
    snap = compile_counter.snapshot()
    got, ahead = _serve_churn(eng, temperature)
    assert (snap.new_compiles, snap.new_traces) == (0, 0)
    assert got == want
    assert none == 0 and _late_counters(serial) == (0, 0)
    for key in ("decode_steps", "tokens_generated", "prefills",
                "sampled_ticks"):
        assert eng.stats[key] == serial.stats[key], key
    assert eng._timings["occupancy_sum"] == serial._timings["occupancy_sum"]
    late, unread = _late_counters(eng)
    # every request can be seen through; a tick goes unread behind each
    # call's prefills and ahead of every tick that ends no request
    assert late == eng.stats["prefills"] == 8
    assert unread >= ahead + 3 and unread <= eng.stats["decode_steps"]


def test_admission_behind_a_tick_in_flight_takes_its_tokens(model):
    """A request that arrives while a tick is in flight: the tick
    behind its prefill takes the in-flight tick's tokens on the device
    with the fresh slot's entry placed among them."""
    def serve(eng):
        rng = np.random.RandomState(7)
        rids = [eng.add_request(rng.randint(1, 97, 6), max_new_tokens=30)]
        marks = []
        for i in range(12):
            if i in (3, 6):
                rids.append(eng.add_request(rng.randint(1, 97, 4 + i),
                                            max_new_tokens=12))
            flying = eng._ahead is not None
            before = eng._timings["admissions_read_late"]
            eng.step_or_raise()
            marks.append((flying,
                          eng._timings["admissions_read_late"] - before,
                          eng._ahead is not None))
        eng.run()
        return [eng.results[r].tolist() for r in rids], marks

    def engine():
        return InferenceEngine(model, batch_slots=3,
                               prefill_buckets=[16]).warmup([16])
    want, _ = serve(_all_serial(engine()))
    eng = engine()
    snap = compile_counter.snapshot()
    got, marks = serve(eng)
    assert (snap.new_compiles, snap.new_traces) == (0, 0)
    assert got == want
    # both arrivals met a tick in flight, were read late, and left the
    # tick that holds them in flight
    assert marks[3] == (True, 1, True) and marks[6] == (True, 1, True)
    assert _late_counters(eng)[0] == 3


def test_two_slots_freed_in_one_call_both_reach_the_tick_behind(model):
    """Two requests end at one tick and two take their slots in the
    next call: both first tokens are placed on the device for the one
    tick behind the two prefills."""
    def serve(eng):
        rng = np.random.RandomState(11)
        rids = [eng.add_request(rng.randint(1, 97, n), max_new_tokens=m)
                for n, m in ((5, 4), (7, 4), (6, 20), (9, 6), (3, 7))]
        jumps = []
        while eng.has_work:
            before = eng._timings["admissions_read_late"]
            eng.step_or_raise()
            jumps.append(eng._timings["admissions_read_late"] - before)
        return [eng.results[r].tolist() for r in rids], jumps

    def engine():
        return InferenceEngine(model, batch_slots=3, prefill_buckets=[16])
    want, _ = serve(_all_serial(engine()))
    got, jumps = serve(engine())
    assert got == want
    assert jumps[0] == 3 and 2 in jumps[1:]
    assert [len(tokens) for tokens in got] == [4, 4, 20, 6, 7]


@pytest.mark.parametrize("case", ["eos", "one_token", "cache_end"])
def test_admission_that_may_end_at_its_first_token_is_read_at_once(case):
    """A request with an EOS, one of a single token, and one whose
    prompt ends two short of the cache's end may be over with their
    first token: the host reads it before anything goes behind it, and
    the tokens are the full forward's."""
    m = tiny_model()
    eng = InferenceEngine(m, batch_slots=2, prefill_buckets=[8, 64])
    rng = np.random.RandomState(13)
    prompt = rng.randint(1, 97, 62 if case == "cache_end" else 6)
    kw = {"eos": dict(max_new_tokens=5, eos_id=96),
          "one_token": dict(max_new_tokens=1),
          "cache_end": dict(max_new_tokens=9)}[case]
    rid = eng.add_request(prompt, **kw)
    eng.step_or_raise()
    assert _late_counters(eng)[0] == 0 and eng.stats["prefills"] == 1
    out = eng.run()[rid].tolist()
    want = naive_greedy(m, prompt, {"eos": 5, "one_token": 1,
                                    "cache_end": 2}[case])
    if 96 in want:
        want = want[:want.index(96) + 1]
    assert out == want
    assert _late_counters(eng)[0] == 0
    # the same engine sees through the next one
    rid = eng.add_request(prompt[:5], max_new_tokens=3)
    assert eng.run()[rid].tolist() == naive_greedy(m, prompt[:5], 3)
    assert _late_counters(eng)[0] == 1


@pytest.mark.parametrize("how", ["deadline", "drain"])
def test_fresh_request_retired_before_its_first_read_leaves_no_token(
        model, how):
    """A fresh request retired between the launch of the tick behind
    its prefill and the read of its first token (a deadline, a forced
    drain) gets no token, and neither the read nor the tick in flight
    gives one to the slot's next occupant."""
    eng = InferenceEngine(model, batch_slots=1, prefill_buckets=[8])
    launch = eng._launch_decode

    def launch_then_retire(tick, after=None, fresh=()):
        out = launch(tick, after=after, fresh=fresh)
        if any(req.rid == first for req, _, _ in fresh):
            if how == "deadline":
                eng._slots[0].deadline = 0.0
                eng._retire_expired()
            else:
                eng.drain(timeout_s=0.0)
        return out
    eng._launch_decode = launch_then_retire
    first = eng.add_request(np.arange(1, 6), max_new_tokens=30)
    eng.step_or_raise()
    assert _late_counters(eng) == (1, 1)
    assert eng.results[first].size == 0
    assert eng.request_stats[first]["timed_out"]
    assert eng.num_active == 0 and eng._ahead is None
    second = eng.add_request(np.arange(7, 12), max_new_tokens=5)
    eng.run()
    alone = InferenceEngine(model, batch_slots=1, prefill_buckets=[8])
    rid = alone.add_request(np.arange(7, 12), max_new_tokens=5)
    np.testing.assert_array_equal(eng.results[second], alone.run()[rid])


@pytest.mark.parametrize("kind", ["paged", "speculative", "chunked"])
def test_engines_the_host_cannot_see_through_admit_serially(model, kind):
    """The paged tick makes room from the host's lengths, the
    speculative one seeds its draft with the first token, the chunked
    one has no prefill to go behind: their admissions are read at once,
    as before, and serve the full forward's tokens."""
    kw = {"paged": dict(kv_layout="paged", kv_block_size=8),
          "speculative": dict(spec_k=2, draft_model=model),
          "chunked": dict(prefill_chunk=4)}[kind]
    eng = InferenceEngine(model, batch_slots=2, prefill_buckets=[8], **kw)
    rng = np.random.RandomState(17)
    prompts = [rng.randint(1, 97, n) for n in (5, 7, 3)]
    rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
    out = eng.run()
    for rid, prompt in zip(rids, prompts):
        assert out[rid].tolist() == naive_greedy(model, prompt, 6)
    assert _late_counters(eng) == (0, 0)
    assert eng.stats["prefills"] == 3
