"""Paged KV cache tests: block pool + block tables + radix prefix cache.

Parity chain: tests/test_inference_engine.py proves the DENSE engine
reproduces the naive full-forward rollout exactly; this file proves the
PAGED engine reproduces the same rollout (so paged ≡ dense ≡ full
forward, including GQA and non-uniform lengths), that the paged decode
attention op is BITWISE the dense composite on identical cache
contents, and the allocator-policy claims of ISSUE 6: admission by free
blocks sustains strictly more concurrent requests than dense slots at
equal memory, pool exhaustion preempts-to-queue instead of
deadlocking, prefix-cache hits skip prefill work (prefill token count
measured), the block pool drains leak-free, and the whole thing stays
recompile-free after warmup (utils.compile_counter.assert_no_recompiles
— the PR 3/4 prove-it discipline).
"""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.inference import (BlockAllocator, InferenceEngine,
                                  RadixPrefixCache, blocks_for)
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
def paged_eng(model):
    """Shared paged engine, all executables warmed up front so the
    tests after the first run compile-free."""
    eng = InferenceEngine(model, batch_slots=3, prefill_buckets=[8, 16],
                          kv_layout="paged", kv_block_size=8)
    eng.warmup(buckets=eng.buckets)
    return eng


def assert_greedy_rollout(model, prompt, gen):
    """Teacher-forcing oracle: ONE full forward over prompt+generated
    must reproduce every generated token by argmax at its position —
    exactly equivalent to a step-by-step naive greedy rollout (the
    dense engine's proven ground truth in test_inference_engine.py),
    but one compile per sequence length instead of one per token."""
    gen = np.asarray(gen).reshape(-1)
    seq = np.concatenate([np.asarray(prompt, np.int32).reshape(-1),
                          gen.astype(np.int32)])
    logits = model(paddle.to_tensor(seq[None])).numpy()[0]
    plen = len(seq) - len(gen)
    for i, t in enumerate(gen):
        want = int(np.argmax(logits[plen + i - 1]))
        assert int(t) == want, f"position {i}: got {t}, greedy {want}"


# ---- paged decode attention op ----------------------------------------

def _pool_from_dense(k_dense, tables, bs):
    """Scatter a dense head-major [B, Hkv, S, D] cache into a pool laid
    out by `tables` (so a gather through the table reconstructs it
    exactly)."""
    b, hkv, s, d = k_dense.shape
    mb = s // bs
    nb = int(tables.max()) + 1
    pool = np.zeros((nb, hkv, bs, d), k_dense.dtype)   # head-major
    for bi in range(b):
        for j in range(mb):
            pool[tables[bi, j]] = k_dense[bi, :, j * bs:(j + 1) * bs]
    return pool


def test_paged_composite_bitwise_matches_dense_composite():
    """Identical cache contents through the block table must give the
    BITWISE same output as the dense composite (same values, same
    reduction order) — the 'bitwise where dense is' acceptance leg."""
    rng = np.random.RandomState(0)
    b, s, h, hkv, d, bs = 3, 64, 4, 2, 16, 16
    q = jnp.asarray(rng.randn(b, h, d).astype(np.float32) * 0.3)
    k = rng.randn(b, hkv, s, d).astype(np.float32) * 0.3
    v = rng.randn(b, hkv, s, d).astype(np.float32) * 0.3
    # distinct shuffled blocks per slot, as a real allocator would hand out
    tables = (1 + rng.permutation(b * (s // bs))).reshape(b, s // bs) \
        .astype(np.int32)
    k_pool = _pool_from_dense(k, tables, bs)
    v_pool = _pool_from_dense(v, tables, bs)
    lengths = jnp.asarray([5, 33, 64], jnp.int32)
    dense = da._decode_composite(q, jnp.asarray(k), jnp.asarray(v),
                                 lengths)
    paged = da.paged_decode_attention(q, jnp.asarray(k_pool),
                                      jnp.asarray(v_pool),
                                      jnp.asarray(tables), lengths)
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))


@pytest.mark.parametrize("hkv", [4, 2, 1])
def test_paged_kernel_matches_composite(hkv):
    """Pallas paged kernel (interpret mode, scalar-prefetched block
    table) vs the gather composite, incl. GQA and length masking."""
    da.set_interpret_mode(True)
    try:
        rng = np.random.RandomState(1)
        b, h, d, bs, mb, nb = 3, 4, 64, 128, 2, 8
        q = jnp.asarray(rng.randn(b, h, d).astype(np.float32) * 0.3)
        k_pool = jnp.asarray(
            rng.randn(nb, hkv, bs, d).astype(np.float32) * 0.3)
        v_pool = jnp.asarray(
            rng.randn(nb, hkv, bs, d).astype(np.float32) * 0.3)
        tables = jnp.asarray(
            (1 + rng.permutation(nb - 1))[:b * mb].reshape(b, mb)
            .astype(np.int32))
        lengths = jnp.asarray([1, 140, 256], jnp.int32)
        out = da.paged_decode_attention(q, k_pool, v_pool, tables,
                                        lengths)
        ref = da._paged_composite(q, k_pool, v_pool, tables, lengths)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    finally:
        da.set_interpret_mode(None)


# ---- host-side allocator + radix tree ---------------------------------

def test_block_allocator_invariants():
    al = BlockAllocator(9, 4)                      # 8 usable + null
    assert al.capacity == 8 and al.num_free == 8
    a = al.alloc(3)
    b = al.alloc(5)
    assert al.alloc(1) is None                     # refuses, not raises
    al.incref(a)
    al.decref(a)
    assert al.num_free == 0                        # still held once
    al.decref(a)
    al.decref(b)
    al.check_leak_free()
    with pytest.raises(RuntimeError, match="double free"):
        al.decref([a[0]])


def test_radix_match_insert_evict_pinning():
    al = BlockAllocator(9, 4)
    pc = RadixPrefixCache(al, block_size=4)
    toks = list(range(10, 22))                     # 3 full blocks
    blocks = al.alloc(3)
    assert pc.insert(toks, blocks) == 3            # tree pins all 3
    hit, n = pc.match(toks)
    assert hit == blocks[:2] and n == 8            # last block held back:
    # a full-prompt match must leave >= 1 token to prefill
    hit, n = pc.match(toks + [99])
    assert hit == blocks and n == 12               # now all 3 match
    miss, n = pc.match([7] * 12)
    assert miss == [] and n == 0
    # slot releases its copies; tree still holds one ref each
    al.decref(blocks)
    assert al.num_free == 8 - 3
    # pin the deepest block as a live slot would; evict frees only LRU
    # leaves nobody else references
    al.incref([blocks[2]])
    assert pc.evict(3) == 0                        # leaf pinned -> stuck
    al.decref([blocks[2]])
    assert pc.evict(3) == 3
    al.check_leak_free()
    assert pc.stats["prefix_hit_queries"] == 2


# ---- paged engine vs ground truth -------------------------------------

def test_paged_engine_matches_naive_mixed_lengths(model, paged_eng):
    """Mixed-length prompts through continuous batching: every paged
    request reproduces the full-forward greedy rollout (the dense
    engine's proven oracle), across block boundaries (max_new 12 > 8)."""
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, 97, (n,)).astype(np.int32)
               for n in (3, 7, 12, 5)]
    rids = [paged_eng.add_request(p, max_new_tokens=12) for p in prompts]
    outs = paged_eng.run()
    for p, r in zip(prompts, rids):
        assert len(outs[r]) == 12
        assert_greedy_rollout(model, p, outs[r])
    paged_eng.flush_prefix_cache()
    paged_eng._alloc.check_leak_free()


def test_paged_engine_gqa_parity():
    """GQA leg of the parity acceptance criterion (num_kv_heads=2)."""
    m = tiny_model(num_kv_heads=2)
    eng = InferenceEngine(m, batch_slots=2, prefill_buckets=[8],
                          kv_layout="paged", kv_block_size=8)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 97, (n,)).astype(np.int32) for n in (4, 7)]
    rids = [eng.add_request(p, max_new_tokens=5) for p in prompts]
    outs = eng.run()
    for p, r in zip(prompts, rids):
        assert len(outs[r]) == 5
        assert_greedy_rollout(m, p, outs[r])
    eng.check_leak_free()


def test_paged_zero_recompiles_after_warmup(model, paged_eng):
    """THE zero-recompile acceptance leg: continuous admission AND
    retirement churn with mixed prompt lengths (both buckets, prefix
    hits and misses, block-boundary crossings) triggers 0 XLA compiles
    and 0 jaxpr traces after warmup."""
    rng = np.random.RandomState(4)
    shared = rng.randint(1, 97, (9,)).astype(np.int32)
    # flush one request through to touch any lazy host one-offs
    paged_eng.add_request(shared, max_new_tokens=2)
    paged_eng.run()
    with compile_counter.assert_no_recompiles("paged decode window"):
        rids = []
        for n in (3, 9, 14, 5, 11):
            rids.append(paged_eng.add_request(
                rng.randint(1, 97, (n,)).astype(np.int32),
                max_new_tokens=6))
        rids.append(paged_eng.add_request(shared, max_new_tokens=6))
        outs = paged_eng.run()
    assert all(len(outs[r]) == 6 for r in rids)
    st = paged_eng.stats
    assert st["prefix_hit_queries"] >= 1      # the repeated prompt hit


def test_prefix_hit_matches_cold_and_skips_prefill_work(model, paged_eng):
    """A prompt sharing a cached prefix must produce the cold prefill's
    exact tokens while PREFILLING FEWER TOKENS (the divergent suffix's
    bucket, not the whole prompt's) — measured by the prefill token
    counter."""
    rng = np.random.RandomState(5)
    prompt = rng.randint(1, 97, (13,)).astype(np.int32)   # 1 full block
    t0 = paged_eng._timings["prefill_tokens"]
    r1 = paged_eng.add_request(prompt, max_new_tokens=5)
    out1 = paged_eng.run()[r1]
    cold_tokens = paged_eng._timings["prefill_tokens"] - t0
    h0 = paged_eng._prefix.hit_queries
    t0 = paged_eng._timings["prefill_tokens"]
    r2 = paged_eng.add_request(prompt, max_new_tokens=5)
    out2 = paged_eng.run()[r2]
    hit_tokens = paged_eng._timings["prefill_tokens"] - t0
    assert paged_eng._prefix.hit_queries == h0 + 1
    assert out2.tolist() == out1.tolist()
    assert_greedy_rollout(model, prompt, out1)
    # cold: bucket_for(13)=16 prefilled; hit: suffix 13-8=5 -> bucket 8
    assert hit_tokens < cold_tokens, (hit_tokens, cold_tokens)


def test_more_concurrent_requests_than_dense_at_equal_memory(model):
    """The capacity acceptance criterion: at DENSE-EQUIVALENT memory for
    2 slots (2·64 positions = 16 blocks of 8), the paged engine holds
    strictly more than 2 short requests in flight at once."""
    dense_slots, bs = 2, 8
    equal_memory_blocks = dense_slots * blocks_for(TINY["max_seq_len"], bs)
    eng = InferenceEngine(model, batch_slots=6, prefill_buckets=[8],
                          kv_layout="paged", kv_block_size=bs,
                          kv_num_blocks=equal_memory_blocks,
                          prefix_cache=False)
    rng = np.random.RandomState(6)
    rids = [eng.add_request(rng.randint(1, 97, (4,)).astype(np.int32),
                            max_new_tokens=8) for _ in range(6)]
    eng.step()
    # all 6 admitted concurrently: each holds ceil(8/8)=1..2 blocks,
    # where the dense layout would cap out at 2 slots
    assert eng.num_active == 6 > dense_slots
    assert eng.blocks_in_use <= equal_memory_blocks
    outs = eng.run()
    assert all(len(outs[r]) == 8 for r in rids)
    eng.check_leak_free()


def test_pool_exhaustion_preempts_to_queue(model):
    """6-block pool, 3 requests that each grow to 3 blocks: the pool
    MUST run dry mid-decode; the scheduler preempts the youngest
    request back onto the queue (resume via re-prefill) instead of
    deadlocking, and every request still completes with the exact
    greedy rollout."""
    eng = InferenceEngine(model, batch_slots=3, prefill_buckets=[8, 32],
                          kv_layout="paged", kv_block_size=8,
                          kv_num_blocks=6, prefix_cache=False)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, 97, (7,)).astype(np.int32)
               for _ in range(3)]
    rids = [eng.add_request(p, max_new_tokens=14) for p in prompts]
    outs = eng.run()
    assert eng.stats["preemptions"] > 0
    for p, r in zip(prompts, rids):
        assert len(outs[r]) == 14
        assert_greedy_rollout(model, p, outs[r])
    eng.check_leak_free()


def test_generate_blocks_on_full_engine(model, paged_eng):
    """The queue-not-raise satellite: generate() on a fully occupied
    engine waits its turn through the admission queue and returns the
    right tokens (in-flight requests keep decoding meanwhile)."""
    rng = np.random.RandomState(8)
    fillers = [paged_eng.add_request(
        rng.randint(1, 97, (5,)).astype(np.int32), max_new_tokens=10)
        for _ in range(3)]                    # all 3 slots busy
    for _ in range(2):
        paged_eng.step()
    assert paged_eng.num_active == 3
    prompt = rng.randint(1, 97, (6,)).astype(np.int32)
    out = paged_eng.generate(prompt, max_new_tokens=4)
    assert len(out) == 4
    assert_greedy_rollout(model, prompt, out)
    res = paged_eng.run()
    assert all(len(res[r]) == 10 for r in fillers)


def test_per_request_stats_recorded(paged_eng):
    """Satellite: TTFT and decode tokens/sec land PER REQUEST in
    engine.stats, plus the aggregates the load harness reports."""
    rid = paged_eng.add_request(np.asarray([5, 6, 7], np.int32),
                                max_new_tokens=4)
    paged_eng.run()
    st = paged_eng.stats
    rec = st["per_request"][rid]
    for key in ("ttft_ms", "queued_ms", "decode_tokens_per_sec",
                "tokens", "preemptions", "prompt_tokens"):
        assert key in rec, key
    assert rec["tokens"] == 4 and rec["ttft_ms"] >= 0
    assert st["ttft_ms_p50"] <= st["ttft_ms_p99"]
    for key in ("kv_layout", "kv_block_size", "kv_blocks_total",
                "block_occupancy", "prefix_hit_rate", "preemptions",
                "prefill_tokens"):
        assert key in st, key


def test_matched_prefix_blocks_survive_admission_eviction(model):
    """Review regression: a radix-matched prefix whose only reference
    is the tree's must be PINNED before admission allocates (allocation
    may evict refcount-1 leaves) — otherwise the matched blocks get
    freed and re-handed out as the same request's suffix blocks,
    aliasing the block table.  Near-dry pool + cached prefix + a
    pool-draining interloper reproduces it."""
    eng = InferenceEngine(model, batch_slots=2, prefill_buckets=[8, 16],
                          kv_layout="paged", kv_block_size=4,
                          kv_num_blocks=6)
    rng = np.random.RandomState(11)
    base = rng.randint(1, 97, (9,)).astype(np.int32)
    r0 = eng.add_request(base, max_new_tokens=2)     # caches 2 blocks
    out0 = eng.run()[r0]
    assert_greedy_rollout(model, base, out0)
    filler = eng.add_request(rng.randint(1, 97, (12,)).astype(np.int32),
                             max_new_tokens=2)       # drains free list
    hit_prompt = np.concatenate(
        [base[:8], rng.randint(1, 97, (3,)).astype(np.int32)])
    hit = eng.add_request(hit_prompt, max_new_tokens=4)
    outs = eng.run()
    assert filler in outs and hit in outs
    # exact rollout = the matched prefix KV was NOT clobbered by the
    # suffix prefill landing in re-handed-out aliased blocks
    assert_greedy_rollout(model, hit_prompt, outs[hit])
    eng.check_leak_free()


def test_prefix_hit_on_shrunk_pool_sheds_instead_of_stalling(model):
    """Review regression: on a pool SMALLER than a slot's max extent, a
    large prefix hit can make prefix+bucket demand more blocks than the
    pool holds; admission must shed prefix blocks down to what fits
    (the cold path is guaranteed to) rather than stall the queue head
    forever behind an unallocatable request."""
    eng = InferenceEngine(model, batch_slots=2, prefill_buckets=[32],
                          kv_layout="paged", kv_block_size=8,
                          kv_num_blocks=6)
    rng = np.random.RandomState(12)
    base = rng.randint(1, 97, (30,)).astype(np.int32)
    r1 = eng.add_request(base, max_new_tokens=2)      # caches 3 blocks
    eng.run()
    # prefix hit 24 -> 24+bucket(32)=56 needs 7 blocks > 6 in the pool;
    # must shed to prefix 16 (16+32=48 -> 6 blocks) and still complete
    prompt2 = np.concatenate(
        [base[:24], rng.randint(1, 97, (6,)).astype(np.int32)])
    r2 = eng.add_request(prompt2, max_new_tokens=3)
    out2 = eng.run()[r2]
    assert_greedy_rollout(model, prompt2, out2)
    eng.check_leak_free()


def test_exhaustion_without_resumable_victim_degrades_not_dies(model):
    """Review regression: with a coarse bucket list, every active
    request can outgrow the largest bucket — no one is preemptable.
    Exhaustion must then retire the REQUESTER with the tokens it has
    (memory-capped finish) and keep serving, not kill the engine with
    a RuntimeError that loses every in-flight request."""
    eng = InferenceEngine(model, batch_slots=2, prefill_buckets=[8],
                          kv_layout="paged", kv_block_size=8,
                          kv_num_blocks=4, prefix_cache=False)
    rng = np.random.RandomState(13)
    prompts = [rng.randint(1, 97, (4,)).astype(np.int32)
               for _ in range(2)]
    rids = [eng.add_request(p, max_new_tokens=20) for p in prompts]
    outs = eng.run()                                 # must not raise
    st = eng.stats
    assert st["memory_capped_retirements"] >= 1
    lens = sorted(len(outs[r]) for r in rids)
    assert lens[1] == 20                 # the survivor ran to the end
    assert 1 <= lens[0] < 20             # the capped one kept its work
    for p, r in zip(prompts, rids):      # partials are still exact
        assert_greedy_rollout(model, p, outs[r])
    eng.check_leak_free()


def test_prefix_clamped_when_padded_extent_overflows_table(model):
    """Coarse bucket sets can push prefix_len + bucket_for(suffix) past
    max_seq; admission must shed cached prefix blocks (recompute those
    tokens) rather than overflow the slot's block table — and still
    produce the exact greedy rollout."""
    eng = InferenceEngine(model, batch_slots=2, prefill_buckets=[16, 64],
                          kv_layout="paged", kv_block_size=8)
    rng = np.random.RandomState(10)
    base = rng.randint(1, 97, (59,)).astype(np.int32)
    r1 = eng.add_request(base[:57], max_new_tokens=2)
    out1 = eng.run()[r1]
    # shares 48 cached tokens (full blocks of 56); raw suffix 11 ->
    # bucket 16 -> 56+16=72 > 64 would need 9 blocks in an 8-entry
    # table; the clamp sheds one shared block (prefix 48, 48+16=64)
    prompt2 = np.concatenate(
        [base[:56], rng.randint(1, 97, (3,)).astype(np.int32)])
    r2 = eng.add_request(prompt2, max_new_tokens=2)
    out2 = eng.run()[r2]
    assert eng._prefix.hit_queries >= 1
    assert_greedy_rollout(model, prompt2, out2)
    assert_greedy_rollout(model, base[:57], out1)
    eng.check_leak_free()


# ---- churn soak (slow) -------------------------------------------------

@pytest.mark.slow
def test_block_refcount_churn_soak(model, paged_eng):
    """Longer admission/retirement churn: waves of mixed-length,
    mixed-temperature requests with prefix sharing; after every wave the
    allocator's refcounts stay consistent, and at drain the pool is
    leak-free with zero recompiles across the whole soak."""
    rng = np.random.RandomState(9)
    shared = rng.randint(1, 97, (10,)).astype(np.int32)
    with compile_counter.assert_no_recompiles("paged churn soak"):
        for wave in range(6):
            rids = []
            for i in range(5):
                if rng.rand() < 0.4:
                    p = np.concatenate([shared, rng.randint(
                        1, 97, (rng.randint(1, 5),)).astype(np.int32)])
                else:
                    p = rng.randint(1, 97, (rng.randint(2, 15),)) \
                        .astype(np.int32)
                rids.append(paged_eng.add_request(
                    p, max_new_tokens=int(rng.randint(2, 10)),
                    temperature=0.8 if i % 2 else 0.0))
            outs = paged_eng.run()
            assert all(r in outs for r in rids)
            in_use = paged_eng._alloc.num_in_use
            cached = paged_eng._prefix.cached_blocks
            assert in_use == cached, (in_use, cached)
    assert paged_eng.stats["prefix_hit_rate"] > 0
    paged_eng.check_leak_free()
