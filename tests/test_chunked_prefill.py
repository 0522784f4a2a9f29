"""Chunked-prefill tests: the ISSUE-20 stall-free-batching contract.

Chunked prefill is a pure SCHEDULING change — admission binds a slot
without running prefill, each tick advances every still-prefilling slot
by up to ``prefill_chunk`` prompt tokens through ONE fixed-shape chunk
executable alongside the decode batch, and a slot graduates to decode
when its prompt completes.  The value proposition collapses unless the
emitted stream stays bit-identical to the monolithic engine's, so this
file pins token identity across the serving matrix (dense AND paged,
fp AND int8 KV, GQA, chunk ∈ {1, 4, ≥prompt}), the zero-recompile
churn contract for the chunk executable, preempt-resume under pool
pressure (with the progressive radix adoption re-hit), speculative
composition, the ``set_prefill_chunk`` hot-apply, the HOL-admission
probe memo, and the ITL / ``prefill_stall_ms`` observability columns
the loadgen + doctor satellites consume.
"""
import importlib

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.func import functional_apply, functional_state
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.inference import InferenceEngine
from paddle_tpu.utils import compile_counter

da = importlib.import_module("paddle_tpu.ops.decode_attention")

TINY = dict(vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
            max_seq_len=64, use_flash_attention=False)


def tiny_model(seed=0, **over):
    paddle.seed(seed)
    cfg = GPTConfig(**{**TINY, **over})
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def target():
    return tiny_model(0)


@pytest.fixture(scope="module")
def draft():
    return tiny_model(1, num_layers=1)


@pytest.fixture(scope="module")
def prompts():
    # lengths straddle every chunk-4 phase (1, 1, 3, 0 mod 4) and the
    # 16 one ends EXACTLY on both a chunk and a bucket boundary
    rng = np.random.RandomState(0)
    return [rng.randint(1, 97, (n,)).astype(np.int32)
            for n in (5, 9, 3, 16)]


@pytest.fixture(scope="module")
def reference(target, prompts):
    """The monolithic dense engine's greedy output — the ground truth
    every chunked configuration must reproduce exactly."""
    eng = InferenceEngine(target, batch_slots=2, prefill_buckets=[16])
    for p in prompts:
        eng.add_request(p, max_new_tokens=10)
    return eng.run()


# ---- op level: the chunk window IS the verify window --------------------

def test_chunk_attention_is_window_attention():
    """Chunked prefill adds NO new kernels: the chunk-attention exports
    are the PR-10 windowed verify ops themselves (scatter-then-attend
    over the staircase mask is the same computation either way)."""
    from paddle_tpu import ops
    assert ops.chunk_prefill_attention is da.decode_attention_window
    assert ops.paged_chunk_prefill_attention is \
        da.paged_decode_attention_window


def test_chunk_window_from_empty_matches_sequential():
    """The window op at the chunk-edge prefix lengths {0, 1, C-1, C} —
    including the cold start lens=0 a monolithic-verify user never hits
    — must equal a sequential chain of single-token decode calls."""
    rng = np.random.RandomState(0)
    B, S, H, Hkv, D, W = 4, 16, 4, 2, 8, 4
    k = jnp.asarray(rng.randn(B, Hkv, S, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, Hkv, S, D).astype(np.float32))
    q = jnp.asarray(rng.randn(B, W, H, D).astype(np.float32))
    lens = jnp.asarray(np.array([0, 1, 3, 4], np.int32))
    out = da.decode_attention_window(q, k, v, lens)
    for i in range(W):
        ref = da.decode_attention(q[:, i], k, v, lens + i + 1)
        np.testing.assert_allclose(np.asarray(out[:, i]),
                                   np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("quantized", [False, True])
def test_chunk_window_kernel_interpret_edges(quantized):
    """Interpret-mode Pallas window kernel ≡ the XLA composite at the
    chunk-edge prefix lengths (GQA, fp and int8, kernel-eligible
    shapes) — the kernel the chunk executable actually dispatches."""
    rng = np.random.RandomState(2)
    B, S, H, Hkv, D, W = 4, 128, 4, 2, 64, 8
    q = jnp.asarray(rng.randn(B, W, H, D).astype(np.float32))
    lens = jnp.asarray(np.array([0, 1, 7, 8], np.int32))
    if quantized:
        k = jnp.asarray(rng.randint(-127, 128, (B, Hkv, S, D))
                        .astype(np.int8))
        v = jnp.asarray(rng.randint(-127, 128, (B, Hkv, S, D))
                        .astype(np.int8))
        ks = jnp.asarray(rng.rand(B, Hkv, S).astype(np.float32) * 0.02)
        vs = jnp.asarray(rng.rand(B, Hkv, S).astype(np.float32) * 0.02)
        args = (q, k, v, lens, ks, vs)
        ref = da._window_composite(q, k, v, lens, ks, vs)
    else:
        k = jnp.asarray(rng.randn(B, Hkv, S, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, Hkv, S, D).astype(np.float32))
        args = (q, k, v, lens)
        ref = da._window_composite(q, k, v, lens)
    da.set_interpret_mode(True)
    try:
        out = da.decode_attention_window(*args)
    finally:
        da.set_interpret_mode(None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---- model level: chunk ticks ≡ monolithic prefill ----------------------

def test_prefill_chunk_matches_monolithic_prefill(target):
    """Driving prefill_chunk to completion reproduces the monolithic
    prefill — graduation logits AND cache contents — including a
    non-participating row (advance 0) whose garbage writes must stay
    above its valid length."""
    m = target
    params, _ = functional_state(m)
    rng = np.random.RandomState(0)
    lens = [7, 2]                       # row 1 sits idle in tick 2
    prompts = [rng.randint(1, 97, (n,)).astype(np.int32) for n in lens]
    C = 4

    mono = m.init_kv_cache(2, 64)
    logits_mono = []
    for s, p in enumerate(prompts):
        lg, mono = functional_apply(
            m, "prefill", params, jnp.asarray(p[None, :]), mono,
            np.int32(s), np.int32(len(p)))
        logits_mono.append(np.asarray(lg)[0])

    chunked = m.init_kv_cache(2, 64)
    pos = [0, 0]
    done_logits = [None, None]
    while any(pos[b] < lens[b] for b in range(2)):
        toks = np.zeros((2, C), np.int32)
        adv = np.zeros((2,), np.int32)
        for b in range(2):
            a = min(C, lens[b] - pos[b])
            if a > 0:
                toks[b, :a] = prompts[b][pos[b]:pos[b] + a]
            adv[b] = a
        lg, chunked = functional_apply(
            m, "prefill_chunk", params, jnp.asarray(toks), chunked,
            jnp.asarray(np.asarray(pos, np.int32)), jnp.asarray(adv))
        lg = np.asarray(lg)
        for b in range(2):
            if adv[b] and pos[b] + adv[b] == lens[b]:
                done_logits[b] = lg[b]
            pos[b] += int(adv[b])

    np.testing.assert_array_equal(np.asarray(chunked.lengths),
                                  np.asarray(lens, np.int32))
    for b in range(2):
        np.testing.assert_allclose(done_logits[b], logits_mono[b],
                                   rtol=2e-5, atol=2e-5)
        for ck, mk_ in zip(chunked.k, mono.k):            # per layer
            np.testing.assert_allclose(
                np.asarray(ck).astype(np.float32)[b, :, :lens[b]],
                np.asarray(mk_).astype(np.float32)[b, :, :lens[b]],
                rtol=1e-5, atol=1e-5)


# ---- engine level: the token-identity matrix ----------------------------

# tier-1 wall budget: the fast lane keeps the 4 corners (chunk extremes
# × dtype × layout, every axis value covered; chunk=4 rides every other
# fast test in this file); the interior combos take the slow lane
_MATRIX_CORNERS = {(1, None, "dense"), (1, "int8", "paged"),
                   (64, None, "paged"), (64, "int8", "dense")}
_MATRIX = [
    pytest.param(c, kv, lay, id=f"{c}-{kv}-{lay}",
                 marks=() if (c, kv, lay) in _MATRIX_CORNERS
                 else pytest.mark.slow)
    for c in (1, 4, 64) for kv in (None, "int8")
    for lay in ("dense", "paged")]


@pytest.mark.parametrize("chunk,kv_dtype,layout", _MATRIX)
def test_chunked_token_identity_matrix(target, prompts, reference,
                                       layout, kv_dtype, chunk):
    """Chunked greedy output ≡ the monolithic rollout across the
    serving matrix — chunk=1 (a tick per token), chunk=64 (every prompt
    completes in one tick) and the interior — with ZERO XLA compiles
    after warmup under slot churn (4 requests over 2 slots).  int8
    engines compare against an int8 MONOLITHIC engine: quantization
    changes logits, never the chunked/monolithic equivalence."""
    kw = dict(kv_layout=layout)
    if layout == "paged":
        kw.update(kv_block_size=8)
    if kv_dtype is None:
        ref = reference
    else:
        ref_eng = InferenceEngine(target, batch_slots=2,
                                  prefill_buckets=[16],
                                  kv_dtype=kv_dtype, **kw)
        for p in prompts:
            ref_eng.add_request(p, max_new_tokens=10)
        ref = ref_eng.run()
    eng = InferenceEngine(target, batch_slots=2, prefill_buckets=[16],
                          prefill_chunk=chunk, kv_dtype=kv_dtype, **kw)
    eng.warmup()
    with compile_counter.assert_no_recompiles(
            f"chunk churn {layout}/{kv_dtype}/C={chunk}"):
        for p in prompts:
            eng.add_request(p, max_new_tokens=10)
        out = eng.run()
    for rr, ss in zip(sorted(ref), sorted(out)):
        np.testing.assert_array_equal(ref[rr], out[ss])
    st = eng.stats
    assert st["chunked_prefill"] and st["prefill_chunk"] == chunk
    assert st["prefill_stall_ms"] == 0
    assert st["prefill_tokens"] == sum(p.size for p in prompts)
    if layout == "paged":
        eng.check_leak_free()


def test_chunked_token_identity_gqa(prompts):
    """The matrix's GQA leg: grouped-query KV through the chunk
    executable, both layouts."""
    tgt = tiny_model(0, num_kv_heads=2)
    ref_eng = InferenceEngine(tgt, batch_slots=2, prefill_buckets=[16])
    for p in prompts:
        ref_eng.add_request(p, max_new_tokens=10)
    ref = ref_eng.run()
    for layout in ("dense", "paged"):
        kw = {"kv_block_size": 8} if layout == "paged" else {}
        eng = InferenceEngine(tgt, batch_slots=2, prefill_chunk=4,
                              kv_layout=layout, **kw)
        for p in prompts:
            eng.add_request(p, max_new_tokens=10)
        out = eng.run()
        for rr, ss in zip(sorted(ref), sorted(out)):
            np.testing.assert_array_equal(ref[rr], out[ss])


def test_chunked_with_spec_decode_token_identity(target, draft, prompts,
                                                 reference):
    """Chunked prefill composes with speculative decoding: prefilling
    slots are excluded from the spec set, the draft catches up at
    graduation, and the stream still matches the plain monolithic
    non-spec rollout — with zero compiles under churn."""
    for layout in ("dense", "paged"):
        kw = {"kv_block_size": 8} if layout == "paged" else {}
        eng = InferenceEngine(target, batch_slots=2,
                              prefill_buckets=[16], prefill_chunk=4,
                              spec_k=2, draft_model=draft,
                              kv_layout=layout, **kw)
        eng.warmup(buckets=eng.buckets)
        with compile_counter.assert_no_recompiles(
                f"chunk+spec churn {layout}"):
            for p in prompts:
                eng.add_request(p, max_new_tokens=10)
            out = eng.run()
        for rr, ss in zip(sorted(reference), sorted(out)):
            np.testing.assert_array_equal(reference[rr], out[ss])
        assert eng.stats["spec_ticks"] > 0
        if layout == "paged":
            eng.check_leak_free()


def test_chunked_preempt_resume_radix_rehit(target):
    """Pool pressure mid-stream preempts a chunked slot; the resume
    goes back through chunked admission, re-hits the progressively
    adopted radix blocks, and the output still matches the roomy
    monolithic reference.  The pool is sized so two full-length slots
    CANNOT coexist (2 + 2×3 shared/distinct blocks > 7), forcing at
    least one preemption."""
    rng = np.random.RandomState(11)
    prefix = rng.randint(1, 97, (16,)).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.randint(1, 97, (4,)).astype(np.int32)])
               for _ in range(4)]
    ref_eng = InferenceEngine(target, batch_slots=2,
                              prefill_buckets=[32])
    for p in prompts:
        ref_eng.add_request(p, max_new_tokens=20)
    ref = ref_eng.run()
    eng = InferenceEngine(target, batch_slots=2, prefill_chunk=4,
                          kv_layout="paged", kv_block_size=8,
                          kv_num_blocks=8)
    for p in prompts:
        eng.add_request(p, max_new_tokens=20)
    out = eng.run()
    for rr, ss in zip(sorted(ref), sorted(out)):
        np.testing.assert_array_equal(ref[rr], out[ss])
    assert eng.stats["preemptions"] >= 1
    # progressive adoption made the shared prefix (and any resumed
    # request's own prompt blocks) radix hits
    assert eng._prefix.hit_blocks > 0
    eng.check_leak_free()


# ---- scheduler: HOL admission memo --------------------------------------

@pytest.mark.parametrize("chunk", [0, 4])
def test_hol_blocked_head_not_reprobed(target, chunk):
    """A head-of-line request refused for lack of blocks must NOT be
    re-probed every tick: the allocator's probe counter stays flat
    until a release actually frees something, then the head admits."""
    eng = InferenceEngine(target, batch_slots=2,
                          prefill_buckets=[16, 40], kv_layout="paged",
                          kv_block_size=8, kv_num_blocks=5,
                          prefill_chunk=chunk)
    rng = np.random.RandomState(5)
    pa = rng.randint(1, 97, (35,)).astype(np.int32)
    pb = rng.randint(1, 97, (5,)).astype(np.int32)
    # A fills the ENTIRE pool: 35 + 5 = 40 tokens = all 5 usable blocks,
    # and the final sampled token is returned without a cache write, so
    # decode never extends — the blocked window below sees no legitimate
    # allocator traffic.  Drive A through its whole prefill first.
    ra = eng.add_request(pa, max_new_tokens=5)
    for _ in range(1 if chunk == 0 else -(-35 // chunk)):
        eng.step()
    rb = eng.add_request(pb, max_new_tokens=4)
    eng.step()                          # ONE probe: refused, memoized
    p0 = eng._alloc.probes
    for _ in range(2):
        eng.step()                      # A decodes; head stays gated
    assert eng._alloc.probes == p0, \
        "blocked head-of-line request was re-probed with nothing freed"
    out = eng.run()                     # A retires -> freed blocks wake B
    assert eng._alloc.probes > p0
    assert len(out[ra]) == 5 and len(out[rb]) == 4
    eng.check_leak_free()


# ---- hot-apply + observability ------------------------------------------

def test_set_prefill_chunk_hot_apply(target, prompts, reference):
    """Flipping a warmed monolithic engine into chunked mode is a
    host-side switch whose one-time chunk compile lands at apply time —
    the traffic window after it stays compile-free and
    token-identical."""
    eng = InferenceEngine(target, batch_slots=2, prefill_buckets=[16])
    eng.warmup(buckets=eng.buckets)
    assert eng.set_prefill_chunk(4)
    assert eng.stats["chunked_prefill"] is True
    with compile_counter.assert_no_recompiles("hot-applied chunk"):
        for p in prompts:
            eng.add_request(p, max_new_tokens=10)
        out = eng.run()
    for rr, ss in zip(sorted(reference), sorted(out)):
        np.testing.assert_array_equal(reference[rr], out[ss])
    assert eng.set_prefill_chunk(0)     # and back off again
    assert eng.stats["chunked_prefill"] is False


def test_itl_columns_and_stall_counter(target):
    """Per-request ITL gap percentiles + the pooled engine columns, and
    the prefill_stall_ms counter: positive for a monolithic engine
    whose staggered admissions stall live decodes, identically zero
    under chunking on the same workload."""
    rng = np.random.RandomState(3)
    work = [(rng.randint(1, 97, (n,)).astype(np.int32), mn)
            for n, mn in zip((5, 9, 7, 11, 6), (6, 8, 10, 7, 9))]

    eng = InferenceEngine(target, batch_slots=2, prefill_buckets=[16])
    rids = [eng.add_request(p, max_new_tokens=mn) for p, mn in work]
    eng.run()
    st = eng.stats
    assert st["prefill_stall_ms"] > 0
    for rid, (_, mn) in zip(rids, work):
        rec = st["per_request"][rid]
        assert len(rec["itl_gaps_ms"]) == mn - 1
        assert rec["itl_ms_p99"] >= rec["itl_ms_p50"] >= 0
    assert st["itl_ms_p99"] >= st["itl_ms_p50"] >= 0

    eng2 = InferenceEngine(target, batch_slots=2, prefill_chunk=4)
    for p, mn in work:
        eng2.add_request(p, max_new_tokens=mn)
    eng2.run()
    st2 = eng2.stats
    assert st2["prefill_stall_ms"] == 0
    assert st2["itl_ms_p99"] >= st2["itl_ms_p50"] >= 0


def test_loadtest_report_itl_columns(target):
    """The loadgen report carries the CO-corrected ITL percentiles next
    to the TTFT ones (satellite a)."""
    from paddle_tpu.inference.loadgen import (SharedPrefixWorkload,
                                              run_loadtest)
    eng = InferenceEngine(target, batch_slots=2, prefill_buckets=[16],
                          prefill_chunk=4)
    eng.warmup()
    wl = SharedPrefixWorkload(97, seed=0, shared_frac=0.0,
                              prefix_len=8, tail_len=(3, 10),
                              max_new=(4, 8))
    rep = run_loadtest(eng, 8, 200.0, workload=wl)
    assert rep["itl_ms_p50"] is not None
    assert rep["itl_ms_p99"] >= rep["itl_ms_p50"] >= 0
    assert rep["num_requests"] == 8


def test_prefill_stall_doctor_rule():
    """The 'prefill-stall' rule: fires on a real stall share with the
    chunked-prefill knob as its machine action, stays silent when
    chunking is already on (its own advice taken), below the window,
    or with the signal absent."""
    from paddle_tpu.observability.doctor import diagnose

    def hits(stats):
        return [v for v in diagnose(stats, "serve")
                if v["bottleneck"] == "prefill-stall"]

    hit = hits({"prefill_stall_ms": 40.0, "decode_ms": 60.0})
    assert hit, "rule did not fire on a 40% stall share"
    act = hit[0]["action"]
    assert act["param"] == "prefill_chunk"
    assert act["env"] == "PADDLE_TPU_CHUNKED_PREFILL"
    assert act["candidates"]
    assert not hits({"prefill_stall_ms": 40.0, "decode_ms": 60.0,
                     "chunked_prefill": True})
    assert not hits({"prefill_stall_ms": 2.0, "decode_ms": 3.0})
    assert not hits({"prefill_stall_ms": 5.0, "decode_ms": 95.0})
    assert not hits({"decode_ms": 95.0})
