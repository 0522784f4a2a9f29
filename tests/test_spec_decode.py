"""Speculative decoding tests: the ISSUE-12 token-identity contract.

The whole value of greedy speculative decoding is that it is a pure
SCHEDULING change — the emitted stream must be bit-identical to the
non-speculative engine's (which test_inference_engine/test_paged_kv
prove equal to the naive full-forward rollout).  This file pins that
down across the serving matrix: dense AND paged targets, fp AND int8 KV
caches, GQA, draft window K ∈ {1, 2, 4}, EOS mid-window — plus the
zero-recompile churn contract for the three new executables (draft
prefill, spec tick, verify window) and the windowed-attention op layer
(composite ≡ sequential single-token oracle; interpret-mode Pallas
kernels ≡ composite).
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
    # a genuinely DIFFERENT model (fewer layers, different init): the
    # acceptance rule must keep output identical even when the draft
    # disagrees with the target
    return tiny_model(1, num_layers=1)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.RandomState(0)
    return [rng.randint(1, 97, (n,)).astype(np.int32)
            for n in (5, 9, 3)]


@pytest.fixture(scope="module")
def reference(target, prompts):
    """The non-speculative dense engine's greedy output — the ground
    truth every spec configuration must reproduce exactly."""
    eng = InferenceEngine(target, batch_slots=2, prefill_buckets=[16])
    for p in prompts:
        eng.add_request(p, max_new_tokens=12)
    return eng.run()


# ---- op level: window attention -----------------------------------------

def test_window_attention_matches_sequential():
    """decode_attention_window(q[:, i]) must equal a sequential chain
    of single-token decode_attention calls — that equivalence IS the
    spec-decode verify correctness argument."""
    rng = np.random.RandomState(0)
    B, S, H, Hkv, D, W = 2, 16, 4, 2, 8, 3
    k = jnp.asarray(rng.randn(B, Hkv, S, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, Hkv, S, D).astype(np.float32))
    q = jnp.asarray(rng.randn(B, W, H, D).astype(np.float32))
    lens = jnp.asarray(np.array([5, 9], np.int32))
    out = da.decode_attention_window(q, k, v, lens)
    for i in range(W):
        ref = da.decode_attention(q[:, i], k, v, lens + i + 1)
        np.testing.assert_allclose(np.asarray(out[:, i]),
                                   np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_paged_window_matches_dense_window():
    """The paged window composite over a scattered pool must equal the
    dense window on identical contents (the paged parity-oracle chain
    extended to W > 1)."""
    rng = np.random.RandomState(1)
    B, S, H, Hkv, D, W, bs = 2, 16, 4, 2, 8, 3, 8
    k = rng.randn(B, Hkv, S, D).astype(np.float32)     # head-major
    v = rng.randn(B, Hkv, S, D).astype(np.float32)
    q = jnp.asarray(rng.randn(B, W, H, D).astype(np.float32))
    lens = jnp.asarray(np.array([4, 8], np.int32))
    tables = np.array([[1, 2], [3, 4]], np.int32)
    pool_k = np.zeros((5, Hkv, bs, D), np.float32)     # head-major
    pool_v = np.zeros_like(pool_k)
    for b in range(B):
        for j in range(S // bs):
            pool_k[tables[b, j]] = k[b, :, j * bs:(j + 1) * bs]
            pool_v[tables[b, j]] = v[b, :, j * bs:(j + 1) * bs]
    dense = da.decode_attention_window(q, jnp.asarray(k), jnp.asarray(v),
                                       lens)
    paged = da.paged_decode_attention_window(
        q, jnp.asarray(pool_k), jnp.asarray(pool_v),
        jnp.asarray(tables), lens)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("quantized", [False, True])
def test_window_kernel_interpret_vs_composite(quantized):
    """Interpret-mode Pallas window kernel ≡ the XLA composite (dense
    layout, kernel-eligible shapes, GQA, fp and int8)."""
    rng = np.random.RandomState(2)
    B, S, H, Hkv, D, W = 2, 128, 4, 2, 64, 3
    q = jnp.asarray(rng.randn(B, W, H, D).astype(np.float32))
    lens = jnp.asarray(np.array([37, 90], np.int32))
    if quantized:
        k = jnp.asarray(rng.randint(-127, 128, (B, Hkv, S, D))
                        .astype(np.int8))
        v = jnp.asarray(rng.randint(-127, 128, (B, Hkv, S, D))
                        .astype(np.int8))
        ks = jnp.asarray(rng.rand(B, Hkv, S).astype(np.float32) * 0.02)
        vs = jnp.asarray(rng.rand(B, Hkv, S).astype(np.float32) * 0.02)
        args = (q, k, v, lens, ks, vs)
    else:
        k = jnp.asarray(rng.randn(B, Hkv, S, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, Hkv, S, D).astype(np.float32))
        args = (q, k, v, lens)
    ref = da._window_composite(q, args[1], args[2], lens,
                               *(args[4:] if quantized else ()))
    da.set_interpret_mode(True)
    try:
        out = da.decode_attention_window(*args)
    finally:
        da.set_interpret_mode(None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("quantized", [False, True])
def test_paged_window_kernel_interpret_vs_composite(quantized):
    """Interpret-mode scalar-prefetch paged window kernel ≡ the gather
    composite."""
    rng = np.random.RandomState(3)
    B, H, Hkv, D, W, bs, nb, mb = 2, 4, 2, 64, 3, 128, 5, 2
    q = jnp.asarray(rng.randn(B, W, H, D).astype(np.float32))
    tables = jnp.asarray(np.array([[1, 2], [3, 4]], np.int32))
    lens = jnp.asarray(np.array([100, 200], np.int32))
    if quantized:
        kp = jnp.asarray(rng.randint(-127, 128, (nb, Hkv, bs, D))
                         .astype(np.int8))
        vp = jnp.asarray(rng.randint(-127, 128, (nb, Hkv, bs, D))
                         .astype(np.int8))
        ks = jnp.asarray(rng.rand(nb, Hkv, bs).astype(np.float32) * 0.02)
        vs = jnp.asarray(rng.rand(nb, Hkv, bs).astype(np.float32) * 0.02)
        args = (q, kp, vp, tables, lens, ks, vs)
        ref = da._paged_window_composite(*args)
    else:
        kp = jnp.asarray(rng.randn(nb, Hkv, bs, D).astype(np.float32))
        vp = jnp.asarray(rng.randn(nb, Hkv, bs, D).astype(np.float32))
        args = (q, kp, vp, tables, lens)
        ref = da._paged_window_composite(*args)
    da.set_interpret_mode(True)
    try:
        out = da.paged_decode_attention_window(*args)
    finally:
        da.set_interpret_mode(None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---- model level: verify_step ≡ sequential decode -----------------------

@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_verify_step_matches_sequential(target, kv_dtype):
    """One verify_step window over W tokens reproduces W sequential
    decode_step calls — logits at every position, cache contents
    included (fp bitwise-tight tolerance; int8 goes through the SAME
    quantization on both paths so it stays tight too)."""
    m = target
    params, _ = functional_state(m)
    rng = np.random.RandomState(0)
    prompt = rng.randint(1, 97, (2, 5)).astype(np.int32)
    toks = rng.randint(1, 97, (2, 3)).astype(np.int32)
    cache = m.init_kv_cache(2, 64, kv_dtype=kv_dtype)
    for s in range(2):
        _, cache = functional_apply(
            m, "prefill", params, jnp.asarray(prompt[s:s + 1]), cache,
            np.int32(s), np.int32(5))
    seq_cache = cache
    seq_logits = []
    for i in range(3):
        lg, seq_cache = functional_apply(
            m, "decode_step", params, jnp.asarray(toks[:, i]),
            seq_cache, jnp.ones(2, jnp.int32))
        seq_logits.append(np.asarray(lg))
    win_logits, win_cache = functional_apply(
        m, "verify_step", params, jnp.asarray(toks), cache)
    win_logits = np.asarray(win_logits)
    for i in range(3):
        np.testing.assert_allclose(win_logits[:, i], seq_logits[i],
                                   rtol=2e-5, atol=2e-5)
    for win_k, seq_k in zip(win_cache.k, seq_cache.k):    # per layer
        np.testing.assert_allclose(
            np.asarray(win_k).astype(np.float32)[:, :, :8],
            np.asarray(seq_k).astype(np.float32)[:, :, :8],
            rtol=1e-5, atol=1e-5)


# ---- engine level: the token-identity matrix ----------------------------

# tier-1 wall budget: the fast lane keeps the 4 corners (k extremes ×
# dtype × layout, every axis value covered); the 8 interior combos of
# the k × dtype × layout cube ride the slow lane
_MATRIX_CORNERS = {(1, None, "dense"), (1, "int8", "paged"),
                   (4, None, "paged"), (4, "int8", "dense")}
_MATRIX = [
    pytest.param(k, kv, lay, id=f"{k}-{kv}-{lay}",
                 marks=() if (k, kv, lay) in _MATRIX_CORNERS
                 else pytest.mark.slow)
    for k in (1, 2, 4) for kv in (None, "int8")
    for lay in ("dense", "paged")]


@pytest.mark.parametrize("k,kv_dtype,layout", _MATRIX)
def test_spec_token_identity_matrix(target, draft, prompts, reference,
                                    layout, kv_dtype, k):
    """Greedy speculative output ≡ the non-speculative rollout across
    the full serving matrix, with ZERO XLA compiles after warmup (the
    draft-prefill / spec-tick / verify executables are shape-stable).
    int8 targets are compared against an int8 NON-spec engine — the
    identity claim is per-configuration (quantization changes logits,
    never the spec/non-spec equivalence)."""
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
            ref_eng.add_request(p, max_new_tokens=12)
        ref = ref_eng.run()
    eng = InferenceEngine(target, batch_slots=2, prefill_buckets=[16],
                          spec_k=k, draft_model=draft,
                          kv_dtype=kv_dtype, **kw)
    eng.warmup(buckets=eng.buckets)
    with compile_counter.assert_no_recompiles(
            f"spec churn {layout}/{kv_dtype}/K={k}"):
        for p in prompts:
            eng.add_request(p, max_new_tokens=12)
        out = eng.run()
    for rr, ss in zip(sorted(ref), sorted(out)):
        np.testing.assert_array_equal(ref[rr], out[ss])
    if layout == "paged":
        eng.check_leak_free()
    st = eng.stats
    assert st["spec_ticks"] > 0
    assert st["accepted_tokens_per_tick"] >= 1.0


def test_spec_token_identity_gqa(prompts):
    """The matrix's GQA leg: grouped-query target + draft."""
    tgt = tiny_model(0, num_kv_heads=2)
    drf = tiny_model(1, num_kv_heads=2, num_layers=1)
    ref_eng = InferenceEngine(tgt, batch_slots=2, prefill_buckets=[16])
    for p in prompts:
        ref_eng.add_request(p, max_new_tokens=12)
    ref = ref_eng.run()
    for layout in ("dense", "paged"):
        kw = {"kv_block_size": 8} if layout == "paged" else {}
        eng = InferenceEngine(tgt, batch_slots=2, prefill_buckets=[16],
                              spec_k=2, draft_model=drf,
                              kv_layout=layout, **kw)
        for p in prompts:
            eng.add_request(p, max_new_tokens=12)
        out = eng.run()
        for rr, ss in zip(sorted(ref), sorted(out)):
            np.testing.assert_array_equal(ref[rr], out[ss])


def test_spec_eos_mid_window(target, draft):
    """EOS landing INSIDE an accepted window truncates exactly where
    the sequential rollout stops — find a prompt whose greedy rollout
    emits some token t, declare t the EOS id, and check both engines
    stop identically."""
    rng = np.random.RandomState(7)
    hit = 0
    for trial in range(12):
        prompt = rng.randint(1, 97, (rng.randint(3, 9),)).astype(np.int32)
        ref_eng = InferenceEngine(target, batch_slots=1,
                                  prefill_buckets=[16])
        base = ref_eng.generate(prompt, max_new_tokens=10)
        if len(base) < 3:
            continue
        eos = int(base[len(base) // 2])    # a token mid-stream
        ref_eng2 = InferenceEngine(target, batch_slots=1,
                                   prefill_buckets=[16])
        want = ref_eng2.generate(prompt, max_new_tokens=10, eos_id=eos)
        spec = InferenceEngine(target, batch_slots=1,
                               prefill_buckets=[16], spec_k=3,
                               draft_model=draft)
        got = spec.generate(prompt, max_new_tokens=10, eos_id=eos)
        np.testing.assert_array_equal(want, got)
        assert int(got[-1]) == eos
        hit += 1
        if hit >= 3:
            break
    assert hit >= 1, "no rollout long enough to plant a mid-stream EOS"


def test_spec_self_draft_accepts_everything(target, prompts):
    """Drafting with the target itself is the acceptance ceiling: every
    proposal matches, so each tick commits K+1 tokens except the final
    max-new-truncated window (metrics count tokens that actually
    reached the stream: 11 remaining tokens over 3 ticks per request =
    3.67/tick at K=3) — the harness the fleet smoke leans on."""
    eng = InferenceEngine(target, batch_slots=2, prefill_buckets=[16],
                          spec_k=3, draft_model=target)
    for p in prompts:
        eng.add_request(p, max_new_tokens=12)
    eng.run()
    st = eng.stats
    assert st["accepted_tokens_per_tick"] > 3.0      # ceiling K+1 = 4
    assert st["spec_acceptance_rate"] > 0.85
    assert st["spec_capacity_retirements"] == 0


def test_spec_sampled_seeded_determinism(target, draft):
    """Sampled-request speculation (ISSUE 18): temperature>0 requests
    ride the spec path (full rejection-sampling residual) and a seeded
    engine replays the exact same stream — the determinism half of the
    correctness contract (distribution fidelity is pinned by
    test_spec_sampled_residual_distribution)."""
    prompt = np.array([1, 2, 3], np.int32)

    def run(seed):
        eng = InferenceEngine(target, batch_slots=2,
                              prefill_buckets=[16], seed=seed,
                              spec_k=2, draft_model=draft)
        r_s = eng.add_request(prompt, max_new_tokens=10,
                              temperature=0.8, top_p=0.9)
        r_g = eng.add_request(prompt, max_new_tokens=10)
        out = eng.run()
        return out[r_s], out[r_g]

    s0, g0 = run(7)
    s1, g1 = run(7)
    s2, _ = run(8)
    np.testing.assert_array_equal(s0, s1)
    np.testing.assert_array_equal(g0, g1)
    assert len(s0) == 10 and len(s2) == 10
    # the greedy slot of a mixed batch must still match the greedy
    # reference engine exactly (the sampled neighbor consumes PRNG but
    # greedy outputs never depend on it)
    ref = InferenceEngine(target, batch_slots=1, prefill_buckets=[16])
    rid = ref.add_request(prompt, max_new_tokens=10)
    np.testing.assert_array_equal(g0, ref.run()[rid])


def test_spec_sampled_residual_distribution(target, draft):
    """The rejection-sampling identity, checked exactly where it must
    hold: for draft ~ q, accept with min(1, p/q), else resample from
    norm(max(p-q, 0)) — the committed token's marginal IS p.  Run
    SpecDecoder._accept over thousands of independent rows with known
    p != q and bound the total-variation distance of the committed
    first token against p, plus the acceptance rate against the
    distribution overlap sum(min(p, q))."""
    import jax

    eng = InferenceEngine(target, batch_slots=1, prefill_buckets=[16],
                          spec_k=1, draft_model=draft)
    sd = eng._spec
    rng = np.random.RandomState(0)
    V, N = 8, 8192
    p = np.array([.30, .20, .15, .10, .10, .08, .05, .02], np.float32)
    q = p[::-1].copy()                      # reversed: TV(p, q) = 0.46
    drafts = rng.choice(V, size=(N, 1),
                        p=q / q.sum()).astype(np.int32)
    # temps=1, top_p=1, top_k=0 make the warped target distribution
    # exactly softmax(logits) = p at every position
    logits = np.broadcast_to(np.log(p), (N, 2, V)).astype(np.float32)
    toks, n_acc, n_emit, _ = jax.jit(sd._accept)(
        jnp.asarray(drafts),
        jnp.asarray(np.broadcast_to(q, (N, 1, V)).copy()),
        jnp.asarray(logits), jnp.ones(N, jnp.int32),
        jax.random.PRNGKey(0), jnp.ones(N, jnp.float32),
        jnp.ones(N, jnp.float32))
    assert int(np.asarray(n_emit).min()) >= 1
    h = np.bincount(np.asarray(toks[:, 0]), minlength=V) / N
    tv = 0.5 * float(np.abs(h - p).sum())
    # statistical floor at N=8192 is ~0.015; sampling q instead of the
    # residual (or always taking the draft) lands near TV(p,q)=0.46
    assert tv < 0.05, f"committed-token marginal diverged from p: {tv}"
    acc = float(np.asarray(n_acc).mean())
    overlap = float(np.minimum(p, q).sum())
    assert abs(acc - overlap) < 0.05, (acc, overlap)


def test_spec_draft_validation(target):
    """Draft/target contract checks: vocab and position-table
    mismatches raise at construction."""
    bad_vocab = tiny_model(2, vocab_size=64)
    with pytest.raises(ValueError, match="vocab"):
        InferenceEngine(target, batch_slots=1, spec_k=2,
                        draft_model=bad_vocab)
    bad_seq = tiny_model(2, max_seq_len=32)
    with pytest.raises(ValueError, match="max_seq_len"):
        InferenceEngine(target, batch_slots=1, spec_k=2,
                        draft_model=bad_seq)
    with pytest.raises(ValueError, match="draft_model"):
        InferenceEngine(target, batch_slots=1, spec_k=2)


def test_spec_preemption_resume_identity(target, draft):
    """A spec engine under pool pressure (preempt-to-queue) still
    reproduces the non-speculative output: the resume prefill re-seeds
    both the target blocks and the draft cache."""
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, 97, (6,)).astype(np.int32)
               for _ in range(4)]
    ref_eng = InferenceEngine(target, batch_slots=2,
                              prefill_buckets=[8, 16])
    for p in prompts:
        ref_eng.add_request(p, max_new_tokens=10)
    ref = ref_eng.run()
    # a pool just big enough to admit but tight enough to preempt
    eng = InferenceEngine(target, batch_slots=2, prefill_buckets=[8, 16],
                          kv_layout="paged", kv_block_size=8,
                          kv_num_blocks=7, spec_k=2, draft_model=draft)
    for p in prompts:
        eng.add_request(p, max_new_tokens=10)
    out = eng.run()
    for rr, ss in zip(sorted(ref), sorted(out)):
        np.testing.assert_array_equal(ref[rr], out[ss])
    eng.check_leak_free()
