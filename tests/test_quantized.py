"""Quantized compute path tests (ISSUE 7): int8/fp8 matmul + fake-quant
VJP, quantized KV caches, the kernel's tiles.

The contracts under test:
- ops.quantized_matmul: the Pallas int8 kernel reproduces the XLA
  composite (the CPU parity oracle) bitwise-within-epsilon, and the
  composite tracks the fp matmul at int8 tolerance;
- ops.fake_quant_matmul's custom VJP ≡ the straight-through-estimator
  reference ``fq(x) @ fq(w)`` with ``fq(t) = t + sg(qdq(t) - t)`` —
  values AND grads;
- GPTConfig(quantize='int8') / strategy.qat train (loss decreases,
  params move) without touching the optimizer;
- int8 KV decode stays within tolerance of the dense decode on BOTH
  cache layouts (static and paged, GQA included), and a warmed int8
  engine churns admissions/retirements with ZERO recompiles;
- the kernel's tiles are its defaults clamped to divide the problem.
"""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.inference import InferenceEngine
from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                               GPTPretrainingCriterion)
from paddle_tpu.utils import compile_counter

qm = importlib.import_module("paddle_tpu.ops.quantized_matmul")
da = importlib.import_module("paddle_tpu.ops.decode_attention")

TINY = dict(vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
            max_seq_len=64, use_flash_attention=False)


def tiny_model(**over):
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(**{**TINY, **over}))
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return tiny_model()


@pytest.fixture(scope="module")
def int8_dense_eng(model):
    """Shared warmed int8 dense-layout engine (tier-1 budget: one
    construction + warmup serves the churn and rollout tests)."""
    eng = InferenceEngine(model, batch_slots=2, prefill_buckets=[8],
                          kv_dtype="int8")
    eng.warmup(buckets=[8])
    return eng


@pytest.fixture(scope="module")
def int8_paged_eng(model):
    """Shared warmed int8 paged-layout engine (churn + prefix-hit)."""
    eng = InferenceEngine(model, batch_slots=2, prefill_buckets=[8, 16],
                          kv_layout="paged", kv_block_size=8,
                          kv_dtype="int8")
    eng.warmup(buckets=eng.buckets)
    return eng


# ---------------------------------------------------------------------------
# quantized matmul op
# ---------------------------------------------------------------------------
def _xw(m=32, k=256, n=128, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(m, k).astype(np.float32)),
            jnp.asarray(rng.randn(k, n).astype(np.float32)))


def test_quantized_matmul_composite_tracks_fp():
    x, w = _xw()
    y = qm.quantized_matmul(x, w)
    ref = x @ w
    rel = float(jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 0.05, rel          # int8 noise, not garbage
    assert y.dtype == x.dtype


def test_quantized_matmul_kernel_matches_composite():
    """Pallas int8 kernel (interpret mode) vs the dot_general composite:
    both accumulate in exact int32, so the only difference is the f32
    rescale ordering — epsilon, not tolerance."""
    x, w = _xw()
    ref = qm.quantized_matmul(x, w)          # composite on CPU
    qm._fa.set_interpret_mode(True)
    try:
        out = qm.quantized_matmul(x, w)      # kernel path
    finally:
        qm._fa.set_interpret_mode(False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_fake_quant_vjp_matches_ste_reference():
    """The custom VJP ≡ grad of fq(x)@fq(w) with straight-through
    fake-quant — grads bit-for-bit, forward at fp-reassociation eps."""
    x, w = _xw(m=12, k=96, n=40, seed=1)     # odd shapes: composite path

    def qdq(t, axis):
        q, s = qm.quantize_channel(t, axis=axis)
        return (q.astype(jnp.float32) * s).astype(t.dtype)

    def ref(x, w):
        fx = x + jax.lax.stop_gradient(qdq(x, 1) - x)
        fw = w + jax.lax.stop_gradient(qdq(w, 0) - w)
        return (fx @ fw).sum()

    def fq(x, w):
        return qm.fake_quant_matmul(x, w).sum()

    assert float(ref(x, w)) == pytest.approx(float(fq(x, w)), rel=1e-5)
    gr = jax.grad(ref, argnums=(0, 1))(x, w)
    gf = jax.grad(fq, argnums=(0, 1))(x, w)
    np.testing.assert_array_equal(np.asarray(gf[0]), np.asarray(gr[0]))
    np.testing.assert_array_equal(np.asarray(gf[1]), np.asarray(gr[1]))


def test_fake_quant_matmul_leading_dims_and_dtype():
    rng = np.random.RandomState(2)
    x3 = jnp.asarray(rng.randn(2, 8, 64).astype(np.float32))
    w = jnp.asarray(rng.randn(64, 32).astype(np.float32))
    y = qm.fake_quant_matmul(x3, w)
    assert y.shape == (2, 8, 32) and y.dtype == x3.dtype


def test_quantize_mode_validation():
    with pytest.raises(ValueError, match="quantize dtype"):
        GPTConfig(**{**TINY, "quantize": "int4"})
    # MoE expert FFNs have no quantized path: raising beats silently
    # quantizing only attention and misattributing the measured MFU
    with pytest.raises(NotImplementedError, match="MoE"):
        GPTConfig(**{**TINY, "quantize": "int8", "moe_num_experts": 2})
    assert qm.resolve_kv_quant("") is None
    assert qm.resolve_kv_quant("int8") == "int8"
    with pytest.raises(ValueError):
        qm.resolve_kv_quant("int4")


def test_kv_quant_roundtrip_idempotent():
    """Requantizing a dequantized buffer with fresh per-token scales is
    exact (amax positions land on ±127), which is what lets the paged
    prefill requant-scatter untouched prefix blocks bit-for-bit."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(5, 4, 64).astype(np.float32))
    q1, s1 = qm.quantize_kv(x)
    deq = qm.dequantize_kv(q1, s1)
    q2, s2 = qm.quantize_kv(deq)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-6, atol=0)


# ---------------------------------------------------------------------------
# quantized training (AQT / straight-through)
# ---------------------------------------------------------------------------
def test_quantized_training_and_strategy_qat():
    """GPTConfig(quantize='int8') trains through the compiled trainer
    (loss decreases, optimizer untouched), and strategy.qat=True on an
    unquantized model reproduces the same first steps exactly."""
    from paddle_tpu.distributed import SpmdTrainer, create_mesh
    from paddle_tpu.distributed.fleet import DistributedStrategy

    cfg = GPTConfig(**{**TINY, "quantize": "int8"})
    crit = GPTPretrainingCriterion()
    mesh = create_mesh({"dp": 1}, devices=jax.devices()[:1])
    rng = np.random.RandomState(0)
    ids = rng.randint(0, TINY["vocab_size"], (4, 32)).astype(np.int32)
    lab = np.roll(ids, -1, 1).astype(np.int32)

    paddle.seed(0)
    m = GPTForCausalLM(cfg)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=m.parameters())
    tr = SpmdTrainer(m, opt, lambda o, l: crit(o, l), mesh=mesh,
                     strategy=DistributedStrategy())
    losses = [float(tr.train_step(ids, lab)) for _ in range(5)]
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], losses

    paddle.seed(0)
    m2 = GPTForCausalLM(GPTConfig(**TINY))
    opt2 = paddle.optimizer.Adam(learning_rate=1e-3,
                                 parameters=m2.parameters())
    st = DistributedStrategy()
    st.qat = True
    tr2 = SpmdTrainer(m2, opt2, lambda o, l: crit(o, l), mesh=mesh,
                      strategy=st)
    assert m2.cfg.quantize == "int8"        # enable_quantize() ran
    l2 = [float(tr2.train_step(ids, lab)) for _ in range(2)]
    np.testing.assert_allclose(l2, losses[:2], rtol=1e-6)


# ---------------------------------------------------------------------------
# int8 KV cache: static layout
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kv_heads", [
    None,
    # tier-1 wall budget: GQA variant rides the slow lane
    pytest.param(2, marks=pytest.mark.slow)])
def test_int8_kv_decode_tracks_dense_static(model, kv_heads):
    """prefill + teacher-forced decode over an int8 StaticKVCache stays
    within quantization tolerance of the full forward at every step
    (GQA covered)."""
    m = model if kv_heads is None else tiny_model(num_kv_heads=kv_heads)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 97, (1, 10)).astype(np.int32)
    full = np.asarray(m(paddle.to_tensor(ids)).data)     # [1, 10, V]
    scale = float(np.max(np.abs(full)))

    cache = m.init_kv_cache(batch_slots=2, kv_dtype="int8")
    assert cache.quantized and cache.dtype == jnp.int8
    logits, cache = m.prefill(jnp.asarray(ids[:, :7]), cache, 0, 7)
    # prefill attends the fp k/v (only the stored copy is quantized):
    # bitwise the dense prefill
    np.testing.assert_allclose(np.asarray(logits)[0], full[0, 6],
                               rtol=1e-4, atol=1e-4)
    for t in range(7, 9):
        toks = np.zeros(2, np.int32)
        toks[0] = ids[0, t]
        lg, cache = m.decode_step(jnp.asarray(toks), cache,
                                  jnp.asarray([1, 0], jnp.int32))
        diff = float(np.max(np.abs(np.asarray(lg)[0] - full[0, t])))
        assert diff < 0.05 * scale, (t, diff, scale)


# ---------------------------------------------------------------------------
# int8 KV cache: paged layout
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kv_heads", [
    None,
    # tier-1 wall budget: GQA variant rides the slow lane
    pytest.param(2, marks=pytest.mark.slow)])
def test_int8_kv_decode_tracks_dense_paged(model, kv_heads):
    """Same contract over a paged int8 pool: manual block tables, cold
    prefill + teacher-forced paged decode vs the full forward."""
    from paddle_tpu.inference.paged_kv import init_paged_cache
    m = model if kv_heads is None else tiny_model(num_kv_heads=kv_heads)
    bs, mb = 8, 2                            # 16 positions: covers 10
    rng = np.random.RandomState(1)
    ids = rng.randint(0, 97, (1, 10)).astype(np.int32)
    full = np.asarray(m(paddle.to_tensor(ids)).data)
    scale = float(np.max(np.abs(full)))

    cache = init_paged_cache(m, num_blocks=1 + mb, block_size=bs,
                             kv_dtype="int8")
    assert cache.quantized and cache.k.dtype == jnp.int8
    row = np.arange(1, mb + 1, dtype=np.int32)   # blocks 1..mb
    padded = np.zeros((1, 16), np.int32)
    padded[0, :7] = ids[0, :7]
    logits, cache = m.prefill_paged(jnp.asarray(padded), cache,
                                    jnp.asarray(row), 0, np.int32(7))
    np.testing.assert_allclose(np.asarray(logits)[0], full[0, 6],
                               rtol=1e-4, atol=1e-4)
    # 2 steps: position 8 crosses into the slot's second block
    lengths = np.asarray([7], np.int64)
    for t in range(7, 9):
        toks = jnp.asarray([ids[0, t]], jnp.int32)
        lg, cache = m.decode_step_paged(
            toks, cache, jnp.asarray(row[None]),
            jnp.asarray(lengths.astype(np.int32)))
        lengths += 1
        diff = float(np.max(np.abs(np.asarray(lg)[0] - full[0, t])))
        assert diff < 0.05 * scale, (t, diff, scale)


def test_paged_quant_op_parity_with_dense_quant_op():
    """ops-level: paged int8 decode attention through a shuffled block
    table ≡ dense int8 decode attention on identical cache contents
    (both composites), and the interpret-mode kernels match them."""
    rng = np.random.RandomState(4)
    b, s, h, hkv, d, bs = 2, 256, 4, 2, 64, 128
    q = jnp.asarray(rng.randn(b, h, d).astype(np.float32) * 0.3)
    k = rng.randn(b, hkv, s, d).astype(np.float32) * 0.3   # head-major
    v = rng.randn(b, hkv, s, d).astype(np.float32) * 0.3
    lengths = jnp.asarray([37, 256], jnp.int32)
    qk, sk = qm.quantize_kv(jnp.asarray(k))
    qv, sv = qm.quantize_kv(jnp.asarray(v))
    dense = da._decode_composite(q, qk, qv, lengths, sk, sv)

    mb = s // bs
    tables = (1 + rng.permutation(b * mb)).reshape(b, mb).astype(np.int32)
    nb = b * mb + 1
    # pools are head-major: [nb, hkv, bs, d] values, [nb, hkv, bs] scales
    kp = np.zeros((nb, hkv, bs, d), np.int8)
    vp = np.zeros((nb, hkv, bs, d), np.int8)
    ksp = np.zeros((nb, hkv, bs), np.float32)
    vsp = np.zeros((nb, hkv, bs), np.float32)
    for bi in range(b):
        for j in range(mb):
            rows = slice(j * bs, (j + 1) * bs)
            kp[tables[bi, j]] = np.asarray(qk)[bi, :, rows]
            vp[tables[bi, j]] = np.asarray(qv)[bi, :, rows]
            ksp[tables[bi, j]] = np.asarray(sk)[bi, :, rows]
            vsp[tables[bi, j]] = np.asarray(sv)[bi, :, rows]
    paged = da._paged_composite(q, jnp.asarray(kp), jnp.asarray(vp),
                                jnp.asarray(tables), lengths,
                                jnp.asarray(ksp), jnp.asarray(vsp))
    np.testing.assert_array_equal(np.asarray(paged), np.asarray(dense))

    da.set_interpret_mode(True)
    try:
        kd = da.decode_attention(q, qk, qv, lengths, sk, sv)
        kpg = da.paged_decode_attention(
            q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
            lengths, jnp.asarray(ksp), jnp.asarray(vsp))
    finally:
        da.set_interpret_mode(None)
    np.testing.assert_allclose(np.asarray(kd), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(kpg), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# zero-recompile churn over quantized engines
# ---------------------------------------------------------------------------
def test_quantized_decode_zero_recompile_churn(int8_dense_eng,
                                               int8_paged_eng):
    """THE acceptance leg: warmed int8 engines (dense AND paged layout)
    churn admissions/retirements with 0 XLA compiles and 0 jaxpr
    traces — the scale operands are as shape-stable as the caches."""
    rng = np.random.RandomState(5)
    for eng in (int8_dense_eng, int8_paged_eng):
        assert eng.stats["kv_dtype"] == "int8"
        # flush one request through to touch lazy host one-offs
        eng.add_request(rng.randint(1, 97, (4,)).astype(np.int32),
                        max_new_tokens=2)
        eng.run()
        with compile_counter.assert_no_recompiles(
                f"int8 {eng.kv_layout} decode churn"):
            rids = [eng.add_request(
                rng.randint(1, 97, (n,)).astype(np.int32),
                max_new_tokens=5) for n in (3, 6, 4)]
            outs = eng.run()
        assert all(len(outs[r]) == 5 for r in rids)


def test_int8_prefix_hit_matches_cold(int8_paged_eng):
    """Radix-cache hit over QUANTIZED prefix blocks: the hit admission
    dequant-gathers the cached int8 prefix, prefills only the suffix,
    and requant-scatters — and still reproduces the cold request's
    exact tokens (the requant-idempotency property end to end)."""
    eng = int8_paged_eng
    rng = np.random.RandomState(7)
    prompt = rng.randint(1, 97, (13,)).astype(np.int32)
    r1 = eng.add_request(prompt, max_new_tokens=5)
    out1 = eng.run()[r1]
    h0 = eng._prefix.hit_queries
    r2 = eng.add_request(prompt, max_new_tokens=5)
    out2 = eng.run()[r2]
    assert eng._prefix.hit_queries == h0 + 1
    assert out2.tolist() == out1.tolist()
    eng.flush_prefix_cache()
    eng._alloc.check_leak_free()


def test_int8_engine_matches_model_level_rollout(model, int8_dense_eng):
    """The int8 dense engine's greedy tokens ≡ a model-level int8-cache
    greedy rollout (same executable math, scheduler adds nothing)."""
    rng = np.random.RandomState(6)
    prompt = rng.randint(1, 97, (6,)).astype(np.int32)
    rid = int8_dense_eng.add_request(prompt, max_new_tokens=4)
    out = int8_dense_eng.run()[rid]

    padded = np.zeros((1, 8), np.int32)
    padded[0, :6] = prompt
    cache = model.init_kv_cache(1, kv_dtype="int8")
    lg, cache = model.prefill(jnp.asarray(padded), cache, 0, 6)
    toks = [int(np.argmax(np.asarray(lg)[0]))]
    act = jnp.ones((1,), jnp.int32)
    for _ in range(3):
        lg, cache = model.decode_step(
            jnp.asarray([toks[-1]], jnp.int32), cache, act)
        toks.append(int(np.argmax(np.asarray(lg)[0])))
    assert out.tolist() == toks


# ---------------------------------------------------------------------------
# decode HBM byte accounting
# ---------------------------------------------------------------------------
def test_decode_hbm_bytes_per_tok_int8_smaller(model):
    fp = InferenceEngine(model, batch_slots=2, prefill_buckets=[16])
    q8 = InferenceEngine(model, batch_slots=2, prefill_buckets=[16],
                         kv_dtype="int8")
    b_fp = fp.stats["decode_hbm_bytes_per_tok"]
    b_q8 = q8.stats["decode_hbm_bytes_per_tok"]
    assert b_fp > 0 and b_q8 > 0
    # int8 shrinks the KV values but adds an f32 scale per (position,
    # head): 16 + 4 bytes against 64 at this model's d=16 in f32
    assert b_q8 < b_fp
    cfg = model.cfg
    kv_fp = 2 * cfg.num_layers * fp.max_seq_len * cfg.num_kv_heads * \
        cfg.head_dim * 4            # f32 cache on CPU
    assert b_fp >= kv_fp            # params amortized on top


# ---------------------------------------------------------------------------
# the kernel's tiles
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,n,k,want", [
    (64, 256, 512, (64, 256, 512)),          # smaller than the defaults
    (1024, 1024, 4096, (256, 256, 512)),     # the MXU-sized defaults
    (96, 384, 640, (32, 128, 128)),          # clamped to divide
])
def test_qmm_tiles_divide_the_problem(m, n, k, want):
    assert qm.get_qmm_tiles(m, n, k) == want
