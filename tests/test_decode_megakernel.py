"""Decode megakernel (ISSUE 11): the fused per-layer decode step must be
tolerance-equal (1e-5) to the composed kernels path across fp/int8 ×
dense/paged × GQA, keep the zero-recompile decode contract, and the
sweep/tuning satellites must behave (bench resume, nearest-shape tuning
fallbacks, remat-policy table, decode HBM byte accounting)."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.ops import decode_megakernel as mk
from paddle_tpu.ops.quantized_matmul import quantize_kv
from paddle_tpu.utils import compile_counter
from paddle_tpu.utils import tuning as _tuning

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TOL = 1e-5


def _weights(rng, h, hkv, d, f):
    kvd = hkv * d

    def r(*s):
        return jnp.asarray(rng.randn(*s).astype(np.float32) * 0.05)

    return (r(h) + 1.0, r(h), r(h, h + 2 * kvd), r(h + 2 * kvd),
            r(h, h), r(h), r(h) + 1.0, r(h), r(h, f), r(f), r(f, h),
            r(h))


# ---------------------------------------------------------------------------
# op level: interpret-mode Pallas kernel ≡ XLA composite
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("hkv", [2, 1])  # MHA and GQA (2 heads)
def test_kernel_matches_composite(paged, quantized, hkv):
    """Pallas megakernel (interpret) vs the XLA composite across the
    fp/int8 × dense/paged × GQA matrix, lengths pinned at the prefix
    boundaries (0, 1, block edge, block edge - 1, cap - 1)."""
    rng = np.random.RandomState(0)
    B, heads, d, f = 5, 2, 64, 256
    h = heads * d
    cap = 256
    w = _weights(rng, h, hkv, d, f)
    x = jnp.asarray(rng.randn(B, h).astype(np.float32) * 0.1)
    lengths = jnp.asarray([0, 1, 127, 128, 255], jnp.int32)
    if paged:
        bs = 128
        mb = cap // bs
        nb = B * mb + 1
        kp = jnp.asarray(rng.randn(nb, hkv, bs, d).astype(np.float32)
                         * 0.1)
        vp = jnp.asarray(rng.randn(nb, hkv, bs, d).astype(np.float32)
                         * 0.1)
        tables = jnp.asarray(
            np.arange(1, B * mb + 1).reshape(B, mb), jnp.int32)
        if quantized:
            kq, ks = quantize_kv(kp)
            vq, vs = quantize_kv(vp)
            args = (x, w, kq, vq, tables, lengths, ks, vs)
        else:
            args = (x, w, kp, vp, tables, lengths)
        fn = mk.decode_layer_step_paged
    else:
        k = jnp.asarray(rng.randn(B, hkv, cap, d).astype(np.float32)
                        * 0.1)
        v = jnp.asarray(rng.randn(B, hkv, cap, d).astype(np.float32)
                        * 0.1)
        if quantized:
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            args = (x, w, kq, vq, lengths, ks, vs)
        else:
            args = (x, w, k, v, lengths)
        fn = mk.decode_layer_step

    mk.set_interpret_mode(False)       # CPU: forces the composite
    try:
        xc, kc, vc = jax.jit(lambda *a: fn(*a))(*args)
        mk.set_interpret_mode(True)
        assert mk.decode_megakernel_available()
        xk, kk, vk = jax.jit(lambda *a: fn(*a))(*args)
    finally:
        mk.set_interpret_mode(None)
    np.testing.assert_allclose(np.asarray(xk), np.asarray(xc), atol=TOL,
                               rtol=0)
    np.testing.assert_allclose(np.asarray(kk), np.asarray(kc), atol=TOL,
                               rtol=0)
    np.testing.assert_allclose(np.asarray(vk), np.asarray(vc), atol=TOL,
                               rtol=0)


def test_kernel_gate_falls_back_not_crashes():
    """Unfriendly shapes (h % 128 != 0) must route the composite, not
    raise — the gate is what keeps tiny test configs working."""
    rng = np.random.RandomState(1)
    B, hkv, d, f = 2, 1, 16, 64   # h=16: kernel-unsupported
    h = 16
    w = _weights(rng, h, hkv, d, f)
    x = jnp.asarray(rng.randn(B, h).astype(np.float32))
    k = jnp.asarray(rng.randn(B, hkv, 32, d).astype(np.float32))
    v = jnp.asarray(rng.randn(B, hkv, 32, d).astype(np.float32))
    mk.set_interpret_mode(True)
    try:
        xo, kn, vn = mk.decode_layer_step(
            x, w, k, v, jnp.asarray([3, 7], jnp.int32))
    finally:
        mk.set_interpret_mode(None)
    assert xo.shape == (B, h) and kn.shape == (B, hkv, d)


def test_vmem_gate_admits_350m_class_config():
    """The ISSUE-12 gate-widening satellite: with the qkv/out-proj
    weight fetches TILED (streamed per phase instead of resident), a
    gpt3-350m-shaped layer (h=1024, f=4096, 16 heads x 64, cap 2048,
    bf16, 8 slots) fits the VMEM budget and runs fused — fp AND int8
    KV — where the resident-qkv estimate used to fall back."""
    h, hkv, d, f, cap, B = 1024, 16, 64, 4096, 2048, 8
    kvd = hkv * d
    shapes = [(h,), (h,), (h, h + 2 * kvd), (h + 2 * kvd,), (h, h),
              (h,), (h,), (h,), (h, f), (f,), (f, h), (h,)]
    w = [jnp.zeros(s, jnp.bfloat16) for s in shapes]
    x = jnp.zeros((B, h), jnp.bfloat16)
    block_s = mk._pick_blocks(cap, f)[0]
    mk.set_interpret_mode(True)     # the gate below the backend check
    try:
        # interpret mode waives the VMEM gate; the estimate is asserted
        # against the budget explicitly below
        assert mk._fused_refusal(x, w, hkv, d, block_s, None,
                                 jnp.bfloat16, 2, False) == ""
        assert mk._fused_refusal(x, w, hkv, d, block_s, None,
                                 jnp.int8, 1, True) == ""
    finally:
        mk.set_interpret_mode(None)
    # the estimate itself sits under the budget with real headroom
    bs2, bf2, bq, bo = mk._pick_blocks(cap, f, h + 2 * kvd, h)
    est = mk._vmem_estimate(h, kvd, f, bs2, bf2, bq, bo, hkv, d, 2, 2,
                            False, B)
    assert est < mk._VMEM_BUDGET
    # a resident qkv+out accounting would NOT have fit: adding those
    # matrices back on top of the streamed tiles blows the budget
    resident_extra = (h * (h + 2 * kvd) + h * h) * 2
    assert est + resident_extra > mk._VMEM_BUDGET


# ---------------------------------------------------------------------------
# model level: fused path ≡ composed path
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def model():
    cfg = GPTConfig(vocab_size=97, hidden_size=64, num_layers=2,
                    num_heads=4, num_kv_heads=2, max_seq_len=64,
                    use_flash_attention=False)
    paddle.seed(0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_megakernel_matches_composed_dense(model, kv_dtype):
    """Fused decode steps (CPU composite) track the composed path's
    logits AND cache contents over several steps, mixed slot lengths,
    GQA model."""
    m = model
    rng = np.random.RandomState(0)
    p0 = rng.randint(0, 97, 9).astype(np.int32)
    p1 = rng.randint(0, 97, 5).astype(np.int32)
    act = jnp.ones((2,), jnp.int32)

    def rollout(fused):
        m.enable_decode_megakernel(fused)
        try:
            c = m.init_kv_cache(2, kv_dtype=kv_dtype)
            ids0 = np.zeros((1, 16), np.int32)
            ids0[0, :9] = p0
            _, c = m.prefill(jnp.asarray(ids0), c, 0, 9)
            ids1 = np.zeros((1, 16), np.int32)
            ids1[0, :5] = p1
            _, c = m.prefill(jnp.asarray(ids1), c, 1, 5)
            toks = jnp.asarray([p0[-1], p1[-1]], jnp.int32)
            outs = []
            for _ in range(3):
                logits, c = m.decode_step(toks, c, act)
                toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                outs.append(np.asarray(logits))
            return outs, c
        finally:
            m.enable_decode_megakernel(False)

    outs_c, cache_c = rollout(False)
    outs_f, cache_f = rollout(True)
    for lc, lf in zip(outs_c, outs_f):
        np.testing.assert_allclose(lf, lc, atol=TOL, rtol=0)
    np.testing.assert_allclose(
        np.asarray(cache_f.k, np.float32),
        np.asarray(cache_c.k, np.float32), atol=TOL, rtol=0)
    if kv_dtype:
        np.testing.assert_allclose(np.asarray(cache_f.k_scale),
                                   np.asarray(cache_c.k_scale),
                                   atol=TOL, rtol=0)


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_megakernel_matches_composed_paged_engine(model, kv_dtype):
    """Paged engines with the megakernel off/on generate IDENTICAL
    greedy tokens (CPU lowers both to the same XLA ops)."""
    from paddle_tpu.inference import InferenceEngine
    m = model
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 97, n).astype(np.int32)
               for n in (9, 5, 12)]

    def run(fused):
        m.enable_decode_megakernel(fused)
        try:
            eng = InferenceEngine(m, batch_slots=2, kv_layout="paged",
                                  kv_block_size=8,
                                  prefill_buckets=[16],
                                  kv_dtype=kv_dtype)
            rids = [eng.add_request(p, max_new_tokens=6)
                    for p in prompts]
            out = eng.run()
            return [out[r].tolist() for r in rids]
        finally:
            m.enable_decode_megakernel(False)

    assert run(False) == run(True)


@pytest.mark.slow  # tier-1 wall budget: heaviest in file
def test_megakernel_matches_composed_quantized_compute(model):
    """With int8 COMPUTE (cfg.quantize) the fused op routes its
    composite, whose projections run ops.quantized_matmul — logits must
    match the composed quantized path."""
    m = model
    rng = np.random.RandomState(4)
    ids = rng.randint(0, 97, (1, 9)).astype(np.int32)
    tok = jnp.asarray([ids[0, -1]], jnp.int32)
    act = jnp.ones((1,), jnp.int32)
    m.enable_quantize("int8")
    try:
        c = m.init_kv_cache(1)
        _, c = m.prefill(jnp.asarray(ids[:, :-1]), c, 0, 8)
        lc, _ = m.decode_step(tok, c, act)
        m.enable_decode_megakernel(True)
        c2 = m.init_kv_cache(1)
        _, c2 = m.prefill(jnp.asarray(ids[:, :-1]), c2, 0, 8)
        lf, _ = m.decode_step(tok, c2, act)
    finally:
        m.enable_decode_megakernel(False)
        m.enable_quantize(None)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lc), atol=TOL,
                               rtol=0)


@pytest.mark.slow  # tier-1 wall budget: heaviest in file
def test_megakernel_interpret_kernel_in_model():
    """The REAL Pallas kernel (interpret mode) inside the model decode
    step matches the composed path — kernel-compatible shapes (h=128,
    cap=128)."""
    cfg = GPTConfig(vocab_size=97, hidden_size=128, num_layers=2,
                    num_heads=2, max_seq_len=128,
                    use_flash_attention=False)
    paddle.seed(1)
    m = GPTForCausalLM(cfg)
    m.eval()
    rng = np.random.RandomState(2)
    ids = rng.randint(0, 97, (1, 9)).astype(np.int32)
    tok = jnp.asarray([ids[0, -1]], jnp.int32)
    act = jnp.ones((1,), jnp.int32)
    c = m.init_kv_cache(1)
    _, c = m.prefill(jnp.asarray(ids[:, :-1]), c, 0, 8)
    lc, _ = m.decode_step(tok, c, act)
    m.enable_decode_megakernel(True)
    mk.set_interpret_mode(True)
    try:
        c2 = m.init_kv_cache(1)
        _, c2 = m.prefill(jnp.asarray(ids[:, :-1]), c2, 0, 8)
        lk, _ = m.decode_step(tok, c2, act)
    finally:
        mk.set_interpret_mode(None)
        m.enable_decode_megakernel(False)
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lc), atol=TOL,
                               rtol=0)


# ---------------------------------------------------------------------------
# zero-recompile churn with the megakernel on
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_zero_recompile_churn_megakernel(model, layout):
    """A warmed megakernel engine admits/retires/decodes with ZERO new
    XLA compiles — the fused op is shape-stable inside the decode
    executable exactly like the composed kernels."""
    from paddle_tpu.inference import InferenceEngine
    m = model
    m.enable_decode_megakernel(True)
    try:
        kw = dict(kv_layout="paged", kv_block_size=8) \
            if layout == "paged" else {}
        eng = InferenceEngine(m, batch_slots=2, prefill_buckets=[16],
                              **kw)
        eng.warmup(buckets=[16])
        # stats report what COMPILED: on the CPU the fused op traces its
        # composite, and says why
        assert eng.stats["decode_megakernel"] is False
        assert eng.stats["decode_megakernel_refusal"] == \
            "backend is not tpu"
        assert eng.kernel_paths[("decode", 0)]["decode_megakernel"][
            "composite"] > 0
        rng = np.random.RandomState(3)
        with compile_counter.assert_no_recompiles(
                f"megakernel churn {layout}"):
            rids = [eng.add_request(rng.randint(1, 97, n)
                                    .astype(np.int32),
                                    max_new_tokens=5)
                    for n in (4, 9, 6)]
            out = eng.run()
        assert all(len(out[r]) == 5 for r in rids)
    finally:
        m.enable_decode_megakernel(False)


# ---------------------------------------------------------------------------
# decode HBM byte accounting
# ---------------------------------------------------------------------------
def test_decode_hbm_bytes_per_tok_int8_smaller(model):
    from paddle_tpu.inference import InferenceEngine
    fp = InferenceEngine(model, batch_slots=2, prefill_buckets=[16])
    q8 = InferenceEngine(model, batch_slots=2, prefill_buckets=[16],
                         kv_dtype="int8")
    b_fp = fp.stats["decode_hbm_bytes_per_tok"]
    b_q8 = q8.stats["decode_hbm_bytes_per_tok"]
    assert b_fp > 0 and b_q8 > 0
    # int8 halves the KV values but adds f32 scale planes; with d=32
    # heads the scales cost 4/32 of fp — still a clear net win
    assert b_q8 < b_fp
    cfg = model.cfg
    kv_fp = 2 * cfg.num_layers * fp.max_seq_len * cfg.num_kv_heads * \
        cfg.head_dim * 4            # f32 cache on CPU
    assert b_fp >= kv_fp            # params amortized on top


# ---------------------------------------------------------------------------
# bench sweep resume (satellite)
# ---------------------------------------------------------------------------
def _bench_module():
    import importlib
    import bench
    return importlib.reload(bench)


def test_bench_resume_matches_persisted_rows(tmp_path, monkeypatch):
    """_persist_row tags rows with the run id and _measured_rows only
    returns rows whose (run, candidate identity) matches — the rerun
    after a late transient failure re-measures only the tail."""
    rows = tmp_path / "rows.jsonl"
    monkeypatch.setenv("BENCH_ROWS_FILE", str(rows))
    monkeypatch.setenv("BENCH_RUN", "r06")
    monkeypatch.delenv("BENCH_RECOMPUTE", raising=False)
    monkeypatch.delenv("BENCH_QUANTIZE", raising=False)
    monkeypatch.delenv("BENCH_SCAN_LAYERS", raising=False)
    monkeypatch.delenv("PADDLE_TPU_OVERLAP", raising=False)
    bench = _bench_module()
    row = {"config": "gpt3-125m", "batch": 8, "seq": 2048,
           "use_flash": True, "remat": False, "remat_policy": "off",
           "scan_layers": True, "overlap": True, "quantize": "int8",
           "mfu": 0.40, "step_ms": 10.0, "pathological": False}
    bench._persist_row(row, kind="train")
    measured = bench._measured_rows("train")
    spec = dict(config="gpt3-125m", batch=8, seq=2048, flash=True,
                remat=False, quantize="int8")
    assert bench._candidate_key(spec) in measured
    assert measured[bench._candidate_key(spec)]["mfu"] == 0.40
    # a different candidate (fp) must NOT match
    other = dict(spec, quantize="off")
    assert bench._candidate_key(other) not in measured
    # rows from another run are invisible
    monkeypatch.setenv("BENCH_RUN", "r07")
    assert bench._measured_rows("train") == {}
    # no run id => resume disabled entirely
    monkeypatch.setenv("BENCH_RUN", "")
    assert bench._measured_rows("train") == {}


def test_bench_resume_serve_rows(tmp_path, monkeypatch):
    rows = tmp_path / "rows.jsonl"
    monkeypatch.setenv("BENCH_ROWS_FILE", str(rows))
    monkeypatch.setenv("BENCH_RUN", "r06")
    bench = _bench_module()
    row = {"config": "gpt3-125m", "batch_slots": 8, "kv_dtype": "dense",
           "decode_megakernel": True, "prompt_len": 128,
           "gen_tokens": 64, "value": 900.0}
    bench._persist_row(row, kind="serve")
    measured = bench._measured_rows("serve")
    # tp (ISSUE 18), ep (ISSUE 19) and prefill_chunk (ISSUE 20) joined
    # the candidate key: a row without the columns resumes as the
    # tp=1/ep=1/monolithic candidate; a tp=2, ep=2 or chunked row is a
    # DIFFERENT point
    key = ("serve", "gpt3-125m", 8, "dense", True, 128, 64, 1, 1, 0)
    assert key in measured and measured[key]["value"] == 900.0
    assert ("serve", "gpt3-125m", 8, "dense", False, 128, 64, 1, 1, 0) \
        not in measured
    assert ("serve", "gpt3-125m", 8, "dense", True, 128, 64, 2, 1, 0) \
        not in measured
    assert ("serve", "gpt3-125m", 8, "dense", True, 128, 64, 1, 2, 0) \
        not in measured
    assert ("serve", "gpt3-125m", 8, "dense", True, 128, 64, 1, 1, 64) \
        not in measured


# ---------------------------------------------------------------------------
# tuning-table nearest-shape fallbacks (satellite)
# ---------------------------------------------------------------------------
@pytest.fixture
def tuning_tmp(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TUNING_CACHE",
                       str(tmp_path / "tuning.json"))
    monkeypatch.delenv("PADDLE_TPU_TUNING", raising=False)
    _tuning.reset_for_tests()
    yield
    _tuning.reset_for_tests()


def test_qmm_tiles_nearest_shape_fallback(tuning_tmp):
    from paddle_tpu.ops.quantized_matmul import get_qmm_tiles
    kind = _tuning.device_kind()
    _tuning.record("qmm_tiles", (kind, 1024, 512, 256, "int8"),
                   [64, 128, 128])
    # exact hit
    assert get_qmm_tiles(1024, 512, 256) == (64, 128, 128)
    # near miss (m bucket 2048, same n/k): nearest entry serves,
    # clamped — NOT the (256, 256, 256) hard defaults
    assert get_qmm_tiles(2048, 512, 256) == (64, 128, 128)
    # different n/k within log-distance still beats hard defaults
    assert get_qmm_tiles(1024, 256, 256) == (64, 128, 128)


def test_flash_blocks_nearest_seq_from_unified_table(tuning_tmp,
                                                     monkeypatch):
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    monkeypatch.delenv("PADDLE_TPU_FLASH_AUTOTUNE_CACHE", raising=False)
    monkeypatch.setenv("PADDLE_TPU_FLASH_AUTOTUNE", "1")
    kind = _tuning.device_kind()
    saved = dict(fa._SWEEP_CACHE)
    fa._SWEEP_CACHE.clear()
    fa._SWEEP_STORE_STATE["loaded"] = False
    try:
        _tuning.record("flash_blocks", (kind, 1024, 64, True),
                       [256, 256])
        # seq 512 has no exact entry anywhere on CPU: the swept 1024
        # entry is the nearest and must serve (defaults are 512/512)
        assert fa.get_block_sizes(512, 64, True) == (256, 256)
    finally:
        fa._SWEEP_CACHE.clear()
        fa._SWEEP_CACHE.update(saved)
        fa._SWEEP_STORE_STATE["loaded"] = False


def test_tuned_remat_policy_consumed(tuning_tmp):
    from paddle_tpu.distributed.spmd import tuned_remat_policy

    class _Cfg:
        hidden_size, num_layers, max_seq_len = 128, 2, 64

    class _Model:
        cfg = _Cfg()

    kind = _tuning.device_kind()
    assert tuned_remat_policy(_Model()) is None
    _tuning.record("remat_policy", (kind, 128, 2, 64), "dots_no_batch")
    assert tuned_remat_policy(_Model()) == "dots_no_batch"
    # nearest shape serves a near-miss model
    _Cfg.hidden_size = 256
    assert tuned_remat_policy(_Model()) == "dots_no_batch"
    # 'off' entries mean "winner ran without remat": ignored
    _tuning.record("remat_policy", (kind, 256, 2, 64), "off")
    assert tuned_remat_policy(_Model()) is None


@pytest.mark.slow
def test_megakernel_long_churn_soak(model):
    """Longer mixed-admission soak with the fused path on (slow tier)."""
    from paddle_tpu.inference import InferenceEngine
    m = model
    m.enable_decode_megakernel(True)
    try:
        eng = InferenceEngine(m, batch_slots=3, prefill_buckets=[16])
        eng.warmup(buckets=[16])
        rng = np.random.RandomState(7)
        with compile_counter.assert_no_recompiles("megakernel soak"):
            for wave in range(4):
                rids = [eng.add_request(
                    rng.randint(1, 97, int(rng.randint(3, 14)))
                    .astype(np.int32),
                    max_new_tokens=int(rng.randint(3, 9)))
                    for _ in range(4)]
                out = eng.run()
                assert all(r in out for r in rids)
    finally:
        m.enable_decode_megakernel(False)
