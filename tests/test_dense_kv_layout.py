"""The dense KV cache's layout: one head-major buffer per layer.

``StaticKVCache.k`` / ``.v`` are tuples of ``[B, Hkv, S, D]`` buffers
(scale planes ``[B, Hkv, S]``).  These tests hold the attention entry
points and the write to that layout against arithmetic written here in
numpy, and an engine's cache, after slots have churned, against the k/v
of one plain forward.
"""
import importlib

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import InferenceEngine
from paddle_tpu.models import GPTConfig, GPTForCausalLM

da = importlib.import_module("paddle_tpu.ops.decode_attention")
qm = importlib.import_module("paddle_tpu.ops.quantized_matmul")


def plain_attention(q, k, v, limit):
    """q [B, W, H, D]; k/v [B, Hkv, S, D] float; limit [B, W]: query
    (b, w) sees positions below limit[b, w].  Softmax in float64, one
    (slot, query, head) at a time."""
    b, w, h, d = q.shape
    g = h // k.shape[1]
    out = np.zeros(q.shape, np.float64)
    for bi in range(b):
        for wi in range(w):
            n = int(limit[bi, wi])
            for hi in range(h):
                kk = k[bi, hi // g, :n].astype(np.float64)
                vv = v[bi, hi // g, :n].astype(np.float64)
                s = kk @ q[bi, wi, hi].astype(np.float64) / np.sqrt(d)
                p = np.exp(s - s.max())
                out[bi, wi, hi] = (p / p.sum()) @ vv
    return out


def _case(rng, quantized, window):
    B, S, H, Hkv, D = 3, 256, 4, 2, 64
    W = 3 if window else 1
    q = rng.randn(B, W, H, D).astype(np.float32) * 0.5
    k = rng.randn(B, Hkv, S, D).astype(np.float32) * 0.5
    v = rng.randn(B, Hkv, S, D).astype(np.float32) * 0.5
    before = np.array([0, 100, S - W], np.int32)   # cached before the window
    limit = before[:, None] + np.arange(W)[None, :] + 1
    scales = ()
    if quantized:
        kq, ks = qm.quantize_kv(jnp.asarray(k))
        vq, vs = qm.quantize_kv(jnp.asarray(v))
        # the reference attends what the cache holds: the dequantized codes
        k = np.asarray(qm.dequantize_kv(kq, ks))
        v = np.asarray(qm.dequantize_kv(vq, vs))
        cache, scales = (kq, vq), (ks, vs)
    else:
        cache = (jnp.asarray(k), jnp.asarray(v))
    return q, k, v, before, limit, cache, scales


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("path", ["composite", "kernel"])
@pytest.mark.parametrize("op", ["decode", "window"])
def test_head_major_attention_matches_plain_softmax(op, path, quantized):
    """decode and window attention, XLA composite and interpreted Pallas
    kernel, fp and int8: all read the ``[B, Hkv, S, D]`` layer as it is
    and agree with a softmax written out by hand (GQA, per-slot lengths
    at 0, mid-buffer and the capacity's edge)."""
    q, k, v, before, limit, cache, scales = _case(
        np.random.RandomState(0), quantized, op == "window")
    want = plain_attention(q, k, v, limit)
    lens = jnp.asarray(before)
    da.set_interpret_mode(path == "kernel")
    try:
        if op == "decode":
            got = da.decode_attention(jnp.asarray(q[:, 0]), *cache,
                                      lens + 1, *scales)[:, None]
        else:
            got = da.decode_attention_window(jnp.asarray(q), *cache, lens,
                                             *scales)
        counted = da.kernel_paths.counts()
    finally:
        da.set_interpret_mode(None)
    name = "decode_attention" if op == "decode" else \
        "decode_attention_window"
    assert counted[name][path] >= 1
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [False, True], ids=["token", "window"])
def test_write_kv_stores_each_slot_at_its_own_position(window):
    """write_kv puts every slot's new rows at that slot's position(s),
    in value buffers [B, Hkv, S, D] and scale planes [B, Hkv, S], and
    touches nothing else."""
    rng = np.random.RandomState(1)
    B, Hkv, S, D, W = 3, 2, 16, 8, 4
    buf = rng.randn(B, Hkv, S, D).astype(np.float32)
    plane = rng.rand(B, Hkv, S).astype(np.float32)
    if window:
        idx = np.array([[0, 1, 2, 3], [5, 6, 7, 8], [12, 13, 14, 15]],
                       np.int32)
        new = rng.randn(B, W, Hkv, D).astype(np.float32)
        new_s = rng.rand(B, W, Hkv).astype(np.float32)
    else:
        idx = np.array([0, 7, 15], np.int32)
        new = rng.randn(B, Hkv, D).astype(np.float32)
        new_s = rng.rand(B, Hkv).astype(np.float32)
    want, want_s = buf.copy(), plane.copy()
    for b in range(B):
        for w, pos in enumerate(np.atleast_1d(idx[b])):
            want[b, :, pos] = new[b, w] if window else new[b]
            want_s[b, :, pos] = new_s[b, w] if window else new_s[b]
    got = da.write_kv(jnp.asarray(buf), jnp.asarray(idx), jnp.asarray(new))
    got_s = da.write_kv(jnp.asarray(plane), jnp.asarray(idx),
                        jnp.asarray(new_s))
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(got_s), want_s)


def _fused_writes():
    return da.kernel_paths.counts().get(
        "decode_attention.fused_write", {}).get("kernel", 0)


def _view_case(rng, hkv, g, d, w, kv_dtype=None):
    """(a DenseKVLayer of 5 slots x 256 positions, q, k, v of a window of
    `w` tokens, the lengths before it): a slot at 0, round a key block's
    edge, mid-buffer, and one at capacity, whose token is clamped to the
    last position."""
    from paddle_tpu.models.gpt import DenseKVLayer
    cap = 256
    lengths = np.array([0, 127, 128, 40, cap], np.int32)
    n = len(lengths)
    k_buf = jnp.asarray(rng.randn(n, hkv, cap, d) * 0.5, jnp.bfloat16)
    v_buf = jnp.asarray(rng.randn(n, hkv, cap, d) * 0.5, jnp.bfloat16)
    scales = ()
    if kv_dtype:
        k_buf, ks = qm.quantize_kv(k_buf.astype(jnp.float32))
        v_buf, vs = qm.quantize_kv(v_buf.astype(jnp.float32))
        scales = (ks, vs)
    q = jnp.asarray(rng.randn(n, w, hkv * g, d) * 0.5, jnp.bfloat16)
    k = jnp.asarray(rng.randn(n, w, hkv, d) * 0.5, jnp.bfloat16)
    v = jnp.asarray(rng.randn(n, w, hkv, d) * 0.5, jnp.bfloat16)
    return DenseKVLayer(k_buf, v_buf, *scales), q, k, v, \
        jnp.asarray(lengths)


def _assert_same_view(got, want):
    (out, kv), (out_w, kv_w) = got, want
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(out_w, np.float32))
    for name in ("k", "v", "k_scale", "v_scale"):
        a, b = getattr(kv, name), getattr(kv_w, name)
        assert (a is None) == (b is None)
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


@pytest.mark.parametrize("hkv,g,d", [(4, 1, 128), (2, 2, 128), (4, 1, 64),
                                     (2, 4, 64)],
                         ids=["mha_128", "gqa2_128", "mha_64", "gqa4_64"])
def test_dense_view_write_attend_is_its_two_halves(hkv, g, d):
    """``DenseKVLayer.write_attend`` of one token a slot where the
    kernel runs: ONE call that also writes (``kernel_paths`` notes it),
    and the output and both buffers are what ``write`` then ``attend``
    give, bit for bit, a slot at length 0 and a slot at capacity among
    them."""
    from paddle_tpu.models.gpt import KVLayerView
    layer, q, k, v, lengths = _view_case(np.random.RandomState(7), hkv, g,
                                         d, 1)
    da.set_interpret_mode(True)
    try:
        before = _fused_writes()
        got = layer.write_attend(q, k, v, lengths)
        fused = _fused_writes() - before
        want = KVLayerView.write_attend(layer, q, k, v, lengths)
        unfused = _fused_writes() - before - fused
    finally:
        da.set_interpret_mode(None)
    assert (fused, unfused) == (1, 0)
    assert got[0].shape == q.shape
    _assert_same_view(got, want)
    at = np.minimum(np.asarray(lengths), layer.k.shape[2] - 1)
    for buf, new in ((got[1].k, k), (got[1].v, v)):
        np.testing.assert_array_equal(
            np.asarray(buf, np.float32)[np.arange(len(at)), :, at],
            np.asarray(new[:, 0], np.float32))


@pytest.mark.parametrize("case", ["window", "int8", "off_the_chip"])
def test_dense_view_keeps_write_then_attend_for_everything_else(case):
    """A window of several tokens, a cache of 8-bit codes beside scale
    planes, and a process without the kernel take the view's default,
    ``write`` then ``attend``: the fused call is noted by none of them
    and the results are the default's own."""
    from paddle_tpu.models.gpt import KVLayerView
    layer, q, k, v, lengths = _view_case(
        np.random.RandomState(8), 2, 2, 64, 3 if case == "window" else 1,
        "int8" if case == "int8" else None)
    if case == "window":
        lengths = jnp.minimum(lengths, layer.k.shape[2] - 3)
    da.set_interpret_mode(None if case == "off_the_chip" else True)
    try:
        before = _fused_writes()
        got = layer.write_attend(q, k, v, lengths)
        want = KVLayerView.write_attend(layer, q, k, v, lengths)
        assert _fused_writes() == before
    finally:
        da.set_interpret_mode(None)
    _assert_same_view(got, want)


def test_cache_is_one_head_major_buffer_per_layer():
    cfg = GPTConfig(vocab_size=97, hidden_size=64, num_layers=3,
                    num_heads=4, num_kv_heads=2, max_seq_len=32,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    for kv_dtype in (None, "int8"):
        c = m.init_kv_cache(5, kv_dtype=kv_dtype)
        assert (c.num_layers, c.batch_slots, c.kv_heads, c.capacity) == \
            (3, 5, 2, 32)
        assert all(a.shape == (5, 2, 32, 16) for a in c.k + c.v)
        # one array a buffer: two that shared memory could not both be
        # donated
        assert len({id(a) for a in c.k + c.v}) == 6
        if kv_dtype:
            assert c.dtype == jnp.int8
            assert all(a.shape == (5, 2, 32)
                       for a in c.k_scale + c.v_scale)
        else:
            assert c.k_scale is None and c.v_scale is None
        assert c.with_lengths(c.lengths + 1).k is c.k


def _plain_forward_kv(model, ids):
    """Per-layer k/v ``[Hkv, s, D]`` of ONE no-cache forward over
    ``ids``: the blocks' own projections, layer by layer."""
    gpt = model.gpt
    s = len(ids)
    x = gpt.wte(paddle.to_tensor(np.asarray([ids], np.int32))) + \
        gpt.wpe(paddle.to_tensor(np.arange(s, dtype=np.int32)[None]))
    out = []
    for blk in gpt.blocks:
        _, k, v = blk.attn._qkv_arrays(blk.ln_1(x))
        out.append((np.asarray(k)[0].swapaxes(0, 1),
                    np.asarray(v)[0].swapaxes(0, 1)))
        x = blk(x)
    return out


@pytest.mark.parametrize("kv_heads", [None, 2], ids=["mha", "gqa"])
def test_engine_cache_equals_one_plain_forward_under_slot_churn(kv_heads):
    """Seven requests of uneven length through three slots: whenever
    looked at, each live slot's cache rows below its length are the k/v
    a single plain forward over that slot's tokens computes — prefill's
    strided write, the decode ticks' in-place writes and a slot's reuse
    by a later request all land where the layout says."""
    cfg = GPTConfig(vocab_size=97, hidden_size=64, num_layers=2,
                    num_heads=4, num_kv_heads=kv_heads, max_seq_len=64,
                    use_flash_attention=False)
    paddle.seed(3)
    model = GPTForCausalLM(cfg)
    model.eval()
    eng = InferenceEngine(model, batch_slots=3, prefill_buckets=[8, 32])
    rng = np.random.RandomState(5)
    for n, new in [(3, 4), (20, 9), (7, 2), (30, 12), (5, 6), (11, 3),
                   (2, 8)]:
        eng.add_request(rng.randint(1, 97, (n,)).astype(np.int32),
                        max_new_tokens=new)
    looked = reused = ahead = 0
    seen = [set() for _ in range(3)]
    for tick in range(200):
        if not eng.has_work:
            break
        eng.step_or_raise()
        if tick % 2:
            continue
        lengths = np.asarray(eng.cache.lengths)
        for slot, req in enumerate(eng._slots):
            if req is None:
                continue
            seen[slot].add(req.rid)
            reused += len(seen[slot]) > 1
            toks = np.concatenate(
                [req.prompt, np.asarray(req.generated, np.int32)])
            n = int(lengths[slot])
            # the newest sampled token is written by the next tick, which
            # the engine has launched already where no request could end
            assert n == len(toks) - 1 + (eng._ahead is not None)
            ahead += eng._ahead is not None
            for layer, (k, v) in enumerate(
                    _plain_forward_kv(model, toks[:n])):
                np.testing.assert_allclose(
                    np.asarray(eng.cache.k[layer])[slot, :, :n], k,
                    rtol=1e-5, atol=1e-5)
                np.testing.assert_allclose(
                    np.asarray(eng.cache.v[layer])[slot, :, :n], v,
                    rtol=1e-5, atol=1e-5)
            looked += 1
    assert not eng.has_work and len(eng.results) == 7
    assert looked >= 12 and reused >= 3 and ahead >= 2, \
        (looked, reused, ahead)
