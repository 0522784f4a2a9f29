"""The dense decode kernel reads a slot up to its length.

``ops/decode_attention.py``'s single-token kernel takes ``lengths`` as a
scalar-prefetch operand: the key blocks past the one that holds position
``lengths[b] - 1`` are never fetched, the positions past the length
inside that block meet an exact zero, and one executable serves every
``lengths``.  These cases hold the interpreted kernel to the XLA
composite over a cache whose tail is poisoned, and the engine's ``tick``
span to what the kernel streams.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.inference import InferenceEngine
from paddle_tpu.models import GPTConfig, GPTForCausalLM

da = importlib.import_module("paddle_tpu.ops.decode_attention")
qm = importlib.import_module("paddle_tpu.ops.quantized_matmul")


@pytest.fixture
def kernel():
    """The Pallas kernels interpreted, for one test."""
    da.set_interpret_mode(True)
    yield
    da.set_interpret_mode(None)


@pytest.fixture
def block_128(monkeypatch):
    """Key blocks of 128 positions, so a small cache has several."""
    monkeypatch.setattr(da, "_DECODE_BLOCK_K", 128)
    return 128


def _edge_lengths(block_k, capacity):
    return np.array([1, block_k - 1, block_k, block_k + 1, capacity - 1,
                     capacity], np.int32)


def _tail(a, lengths, block_k, near, far):
    """`a` [B, Hkv, S, ...] with every slot's positions past its length
    set to `near`, and to `far` from the end of the block the length
    crosses."""
    a = np.array(a)
    for b, n in enumerate(lengths):
        a[b, :, n:] = near
        a[b, :, -(-n // block_k) * block_k:] = far
    return a


def _poisoned(rng, lengths, block_k, shape, dtype, poison, quantized=False):
    """((k or v as the kernel meets it, its scale plane or None), the
    same as the reference reads it): past every slot's length 1e30,
    which a probability of exact zero cancels, and from the end of the
    block the length crosses `poison` (NaN cancels nothing: a block that
    holds it must never be read).  An int8 cache carries the poison in
    its f32 scale plane, under codes of 127."""
    clean = _tail(rng.randn(*shape).astype(np.float32) * 0.5, lengths,
                  block_k, 0.0, 0.0)
    if not quantized:
        return (jnp.asarray(_tail(clean, lengths, block_k, 1e30, poison),
                            dtype), None), jnp.asarray(clean, dtype)
    codes, scales = qm.quantize_kv(jnp.asarray(clean))
    dirty = (jnp.asarray(_tail(codes, lengths, block_k, 127, 127)),
             jnp.asarray(_tail(scales, lengths, block_k, 1e30, poison)))
    return dirty, qm.dequantize_kv(codes, scales).astype(dtype)


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("poison", [np.nan, 1e30], ids=["nan", "1e30"])
@pytest.mark.parametrize("d", [128, 64])
@pytest.mark.parametrize("g", [1, 4])
def test_no_position_past_a_slots_length_reaches_the_output(
        kernel, block_128, g, d, poison, quantized):
    """Per-slot lengths at 1, round a block's edge and at the capacity's
    edge mixed in one call, several kv heads a program, bf16 queries as
    the cell has them over a bf16 or an int8 cache: the kernel agrees
    with the composite over a clean cache although every position past
    a length is poisoned."""
    cap, hkv = 512, 4
    lengths = _edge_lengths(block_128, cap)
    rng = np.random.RandomState(0)
    shape = (len(lengths), hkv, cap, d)
    q = jnp.asarray(rng.randn(len(lengths), hkv * g, d) * 0.5, jnp.bfloat16)
    (k, k_scale), k_clean = _poisoned(rng, lengths, block_128, shape,
                                      jnp.bfloat16, poison, quantized)
    (v, v_scale), v_clean = _poisoned(rng, lengths, block_128, shape,
                                      jnp.bfloat16, poison, quantized)
    assert da._decode_tiling(hkv, cap, d, k.dtype.itemsize) == \
        (hkv, block_128)
    got = np.asarray(da.decode_attention(q, k, v, jnp.asarray(lengths),
                                         k_scale, v_scale), np.float32)
    want = np.asarray(da._decode_composite(q, k_clean, v_clean,
                                           jnp.asarray(lengths)), np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0, atol=4e-3)


@pytest.mark.parametrize("heads", [1, 2, 16])
def test_the_cells_block_at_the_cells_capacity(kernel, monkeypatch, heads):
    """The block the 1.3B cell runs (512 of 2048, 16 heads a program at
    its shape) in float32 against the composite, however many heads a
    program takes; a slot at length 0 reads finite zeros."""
    cap, hkv, d = 2048, 16, 128
    lengths = np.append(_edge_lengths(512, cap), 0).astype(np.int32)
    assert da._decode_tiling(hkv, cap, d, 2) == (16, 512)
    monkeypatch.setattr(da, "_decode_tiling", lambda *shape: (heads, 512))
    rng = np.random.RandomState(1)
    shape = (len(lengths), hkv, cap, d)
    q = jnp.asarray(rng.randn(len(lengths), hkv, d) * 0.5, jnp.float32)
    (k, _), k_clean = _poisoned(rng, lengths, 512, shape, jnp.float32,
                                np.nan)
    (v, _), v_clean = _poisoned(rng, lengths, 512, shape, jnp.float32,
                                np.nan)
    got = np.asarray(da.decode_attention(q, k, v, jnp.asarray(lengths)))
    want = np.asarray(da._decode_composite(q, k_clean, v_clean,
                                           jnp.asarray(lengths)))
    np.testing.assert_allclose(got[:-1], want[:-1], rtol=2e-5, atol=2e-5)
    assert not got[-1].any()


def _write_then_attend(q, k_new, v_new, k, v, idx, attend):
    """The two calls the fused one stands for: ``write_kv`` of both
    buffers, then `attend` over what they leave."""
    k, v = da.write_kv(k, idx, k_new), da.write_kv(v, idx, v_new)
    return attend(q, k, v, idx + 1), k, v


def _bits(a):
    return np.asarray(a).view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


# positions of the new token, a slot each, at a key block of `blk` and a
# capacity of `cap`: a slot's first token, the last row of a block and
# the first of the next, the last position (where a slot at capacity is
# clamped to), the last row of a 16-row tile and the first of the next,
# and a slot nothing is served from, at the stale length it was left at
_WRITE_AT = {"first_token": lambda blk, cap: 0,
             "block_last_row": lambda blk, cap: blk - 1,
             "next_block_first_row": lambda blk, cap: blk,
             "capacity": lambda blk, cap: cap - 1,
             "tile_last_row": lambda blk, cap: blk + 15,
             "inactive": lambda blk, cap: 2 * blk + 37}


def _fused_case(rng, n, hkv, g, cap, d, dtype):
    """(q, k_new, v_new, k, v) of `n` slots."""
    draw = lambda *shape: jnp.asarray(rng.randn(*shape) * 0.5, dtype)
    return (draw(n, hkv * g, d), draw(n, hkv, d), draw(n, hkv, d),
            draw(n, hkv, cap, d), draw(n, hkv, cap, d))


def _assert_fused_is_its_two_calls(q, k_new, v_new, k, v, idx, atol):
    """``write_decode_attention`` against ``write_kv`` + the unfused
    kernel (output and BOTH buffers, whole, the same bits) and against
    ``write_kv`` + the composite (the tolerance the kernel has).
    Returns what the fused call gave."""
    idx = jnp.asarray(idx)
    assert da.decode_attention_writes(q, k)
    got = da.write_decode_attention(q, k_new, v_new, k, v, idx)
    want = _write_then_attend(q, k_new, v_new, k, v, idx,
                              da.decode_attention)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(_bits(a), _bits(b))
    ref = _write_then_attend(q, k_new, v_new, k, v, idx,
                             da._decode_composite)[0]
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               np.asarray(ref, np.float32), rtol=0,
                               atol=atol)
    return got


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("heads", ["one_head_a_step", "all_heads_a_step"])
@pytest.mark.parametrize("g,d", [(1, 128), (4, 128), (1, 64), (2, 64)],
                         ids=["mha_128", "gqa4_128", "mha_64", "gqa2_64"])
def test_fused_write_is_write_kv_then_the_kernel_bit_for_bit(
        kernel, block_128, monkeypatch, g, d, heads, dtype):
    """One slot at each position of ``_WRITE_AT`` in one call; of either
    buffer no row but ``(b, :, idx[b], :)`` differs from what went in."""
    cap, hkv = 512, 4
    idx = np.array([at(block_128, cap) for at in _WRITE_AT.values()],
                   np.int32)
    if heads == "one_head_a_step":
        monkeypatch.setattr(da, "_decode_tiling",
                            lambda *shape: (1, block_128))
    n = len(idx)
    q, k_new, v_new, k, v = _fused_case(np.random.RandomState(3), n, hkv,
                                        g, cap, d, dtype)
    got = _assert_fused_is_its_two_calls(
        q, k_new, v_new, k, v, idx, 4e-3 if dtype == jnp.bfloat16 else 2e-5)
    written = np.zeros((n, cap), bool)
    written[np.arange(n), idx] = True
    for buf, before, new in ((got[1], k, k_new), (got[2], v, v_new)):
        changed = (_bits(buf) != _bits(before)).any(axis=(1, 3))  # [B, S]
        assert not (changed & ~written).any()
        np.testing.assert_array_equal(
            _bits(buf)[np.arange(n), :, idx], _bits(new))


@pytest.mark.parametrize("at", list(_WRITE_AT))
def test_fused_write_at_the_cells_block_and_capacity(kernel, at):
    """The block the 1.3B cell runs (512 of 2048, 16 kv heads of 128 a
    step, bf16): every slot of the call at one position of
    ``_WRITE_AT`` (511 / 512 among them) but the last, which stays an
    active slot mid-buffer."""
    cap, hkv, d, n = 2048, 16, 128, 3
    assert da._decode_tiling(hkv, cap, d, 2) == (16, 512)
    idx = np.full(n, _WRITE_AT[at](512, cap), np.int32)
    idx[-1] = 700
    _assert_fused_is_its_two_calls(
        *_fused_case(np.random.RandomState(5), n, hkv, 1, cap, d,
                     jnp.bfloat16), idx, 4e-3)


def test_one_trace_serves_every_lengths(kernel, block_128):
    """``lengths`` is an operand: no host value enters the call's shape,
    so a second array of lengths neither traces nor compiles again."""
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(3, 4, 64), jnp.float32)
    k = jnp.asarray(rng.randn(3, 2, 256, 64), jnp.float32)
    v = jnp.asarray(rng.randn(3, 2, 256, 64), jnp.float32)
    traces = []

    @jax.jit
    def attend(q, k, v, lengths):
        traces.append(1)
        return da.decode_attention(q, k, v, lengths)

    for lengths in ([1, 128, 256], [200, 3, 129]):
        lengths = jnp.asarray(lengths, jnp.int32)
        np.testing.assert_allclose(
            np.asarray(attend(q, k, v, lengths)),
            np.asarray(da._decode_composite(q, k, v, lengths)),
            rtol=2e-5, atol=2e-5)
    assert len(traces) == 1 and attend._cache_size() == 1


@pytest.mark.parametrize("capacity,lengths,want", [
    (2048, [0, 1, 511, 512, 513, 2047, 2048, 4000],
     [512, 512, 512, 512, 1024, 2048, 2048, 2048]),
    (384, [1, 128, 129, 384], [128, 128, 256, 384]),
], ids=["cell", "odd_capacity"])
def test_positions_streamed_rounds_up_to_the_kernels_block(
        capacity, lengths, want):
    """What the engine's counter reads: the op's own rounding, from
    ``lengths``; an empty slot still costs the block its index map
    names, and no slot more than the capacity."""
    got = da.positions_streamed(np.asarray(lengths), capacity)
    assert got.tolist() == want
    block_k = da._decode_tiling(1, capacity, 128, 2)[1]
    assert (got % block_k == 0).all()


def _served(model, requests, **engine_kw):
    """(engine, tokens of each request, the launched ticks' arguments):
    the requests one after another through ONE slot."""
    eng = InferenceEngine(model, batch_slots=1, prefill_buckets=[16, 256],
                          **engine_kw)
    eng.warmup()
    tr = obs.tracer()
    tr.clear()
    tr.start()
    try:
        rids = [eng.add_request(p, max_new_tokens=n) for p, n in requests]
        eng.run()
        ticks = [e["args"] for e in tr.chrome_trace()["traceEvents"]
                 if e["name"] == "tick" and "kv_positions" in e["args"]]
    finally:
        tr.stop()
        tr.clear()
    return eng, [np.asarray(eng.results[r]) for r in rids], ticks


@pytest.fixture(scope="module")
def churn():
    """A long request and then a short one in the slot the long one
    left, served through the interpreted kernel and through the
    composite."""
    paddle.seed(11)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=211, hidden_size=128, num_layers=2, num_heads=2,
        max_seq_len=384, use_flash_attention=False))
    model.eval()
    rng = np.random.RandomState(4)
    requests = [(rng.randint(1, 211, (150,)).astype(np.int32), 12),
                (rng.randint(1, 211, (5,)).astype(np.int32), 8)]
    block = da._DECODE_BLOCK_K
    da._DECODE_BLOCK_K = 128
    da.set_interpret_mode(True)
    try:
        bounded = _served(model, requests)
    finally:
        da.set_interpret_mode(None)
        da._DECODE_BLOCK_K = block
    return requests, bounded, _served(model, requests)


def test_a_slot_reused_by_a_shorter_request_never_reads_the_stale_tail(
        churn):
    """The long request leaves 161 rows in the slot; the short one that
    follows it holds 13 at most, so block 1 (rows 128 up) is never
    fetched and rows 13-127 are masked: its tokens are the composite's."""
    requests, (eng, tokens, _), (_, composite_tokens, _) = churn
    assert [len(t) for t in tokens] == [n for _, n in requests]
    for got, want in zip(tokens, composite_tokens):
        np.testing.assert_array_equal(got, want)
    assert eng.stats["decode_steps"] == sum(n - 1 for _, n in requests)


def test_tick_span_counts_what_the_kernel_streams(churn):
    """``kv_positions_read`` beside ``kv_positions``: the lengths rounded
    up to the kernel's block where the decode executable traced the
    bounded body, slots x capacity where it reads all; ``kernel_paths``
    holds the note and the benchmark's fallback count still reads 0."""
    from benchmark.readers import host
    requests, (eng, _, ticks), (composite, _, composite_ticks) = churn
    (long_prompt, long_new), (short_prompt, short_new) = requests
    need = [len(long_prompt) + i for i in range(1, long_new)] + \
        [len(short_prompt) + i for i in range(1, short_new)]
    assert [t["kv_positions"] for t in ticks] == need
    assert [t["kv_positions_read"] for t in ticks] == \
        [256] * (long_new - 1) + [128] * (short_new - 1)
    decode = eng.kernel_paths[("decode", 0)]
    # noted once a trace of each of the model's two layers
    assert decode["decode_attention.bounded"] == {"kernel": 2,
                                                  "composite": 0}
    assert decode["decode_attention"] == {"kernel": 2, "composite": 0}
    # and the kernel wrote the tick's token itself
    assert decode["decode_attention.fused_write"] == {"kernel": 2,
                                                      "composite": 0}
    fallbacks = lambda e: host.kernel_fallbacks(
        {"kind": "serve", "kernel_paths": e.kernel_paths},
        {"ops": ["flash_attention", "decode_attention"]})
    assert fallbacks(eng) == 0.0
    # the composite reads every slot whole, whatever it holds
    assert [t["kv_positions"] for t in composite_ticks] == need
    assert {t["kv_positions_read"] for t in composite_ticks} == {384}
    assert not {"decode_attention.bounded", "decode_attention.fused_write"} \
        & set(composite.kernel_paths[("decode", 0)])
    assert fallbacks(composite) == 2.0
