"""The model's one serving step (``GPTModel.step`` over the cache's
per-layer view) against an oracle that knows nothing of caches: the
plain uncached forward over the same tokens.

- the step's logits and the valid part of the cache it leaves, over
  cache {dense, paged} x storage {model dtype, int8} x heads {MHA, GQA}
  x window {1, 4};
- the three ways lengths advance (decode by ``active``, verify not at
  all, chunk by ``advance``);
- W = 1 traces the single-token attention and a ``[B]``-indexed write
  per layer and no window entry point (the shape of that write decides
  on the chip whether a donated buffer is updated where it lies).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import ops
from paddle_tpu.inference.paged_kv import blocks_to_rows, init_paged_cache
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.ops.quantized_matmul import dequantize_kv

VOCAB, BLOCK, MAX_BLOCKS = 97, 8, 2      # 16 positions a paged slot
PROMPTS = (5, 7)                         # 7 + 4 crosses a block boundary


def tiny_model(kv_heads=None):
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=VOCAB, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=kv_heads, max_seq_len=64, use_flash_attention=False))
    m.eval()
    return m


@pytest.fixture(scope="module")
def models():
    return {"mha": tiny_model(), "gqa": tiny_model(kv_heads=2)}


def plain_forward(m, ids):
    """Logits ``[n, V]`` and every layer's k and v ``[n, Hkv, D]`` of one
    sequence, from the blocks' plain forward: no cache, no step."""
    gpt, cfg = m.gpt, m.cfg
    logits = np.asarray(m(paddle.to_tensor(ids[None])).data)[0]
    n = len(ids)
    pos = paddle.to_tensor(np.arange(n, dtype=np.int32)[None])
    x = gpt.wte(paddle.to_tensor(ids[None])) + gpt.wpe(pos)
    kv = []
    for blk in gpt.blocks:
        qkv = np.asarray(blk.attn.qkv_proj(blk.ln_1(x)).data)[0]
        k, v = np.split(qkv[:, cfg.hidden_size:], 2, axis=-1)
        kv.append((k.reshape(n, cfg.num_kv_heads, cfg.head_dim),
                   v.reshape(n, cfg.num_kv_heads, cfg.head_dim)))
        x = blk(x)
    return logits, kv


def prefilled(m, layout, kv_dtype, seqs):
    """A cache holding each slot's prompt, and the paged operands."""
    tables = None
    if layout == "dense":
        cache = m.init_kv_cache(batch_slots=len(seqs), kv_dtype=kv_dtype)
        for slot, (ids, plen) in enumerate(zip(seqs, PROMPTS)):
            _, cache = m.prefill(jnp.asarray(ids[None, :plen]), cache,
                                 slot, plen)
    else:
        cache = init_paged_cache(m, 1 + len(seqs) * MAX_BLOCKS, BLOCK,
                                 kv_dtype=kv_dtype)
        tables = 1 + np.arange(len(seqs) * MAX_BLOCKS, dtype=np.int32)
        tables = tables.reshape(len(seqs), MAX_BLOCKS)[::-1].copy()
        for row, ids, plen in zip(tables, seqs, PROMPTS):
            padded = np.zeros((1, BLOCK * MAX_BLOCKS), np.int32)
            padded[0, :plen] = ids[:plen]
            _, cache = m.prefill_paged(jnp.asarray(padded), cache,
                                       jnp.asarray(row), 0, np.int32(plen))
    return cache, tables


def stored_rows(cache, layer, slot, tables):
    """Slot ``slot``'s k and v of one layer as the cache holds them,
    position-major ``[positions, Hkv, D]`` in float32."""
    out = []
    for plane, scale in ((cache.k, cache.k_scale), (cache.v, cache.v_scale)):
        if tables is None:
            vals = jnp.swapaxes(plane[layer][slot], 0, 1)
            sc = scale and jnp.swapaxes(scale[layer][slot], 0, 1)
        else:
            vals = blocks_to_rows(plane[layer][tables[slot]])
            sc = None if scale is None else \
                blocks_to_rows(scale[layer][tables[slot]])
        out.append(np.asarray(vals, np.float32) if sc is None
                   else np.asarray(dequantize_kv(vals, sc)))
    return out


@pytest.mark.parametrize("w", [1, 4])
@pytest.mark.parametrize("heads", ["mha", "gqa"])
@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["fp", "int8"])
@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_step_matches_the_plain_forward(models, layout, kv_dtype, heads, w):
    m = models[heads]
    rng = np.random.RandomState(3)
    seqs = [rng.randint(0, VOCAB, plen + w).astype(np.int32)
            for plen in PROMPTS]
    want = [plain_forward(m, ids) for ids in seqs]
    cache, tables = prefilled(m, layout, kv_dtype, seqs)
    lengths = jnp.asarray(PROMPTS, jnp.int32)
    window = np.stack([ids[plen:] for ids, plen in zip(seqs, PROMPTS)])

    if w == 1 and layout == "dense":
        logits, cache = m.decode_step(jnp.asarray(window[:, 0]), cache,
                                      jnp.ones(len(seqs), jnp.int32))
    elif w == 1:
        logits, cache = m.decode_step_paged(
            jnp.asarray(window[:, 0]), cache, jnp.asarray(tables), lengths)
    elif layout == "dense":
        logits, cache = m.verify_step(jnp.asarray(window), cache)
    else:
        logits, cache = m.verify_step_paged(
            jnp.asarray(window), cache, jnp.asarray(tables), lengths)
    logits = np.asarray(logits).reshape(len(seqs), w, VOCAB)

    for slot, (plen, (ref_logits, ref_kv)) in enumerate(zip(PROMPTS, want)):
        n = plen + w
        scale = float(np.abs(ref_logits).max())
        if kv_dtype is None:
            np.testing.assert_allclose(logits[slot], ref_logits[plen:],
                                       rtol=1e-4, atol=1e-4)
        else:       # the tolerance of tests/test_quantized.py
            assert np.abs(logits[slot] - ref_logits[plen:]).max() \
                < 0.05 * scale
        for layer, ref in enumerate(ref_kv):
            for got, ref_plane in zip(
                    stored_rows(cache, layer, slot, tables), ref):
                if kv_dtype is None:
                    np.testing.assert_allclose(got[:n], ref_plane,
                                               rtol=1e-5, atol=1e-5)
                else:
                    # a code is off by half of 1/127 of its row's largest
                    # value; deeper layers also read the codes beneath
                    assert np.abs(got[:n] - ref_plane).max() \
                        < 0.02 * np.abs(ref_plane).max()


@pytest.mark.parametrize("entry", ["decode_step", "verify_step",
                                   "prefill_chunk"])
def test_how_lengths_advance(models, entry):
    """decode advances by ``active``; verify leaves lengths to the spec
    tick; a chunk sets ``lengths + advance`` from the scheduler's own
    mirror and leaves a row with ``advance == 0`` as it was."""
    m = models["mha"]
    rng = np.random.RandomState(5)
    seqs = [rng.randint(0, VOCAB, plen + 4).astype(np.int32)
            for plen in PROMPTS]
    cache, _ = prefilled(m, "dense", None, seqs)
    before = stored_rows(cache, 0, 1, None)
    window = jnp.asarray(np.stack([ids[plen:] for ids, plen
                                   in zip(seqs, PROMPTS)]))
    if entry == "decode_step":
        _, after = m.decode_step(window[:, 0], cache,
                                 jnp.asarray([1, 0], jnp.int32))
        want = [PROMPTS[0] + 1, PROMPTS[1]]
    elif entry == "verify_step":
        _, after = m.verify_step(window, cache)
        want = list(PROMPTS)
    else:
        # the cache's own lengths are stale on purpose: the operand wins
        stale = cache.with_lengths(jnp.asarray([63, 63], jnp.int32))
        _, after = m.prefill_chunk(window, stale,
                                   jnp.asarray(PROMPTS, jnp.int32),
                                   jnp.asarray([3, 0], jnp.int32))
        want = [PROMPTS[0] + 3, PROMPTS[1]]
    assert np.asarray(after.lengths).tolist() == want
    # the row that did not advance keeps every valid position
    for got, was in zip(stored_rows(after, 0, 1, None), before):
        np.testing.assert_array_equal(got[:PROMPTS[1]], was[:PROMPTS[1]])


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_single_token_step_traces_the_single_token_program(
        models, monkeypatch, layout):
    """Lowering the W = 1 step: per layer one single-token attention
    call and ``[B]``-indexed writes, and no window entry point."""
    m = models["mha"]
    calls = {"token": 0, "window": 0, "write_ndims": []}

    def counted(name, key):
        real = getattr(ops, name)

        def fn(*args, **kw):
            calls[key] += 1
            return real(*args, **kw)
        monkeypatch.setattr(ops, name, fn)

    counted("decode_attention", "token")
    counted("paged_decode_attention", "token")
    counted("decode_attention_window", "window")
    counted("paged_decode_attention_window", "window")
    real_write = ops.write_kv

    def write_kv(buf, idx, new):
        calls["write_ndims"].append(idx.ndim)
        return real_write(buf, idx, new)
    monkeypatch.setattr(ops, "write_kv", write_kv)

    slots = 3
    tokens = jnp.zeros(slots, jnp.int32)
    if layout == "dense":
        cache = m.init_kv_cache(batch_slots=slots)
        jax.jit(m.decode_step).lower(
            tokens, cache, jnp.ones(slots, jnp.int32))
    else:
        cache = init_paged_cache(m, 1 + slots * MAX_BLOCKS, BLOCK)
        jax.jit(m.decode_step_paged).lower(
            tokens, cache, jnp.zeros((slots, MAX_BLOCKS), jnp.int32),
            jnp.zeros(slots, jnp.int32))
    layers = m.cfg.num_layers
    assert calls["token"] == layers and calls["window"] == 0
    # a write each for k and v; the paged pool scatters through its table
    assert calls["write_ndims"] == ([1] * 2 * layers
                                    if layout == "dense" else [])
