"""Pallas TPU fused single-token decode attention over a static KV cache.

The serving hot loop (inference.engine) appends ONE token per slot per
step and attends it against a preallocated, fixed-capacity cache
layer ``[batch_slots, kv_heads, max_seq, head_dim]`` whose per-slot
occupancy is a ``lengths`` vector.  The layer is HEAD-MAJOR, like the
paged pool: each (slot, kv head)'s ``[max_seq, head_dim]`` strip is the
layer's two minor dimensions, so the kernels take blocks of the buffer
as it lies in HBM (no transpose, no copy) and the composites' einsums
read it as it is.  Decode attention is memory-bound — the whole cost is
streaming what the slots HOLD of the KV cache through the chip once —
so the fusion target is different from training flash attention: there
is no softmax tiling problem (one query row), the win is reading each
K/V block from HBM exactly once and never materializing the [B, H, S]
score matrix or a repeat_interleaved K/V for GQA.

Kernel shape (the single-token kernel, ``_decode_gqa``): ``lengths``
rides in as a scalar-prefetch operand and the grid is ``(slot, kv-head
group, key-block step)``.  A step holds the query groups ``[hb, G, D]``
of ``hb`` kv heads of one slot and ONE ``[hb, block_k, D]`` block of
their k and of their v; the index map names the slot's blocks
``0 .. (lengths[b]-1) // block_k`` on the LAST steps of the axis and
clamps before them, so no block past a slot's length ever leaves HBM
(an unchanged block index is not fetched again) and the body skips the
steps that have none.  Validity inside the block the length crosses is
``iota < lengths[b]``; the running max, denominator and accumulator
live in VMEM scratch across the steps.  No host value enters a shape:
one executable serves every ``lengths``.
Sizing (PERF.md section 6, PR 42): a step costs about 0.35 us whatever
it moves and a (slot, head) block of 512 keys is 0.3 us of copying, so
one head a step buys nothing; a step takes as many of a slot's kv heads
as keep its four blocks in flight inside 8 MiB (all 16 at 16 heads of
128: a 4 MiB step, 24 x 4 steps a layer).  A slot's blocks run last so
that the pipeline, which fetches one step ahead, copies the NEXT slot's
first block under this slot's last products.
An int8 cache goes through the same body (``quantized``).
The decode tick's call is ``write_decode_attention`` (PERF.md section 6,
PR 46): the same kernel takes the token's k and v as two more operands
and returns the two buffers, aliased to their operands.  A slot's last
step holds the block with position ``lengths[b] - 1`` in VMEM; the body
puts the new row there (a tile of 16 rows cut out, selected into and
stored back) and sends the same tile out through a block aliased to the
cache, so the tick holds no scatter and the sums run over the bytes, and
in the order, a ``write_kv`` before the call would have given them.
8-bit codes keep ``write_kv``: their scale planes lie position-minor,
another tile.  The window
kernels still run a grid ``(B·Hkv,)`` over whole ``[S, D]`` strips with
an f32 ``[B, W, S]`` mask strip, and read every slot to its capacity
(ROADMAP D12).

The XLA composite (`_decode_composite`) is the CPU/fallback path and the
ground truth for the kernel tests; both use f32 score accumulation.

Quantized KV (``kv_dtype='int8'`` in the caches): both entry points
accept optional per-(position, head) ``k_scale``/``v_scale`` arrays
(``[B, Hkv, S]`` dense / ``[num_blocks, Hkv, block_size]`` paged, f32;
see ops.quantized_matmul.quantize_kv).  The kernels stream the int8
values + f32 scales and dequantize INSIDE the block loop, so the bytes
leaving HBM per decode step halve (decode attention is bandwidth-bound
— that is the whole win); the composites dequantize up front and reuse
the dense math, which makes them the parity oracle against the fp
cache at quantization tolerance.

The window entry points (``decode_attention_window`` /
``paged_decode_attention_window``) are general over the window width W
and serve TWO schedulers: speculative-decode verify (W = draft K + 1)
and CHUNKED PREFILL (W = the chunk size) — the Sarathi-style admission
mode where each tick advances every still-prefilling slot by up to
`chunk` prompt tokens alongside the decode batch.  Both uses scatter
the window's k/v first and rely on the same staircase mask (query i
sees cache position j iff ``j <= lengths[b]+i``), so chunked prefill
needs no new kernels; the ``chunk_prefill_attention`` aliases at the
bottom of this module name that second contract explicitly and the
chunk tests pin it against the composites.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

import importlib

from . import kernel_paths

# the package __init__ rebinds the name `flash_attention` to the public
# FUNCTION; fetch the sibling module itself (its _INTERPRET flag is
# mutable state we must read live)
_fa = importlib.import_module(__package__ + ".flash_attention")

__all__ = ["decode_attention", "decode_attention_available", "write_kv",
           "write_decode_attention", "decode_attention_writes",
           "paged_decode_attention", "paged_decode_attention_available",
           "decode_attention_window", "paged_decode_attention_window",
           "chunk_prefill_attention", "paged_chunk_prefill_attention",
           "set_interpret_mode"]

_NEG = -1e30
_STATE = {"interpret": None}  # None = follow flash_attention's flag


def set_interpret_mode(flag):
    """True/False force interpret mode; None follows
    flash_attention.set_interpret_mode (so one test switch drives both
    kernels)."""
    _fa.check_interpret_allowed(flag)
    _STATE["interpret"] = flag


def _interpret() -> bool:
    if _STATE["interpret"] is not None:
        return bool(_STATE["interpret"])
    return _fa._INTERPRET


def decode_attention_available() -> bool:
    return _interpret() or jax.default_backend() == "tpu"


def _tp_mesh(hkv: int, h: int):
    """The active serving/compile mesh when its 'tp' axis can partition
    these heads, else (None, 1).  The Pallas calls below are custom
    calls GSPMD cannot partition — under a tp-sharded serving engine
    (ISSUE 18) the entry points wrap them in shard_map over 'tp' with
    per-shard head ranges instead, so each device streams only its own
    KV-head slice (no collectives: decode attention is per-head).  The
    axis name matches the serving engines' create_mesh({'dp','tp'})
    convention (GPTConfig.tp_axis default)."""
    try:
        from ..distributed.mesh import get_mesh
        mesh = get_mesh()
    except Exception:  # pragma: no cover - circular-import safety
        return None, 1
    if mesh is None or "tp" not in mesh.axis_names:
        return None, 1
    tp = int(mesh.shape["tp"])
    if tp <= 1 or hkv % tp or h % tp:
        return None, 1
    return mesh, tp


def _shard_over_tp(body, mesh, in_specs, out_spec, args):
    """shard_map `body` over the mesh with the given per-operand
    PartitionSpecs (axes a spec does not name stay replicated)."""
    from ..distributed.mesh import shard_map
    return shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                     out_specs=out_spec, check_vma=False)(*args)


def write_kv(buf, idx, new):
    """The write half of write-then-attend: store new tokens' k or v
    (or their scales) into one head-major cache buffer, each slot at
    its own position(s).

    buf ``[B, Hkv, S, ...]`` (values carry a trailing D, scale planes
    none); idx ``[B]`` with new ``[B, Hkv, ...]`` (one token a slot),
    or idx ``[B, W]`` with new ``[B, W, Hkv, ...]`` (a window).  The
    scatter names slot, head AND position of every row it writes, so
    its only window dimension is the minor one and the buffer keeps its
    layout: on a donated buffer the compiler writes the B·Hkv·W rows
    where they lie.  (Indexing ``[rows, :, idx]`` instead makes the
    head axis a window dimension, and the TPU compiler then relays the
    whole buffer out position-major and back, two passes over the
    layer a write.)  Slot and head indices are iotas, which GSPMD
    partitions over 'dp' and 'tp' without a collective."""
    b, hkv = buf.shape[:2]
    window = idx.ndim == 2
    if not window:
        idx, new = idx[:, None], new[:, None]
    new = jnp.moveaxis(new, 1, 2).astype(buf.dtype)     # [B, Hkv, W, ...]
    return buf.at[jnp.arange(b)[:, None, None],
                  jnp.arange(hkv)[None, :, None],
                  idx[:, None, :]].set(
        new, indices_are_sorted=True,
        # window positions clamp at the capacity's edge and may repeat
        unique_indices=not window)


def _key_blocks(lengths, block_k: int):
    """How many blocks of ``block_k`` positions hold a slot's first
    ``lengths`` positions (scalars, numpy or jax arrays alike).  The one
    place the length bound is written: the dense kernel's index maps, its
    body and ``positions_streamed`` all read it."""
    return (lengths + (block_k - 1)) // block_k


# Read on the chip at [24, 16, 2048, 128] bf16 over twelve sampled ticks
# of chat-shaped lengths (PERF.md section 6, PR 42): blocks of 512 keys
# with all 16 heads a step 0.210 ms a layer, 8 heads 0.229, 4 heads
# 0.250; blocks of 256 0.266 (a step's products cost nearly what 512
# cost, so half the rounding does not pay).  512 is also the block the
# kernel had before it was bounded, so its sums run in the same order.
_DECODE_BLOCK_K = 512
# k and v blocks, each double-buffered by the pipeline
_DECODE_BLOCKS_VMEM = 8 * 2 ** 20


def _decode_block_k(s: int) -> int:
    """The dense decode kernel's key block: it divides the capacity."""
    return _fa._pick_block(s, _DECODE_BLOCK_K)


def _decode_tiling(hkv: int, s: int, d: int, itemsize: int):
    """(kv heads a program, key block) of the dense decode kernel for a
    ``[B, hkv, s, d]`` layer: the key block divides the capacity, and a
    program takes as many of a slot's kv heads as keep the four blocks in
    flight (k and v, two buffers each, minor dimension padded to the 128
    lanes) inside ``_DECODE_BLOCKS_VMEM``."""
    block_k = _decode_block_k(s)
    strip = 4 * block_k * (-(-d // 128) * 128) * itemsize
    heads = max(h for h in range(1, hkv + 1)
                if hkv % h == 0 and (h * strip <= _DECODE_BLOCKS_VMEM
                                     or h == 1))
    return heads, block_k


def positions_streamed(lengths, capacity: int):
    """Cache positions the dense decode kernel streams for slots holding
    ``lengths`` positions (the new token included) of ``capacity``: the
    length rounded up to the kernel's key block, and one block for an
    empty slot (the pipeline fetches the block its index map names
    whether or not the body runs)."""
    block_k = _decode_block_k(capacity)
    return block_k * _key_blocks(lengths, block_k).clip(
        1, capacity // block_k)


def _first_step(length, block_k: int, steps: int):
    """The step, of a grid axis of ``steps`` key-block steps, at which a
    slot holding ``length`` positions starts: its blocks run on the LAST
    steps, in ascending order, and the steps before them skip.  So the
    step that computes a slot's last block is the one during which the
    pipeline fetches the next slot's first (it prefetches a step ahead):
    a copy is in flight under every block's products."""
    return steps - jnp.clip(_key_blocks(length, block_k), 0, steps)


def _write_rows(dtype) -> int:
    """Rows of the tile through which a write-then-attend call stores
    its token: the storage dtype's sublane tile, 16 of bf16, 8 of
    float32 (the least the chip's compiler lets a block or a copy cut
    out of a buffer)."""
    return 32 // jnp.dtype(dtype).itemsize


def _new_position(length):
    """Where the token a write-then-attend call stores lies in a slot
    that holds ``length`` positions with it: the last of them."""
    return jnp.maximum(length - 1, 0)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, *rest, block_k: int,
                   scale: float, quantized: bool, writes: bool):
    """One (slot, kv-head group, key-block step) step.  len_ref [B] int32
    in scalar memory; q_ref/o_ref [hb, G, D], the query groups of ``hb``
    kv heads of slot b; k_ref/v_ref [hb, block_k, D], the key block the
    index map named for this step: block ``max(j - first, 0)`` with
    ``first`` from ``_first_step``, so nothing past the block that holds
    position ``lengths[b] - 1`` is ever fetched (a block index that does
    not change is not fetched again).  The body runs from step ``first``
    on; the online-softmax state lives in VMEM scratch across the steps
    and the output is written at the last.  Storage-dtype (bf16) MXU
    inputs, f32 scores and accumulation — the same mixed scheme as the
    training flash kernel.  ``quantized``: k/v arrive as int8 with
    ``[hb, 1, block_k]`` f32 scale strips and are dequantized AFTER
    leaving HBM, so the blocks stream at half the bytes.
    ``writes``: the call is the whole write-then-attend of the token at
    position ``lengths[b] - 1``.  kn_ref/vn_ref [hb, 1, D] hold its k
    and v; ko_ref/vo_ref [hb, T, D] are the tile of T rows (the storage
    dtype's sublane tile) of the cache buffers, aliased to k and v,
    that holds the position.  A slot's last step has the block with
    that position in VMEM: the body cuts the tile out of it, selects the
    new row in and stores the tile twice, to the out-ref (the pipeline
    writes it to HBM when the slot's axis ends, and nothing of this slot
    is fetched after) and back into the block, so that the products
    below meet what a write before the call would have left there, in
    the same order."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    elif writes:
        kn_ref, vn_ref, o_ref, ko_ref, vo_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b, j = pl.program_id(0), pl.program_id(2)
    n = len_ref[b]
    heads = q_ref.shape[0]
    first = _first_step(n, block_k, pl.num_programs(2))

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    if writes:
        @pl.when(j == pl.num_programs(2) - 1)
        def _write():
            tile = ko_ref.shape[1]
            at = _new_position(n)
            rows = pl.ds(pl.multiple_of(at % block_k // tile * tile, tile),
                         tile)
            is_new = jax.lax.broadcasted_iota(
                jnp.int32, (1, tile, 1), 1) == at % tile
            for new_ref, blk_ref, out_ref in ((kn_ref, k_ref, ko_ref),
                                              (vn_ref, v_ref, vo_ref)):
                written = jnp.where(is_new, new_ref[:], blk_ref[:, rows, :])
                blk_ref[:, rows, :] = written
                out_ref[:] = written

    @pl.when(j >= first)
    def _block():
        pos = (j - first) * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        valid = pos < n                     # false only in the last block
        for h in range(heads):
            q, k_blk, v_blk = q_ref[h], k_ref[h], v_ref[h]
            if quantized:
                k_blk = (k_blk.astype(jnp.float32) *
                         ks_ref[h, 0, :][:, None]).astype(q.dtype)
                v_blk = (v_blk.astype(jnp.float32) *
                         vs_ref[h, 0, :][:, None]).astype(q.dtype)
            sblk = jax.lax.dot_general(
                q, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [G, bk] f32
            sblk = jnp.where(valid, sblk, _NEG)
            m_prev = m_scr[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(sblk, axis=1, keepdims=True))
            p = jnp.exp(sblk - m_new)
            p = jnp.where(sblk <= _NEG / 2, 0.0, p)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_scr[h, :, :1] * alpha + \
                jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        # a slot at length 0 ran no block: zeros over the guard
        o_ref[:] = (acc_scr[:] / jnp.maximum(l_scr[:, :, :1], 1e-30)
                    ).astype(o_ref.dtype)


def _decode_gqa(q4, k4, v4, lengths, k_scale=None, v_scale=None,
                k_new=None, v_new=None):
    """q4 [B, Hkv, G, D]; k4/v4 [B, Hkv, S, D], the layer as it lies
    (int8 beside its [B, Hkv, S] f32 scale planes when quantized);
    lengths [B] int32.  With ``k_new``/``v_new`` [B, Hkv, 1, D] the call
    also stores them at position ``lengths - 1`` and returns ``(out, k4,
    v4)``."""
    heads, block_k = _decode_tiling(k4.shape[1], k4.shape[2], k4.shape[3],
                                    k4.dtype.itemsize)
    return _decode_call(lengths.astype(jnp.int32), q4, k4, v4, k_scale,
                        v_scale, k_new, v_new, heads=heads, block_k=block_k,
                        interpret=_interpret())


# A jit of its own, so that the layers of a step share ONE trace of the
# body and one Mosaic module: traced layer by layer, the heads unrolled
# in the body cost a 24-layer decode step 3 s of set-up (PERF.md section
# 6, PR 42).  What the trace reads from outside is in the static
# arguments.
@functools.partial(jax.jit, static_argnames=("heads", "block_k", "interpret"))
def _decode_call(lengths, q4, k4, v4, k_scale, v_scale, k_new, v_new, *,
                 heads: int, block_k: int, interpret: bool):
    """The kernel's call: ``lengths`` scalar-prefetched, grid (slot,
    kv-head group, key-block step).  With ``k_new``/``v_new`` it returns
    the cache buffers beside the output, each aliased to its input: of
    either, the call writes one tile of rows a (slot, kv-head group)."""
    pltpu = _fa.pltpu
    b, hkv, g, d = q4.shape
    steps = k4.shape[2] // block_k
    writes = k_new is not None

    def kv_index(i, hg, j, lens):
        first = _first_step(lens[i], block_k, steps)
        return (i, hg, jnp.maximum(j - first, 0), 0)

    io_spec = pl.BlockSpec((None, heads, g, d),
                           lambda i, hg, j, lens: (i, hg, 0, 0))
    kv_spec = pl.BlockSpec((None, heads, block_k, d), kv_index)
    in_specs = [io_spec, kv_spec, kv_spec]
    args = [q4, k4, v4]
    if k_scale is not None:
        # a unit axis spliced in: the strip's two minor dimensions are
        # (1, block_k) however many heads a step takes
        def scale_index(i, hg, j, lens):
            slot, group, block, _ = kv_index(i, hg, j, lens)
            return (slot, group, 0, block)

        sc_spec = pl.BlockSpec((None, heads, 1, block_k), scale_index)
        in_specs += [sc_spec, sc_spec]
        args += [k_scale.astype(jnp.float32)[:, :, None, :],
                 v_scale.astype(jnp.float32)[:, :, None, :]]
    out_specs, out_shape, aliases = io_spec, \
        jax.ShapeDtypeStruct(q4.shape, q4.dtype), {}
    if writes:
        tile = _write_rows(k4.dtype)
        new_spec = pl.BlockSpec((None, heads, 1, d),
                                lambda i, hg, j, lens: (i, hg, 0, 0))
        tile_spec = pl.BlockSpec(
            (None, heads, tile, d), lambda i, hg, j, lens:
            (i, hg, _new_position(lens[i]) // tile, 0))
        in_specs += [new_spec, new_spec]
        args += [k_new, v_new]
        out_specs = [io_spec, tile_spec, tile_spec]
        out_shape = [out_shape, jax.ShapeDtypeStruct(k4.shape, k4.dtype),
                     jax.ShapeDtypeStruct(v4.shape, v4.dtype)]
        # operands count from the scalar-prefetched lengths
        aliases = {2: 1, 3: 2}
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv // heads, steps),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((heads, g, 128), jnp.float32),   # running max
            pltpu.VMEM((heads, g, 128), jnp.float32),   # running denominator
            pltpu.VMEM((heads, g, d), jnp.float32),     # output accumulator
        ],
    )
    call = pl.pallas_call(
        functools.partial(_decode_kernel, block_k=block_k,
                          scale=1.0 / math.sqrt(d),
                          quantized=k_scale is not None, writes=writes),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    return _fa.run_kernel(q4.dtype, call, lengths, *args)


def _dequant_cache(cache, scale, dtype):
    """int8/f8 cache values [..., D] × per-(position, head) scales
    [...] -> compute dtype."""
    return (cache.astype(jnp.float32) *
            scale[..., None].astype(jnp.float32)).astype(dtype)


def _decode_composite(q, k_cache, v_cache, lengths, k_scale=None,
                      v_scale=None):
    """XLA reference math. q [B, H, D]; caches [B, Hkv, S, D]; lengths
    [B] int32 (valid tokens per slot, INCLUDING the one just written).
    With ``k_scale``/``v_scale`` ([B, Hkv, S] f32) the caches hold
    quantized values: dequantize up front, then the IDENTICAL dense
    math — bitwise the dense composite on the dequantized contents."""
    if k_scale is not None:
        k_cache = _dequant_cache(k_cache, k_scale, q.dtype)
        v_cache = _dequant_cache(v_cache, v_scale, q.dtype)
    b, h, d = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, d)
    scores = jnp.einsum("bkgd,bksd->bkgs", qg, k_cache,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    valid = jnp.arange(s)[None, None, None, :] < \
        lengths.astype(jnp.int32)[:, None, None, None]
    scores = jnp.where(valid, scores, _NEG)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,bksd->bkgd", probs, v_cache)
    return out.reshape(b, h, d).astype(q.dtype)


def _dense_kernels_serve(h: int, d: int, k_cache, quantized: bool) -> bool:
    """The shapes the dense kernels serve: a capacity of whole lane
    tiles, D 64 or a multiple of 128, whole query groups, int8 when
    quantized (fp8 rides the composites)."""
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    return (s % 128 == 0 and (d % 128 == 0 or d == 64) and h % hkv == 0
            and (not quantized or k_cache.dtype == jnp.int8))


def decode_attention(q, k_cache, v_cache, lengths, k_scale=None,
                     v_scale=None):
    """Single-token attention over a static, length-masked KV cache.

    q ``[B, H, D]`` — the new token's query per slot; k_cache/v_cache
    ``[B, Hkv, S, D]`` — one head-major layer of the fixed-capacity
    cache AFTER the new token's k/v were written; lengths ``[B]`` int32
    — valid tokens per slot (including the new one).  With a quantized
    cache, ``k_scale``/``v_scale`` carry the per-(position, head) f32
    scales (``[B, Hkv, S]``) and the cache values are int8 (fp8 rides the
    composite).  Returns ``[B, H, D]``.  GQA is native (H % Hkv == 0,
    grouped ``h = hk·G + g`` like flash_attention).  Pallas fused
    kernel when shapes allow, XLA composite otherwise.
    """
    h, d = q.shape[1:]
    hkv = k_cache.shape[1]
    quantized = k_scale is not None
    supported = _dense_kernels_serve(h, d, k_cache, quantized)
    if not supported or not decode_attention_available():
        kernel_paths.note_composite("decode_attention", supported)
        return _decode_composite(q, k_cache, v_cache, lengths,
                                 k_scale, v_scale)
    kernel_paths.note("decode_attention", "kernel")
    mesh, _tp = _tp_mesh(hkv, h)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        specs = [P(None, "tp", None), P(None, "tp", None, None),
                 P(None, "tp", None, None), P(None)]
        args = [q, k_cache, v_cache, lengths]
        if quantized:
            specs += [P(None, "tp", None), P(None, "tp", None)]
            args += [k_scale, v_scale]
        return _shard_over_tp(_decode_kernel_path, mesh, specs,
                              P(None, "tp", None), args)
    return _decode_kernel_path(q, k_cache, v_cache, lengths, k_scale,
                               v_scale)


def decode_attention_writes(q, k_cache) -> bool:
    """Whether ``write_decode_attention`` serves these shapes here:
    ``decode_attention``'s own gate, for a cache without scale planes."""
    return _dense_kernels_serve(q.shape[1], q.shape[2], k_cache, False) \
        and decode_attention_available()


def write_decode_attention(q, k_new, v_new, k_cache, v_cache, idx):
    """``write_kv`` of one token a slot and ``decode_attention`` over
    what it leaves, in ONE kernel call: the decode tick's
    write-then-attend of a cache without scale planes.

    q ``[B, H, D]``; k_new/v_new ``[B, Hkv, D]``, the token's k and v;
    k_cache/v_cache ``[B, Hkv, S, D]``; idx ``[B]`` int32, the token's
    position in its slot (inside the buffer), which holds ``idx + 1``
    positions with it.  Returns ``(out [B, H, D], k_cache, v_cache)``,
    the buffers with row ``idx[b]`` of every head of slot b written and
    nothing else touched, each aliased to its operand: donated, it is
    written where it lies and the tick holds no scatter.  The kernel
    attends the new row from VMEM, in the place and the order in which a
    write before the call would have had it read: the output is
    ``decode_attention``'s bit for bit.  There is no composite here:
    callers ask ``decode_attention_writes`` first and keep ``write_kv``
    + ``decode_attention`` for everything it refuses."""
    h, hkv = q.shape[1], k_cache.shape[1]
    kernel_paths.note("decode_attention", "kernel")
    args = [q, k_new.astype(k_cache.dtype), v_new.astype(v_cache.dtype),
            k_cache, v_cache, idx.astype(jnp.int32) + 1]
    mesh, _tp = _tp_mesh(hkv, h)
    if mesh is None:
        return _decode_write_kernel_path(*args)
    from jax.sharding import PartitionSpec as P
    row, layer = P(None, "tp", None), P(None, "tp", None, None)
    return _shard_over_tp(_decode_write_kernel_path, mesh,
                          [row, row, row, layer, layer, P(None)],
                          (row, layer, layer), args)


def _decode_kernel_path(q, k_cache, v_cache, lengths, k_scale=None,
                        v_scale=None):
    """The dense kernel dispatch AFTER the support gate — also the
    shard_map body under tp (per-shard head ranges, same code;
    ``lengths`` replicated).  The kernel takes the head-major layer as
    it lies: no whole-layer copy, and no mask, stands between the cache
    and the kernel."""
    b, h, d = q.shape
    hkv = k_cache.shape[1]
    kernel_paths.note("decode_attention.bounded", "kernel")
    return _decode_gqa(q.reshape(b, hkv, h // hkv, d), k_cache, v_cache,
                       lengths, k_scale, v_scale).reshape(b, h, d)


def _decode_write_kernel_path(q, k_new, v_new, k_cache, v_cache, lengths):
    """``write_decode_attention``'s dispatch after its gate, and its
    shard_map body under tp: the same kernel with the new rows as
    operands and the buffers as outputs.  ``lengths`` count the new
    token."""
    b, h, d = q.shape
    hkv = k_cache.shape[1]
    kernel_paths.note("decode_attention.bounded", "kernel")
    kernel_paths.note("decode_attention.fused_write", "kernel")
    out, k_cache, v_cache = _decode_gqa(
        q.reshape(b, hkv, h // hkv, d), k_cache, v_cache, lengths,
        k_new=k_new[:, :, None], v_new=v_new[:, :, None])
    return out.reshape(b, h, d), k_cache, v_cache


# ---------------------------------------------------------------------------
# paged variant: K/V live in a block pool, streamed through a block table
# ---------------------------------------------------------------------------
def paged_decode_attention_available() -> bool:
    """Same availability surface as the dense kernel (the paged kernel
    additionally drives its K/V DMA addresses from scalar-prefetched
    block tables)."""
    return decode_attention_available()


def _paged_supported(k_pool, h, d, quantized) -> bool:
    """Shapes the paged kernels serve: block size a multiple of the
    bf16 sublane tile (any such size is a legal block — the per-head
    strip is the pool's two minor dimensions), D 64 or a multiple of
    128, int8 when quantized (fp8 rides the composite)."""
    hkv, bs = k_pool.shape[1], k_pool.shape[2]
    return (bs % 16 == 0 and (d % 128 == 0 or d == 64)
            and h % hkv == 0
            and (not quantized or k_pool.dtype == jnp.int8))


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                  block_size: int, hkv: int, g: int, scale: float,
                  quantized: bool):
    """One (b·hkv, j) program of the paged kernels (single-token decode
    is the W = 1 window): j walks the slot's block table; the BlockSpec
    index_map already resolved table entry j to a pool block, so
    k_ref/v_ref hold that block's ``[block_size, D]`` strip for this kv
    head (int8 plus ``[1, block_size]`` f32 scale strips when
    ``quantized`` — dequantized after the DMA).  q_ref is ``[W·G, D]``,
    rows grouped w·G+g; row r's window index is r//g, so position p is
    valid iff ``p < len_ref[b] + r//g + 1`` (len_ref counts tokens
    cached BEFORE the window).  Online-softmax state (m/l/acc) persists
    in VMEM scratch across the j steps (TPU grids run sequentially,
    innermost fastest); the output is written once on the last block."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    j = pl.program_id(1)
    n_blocks = pl.num_programs(1)
    b = pl.program_id(0) // hkv

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[:]                                        # [W·G, D]
    wg = q.shape[0]
    k_blk = k_ref[:]                                    # [bs, D]
    v_blk = v_ref[:]
    if quantized:
        ks = ks_ref[0, :]                               # (bs,) f32
        vs = vs_ref[0, :]
        k_blk = (k_blk.astype(jnp.float32) * ks[:, None]).astype(q.dtype)
        v_blk = (v_blk.astype(jnp.float32) * vs[:, None]).astype(q.dtype)
    sblk = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale     # [wg, bs] f32
    pos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)                  # [1, bs]
    win = jax.lax.broadcasted_iota(jnp.int32, (wg, 1), 0) // g
    sblk = jnp.where(pos < len_ref[b] + win + 1, sblk, _NEG)
    m_prev = m_scr[:, :1]
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(sblk, axis=1, keepdims=True))
    p = jnp.exp(sblk - m_new)
    p = jnp.where(sblk <= _NEG / 2, 0.0, p)             # fully-masked blocks
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
        p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == n_blocks - 1)
    def _finalize():
        o_ref[:] = (acc_scr[:] /
                    jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)


def _paged_gqa(q3, k_pool, v_pool, tables, lengths, w,
               k_scale=None, v_scale=None):
    """q3 [B·Hkv, W·G, D]; pools [NB, Hkv, bs, D]; tables [B, MB] int32;
    lengths [B] int32 EXCLUDING the window; quantized pools add their
    [NB, Hkv, bs] f32 scale pools.  Scalar-prefetched tables/lengths let
    each grid step's index_map pick its pool block, so only the slot's
    own blocks ever leave HBM (no gather of the whole table into dense
    form).  Heads sit AHEAD of the block dimension in the pool, so the
    per-head ``[bs, D]`` strip (and, with a unit axis spliced in, the
    ``[1, bs]`` scale strip) is the pool's two minor dimensions — the
    block shape the TPU lowering accepts at every block size."""
    pltpu = _fa.pltpu
    bhkv, wg, d = q3.shape
    bs = k_pool.shape[2]
    b, mb = tables.shape
    hkv = bhkv // b
    quantized = k_scale is not None

    def pool_index(i, j, tbl, lens):
        return (tbl[i // hkv, j], i % hkv, 0, 0)

    kv_spec = pl.BlockSpec((None, None, bs, d), pool_index)
    io_spec = pl.BlockSpec((None, wg, d),
                           lambda i, j, tbl, lens: (i, 0, 0))
    in_specs = [io_spec, kv_spec, kv_spec]
    args = [q3, k_pool, v_pool]
    if quantized:
        sc_spec = pl.BlockSpec((None, None, 1, bs), pool_index)
        in_specs += [sc_spec, sc_spec]
        args += [k_scale.astype(jnp.float32)[:, :, None, :],
                 v_scale.astype(jnp.float32)[:, :, None, :]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bhkv, mb),
        in_specs=in_specs,
        out_specs=io_spec,
        scratch_shapes=[
            pltpu.VMEM((wg, 128), jnp.float32),   # running max
            pltpu.VMEM((wg, 128), jnp.float32),   # running denominator
            pltpu.VMEM((wg, d), jnp.float32),     # output accumulator
        ],
    )
    kernel = functools.partial(
        _paged_kernel, block_size=bs, hkv=hkv, g=wg // w,
        scale=1.0 / math.sqrt(d), quantized=quantized)
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bhkv, wg, d), q3.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(),
        name="paged_decode_attention",
    )
    return _fa.run_kernel(
        q3.dtype, call, tables.astype(jnp.int32), lengths.astype(jnp.int32),
        *args)


def _gather_pool(pool, tables):
    """Pool blocks [NB, Hkv, bs, ...] through tables [B, MB] -> the
    dense per-slot head-major layout [B, Hkv, MB·bs, ...]."""
    g = jnp.swapaxes(pool[tables], 1, 2)        # [B, Hkv, MB, bs, ...]
    return g.reshape(g.shape[:2] + (g.shape[2] * g.shape[3],) +
                     g.shape[4:])


def _paged_composite(q, k_pool, v_pool, tables, lengths, k_scale=None,
                     v_scale=None):
    """XLA reference math: gather each slot's blocks into the dense
    ``[B, Hkv, S, D]`` layout (S = MB·bs) and reuse the dense composite.
    Bitwise-identical to the dense path on identical cache contents —
    the parity oracle tests/test_paged_kv.py leans on.  Quantized pools
    gather their ``[num_blocks, Hkv, bs]`` scale pools the same way."""
    ksg = vsg = None
    if k_scale is not None:
        ksg = _gather_pool(k_scale, tables)
        vsg = _gather_pool(v_scale, tables)
    return _decode_composite(q, _gather_pool(k_pool, tables),
                             _gather_pool(v_pool, tables), lengths,
                             ksg, vsg)


def paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                           k_scale=None, v_scale=None):
    """Single-token attention over a PAGED, length-masked KV cache.

    q ``[B, H, D]`` — the new token's query per slot; k_pool/v_pool
    ``[num_blocks, Hkv, block_size, D]`` — the shared block pool AFTER
    the new token's k/v were written; tables ``[B, max_blocks]`` int32 —
    per-slot block table (pool indices; entries past the slot's extent
    point at the reserved null block and stay masked); lengths ``[B]``
    int32 — valid tokens per slot including the new one.  With a
    quantized pool, ``k_scale``/``v_scale`` are the
    ``[num_blocks, Hkv, block_size]`` f32 scale pools and the value
    pools are int8 (fp8 rides the composite).  Returns ``[B, H, D]``.
    The Pallas kernel streams K/V (and scales) block-by-block through
    the block table via scalar prefetch; the XLA composite gathers the
    table into dense form and is the CPU/fallback ground truth.
    """
    b, h, d = q.shape
    supported = _paged_supported(k_pool, h, d, k_scale is not None)
    if not supported or not paged_decode_attention_available():
        kernel_paths.note_composite("paged_decode_attention", supported)
        return _paged_composite(q, k_pool, v_pool, tables, lengths,
                                k_scale, v_scale)
    kernel_paths.note("paged_decode_attention", "kernel")
    # single-token decode IS the W = 1 window whose lengths exclude it
    out = _paged_window_dispatch(
        q[:, None], k_pool, v_pool, tables,
        lengths.astype(jnp.int32) - 1, k_scale, v_scale)
    return out[:, 0]


# ---------------------------------------------------------------------------
# window variant: K+1 query tokens per slot in ONE call — the verify
# half of speculative decoding (Leviathan et al.).  The draft proposes K
# tokens; the target model scores all K+1 positions against the cache in
# one fixed-shape executable instead of K+1 sequential decode steps.
# Query i (absolute position lengths[b]+i) attends cache positions
# j <= lengths[b]+i, where `lengths` counts tokens cached BEFORE the
# window (the caller scatters the window's k/v at lengths..lengths+W-1
# first, exactly like the single-token write-then-attend order).
# ---------------------------------------------------------------------------
def _window_kernel(q_ref, k_ref, v_ref, m_ref, o_ref, *, block_k: int,
                   g: int, scale: float):
    """One (b·hkv) program: q_ref [W·G, D] — W window queries × G query
    heads per kv head, rows grouped w·G+g; k/v [S, D] cache strips;
    m_ref (W, S) f32 per-QUERY validity (the staircase mask); o [W·G, D].
    Same online softmax as _decode_kernel with the mask row picked per
    query row."""
    wg, d = q_ref.shape
    s = k_ref.shape[0]
    n_k = s // block_k

    q = q_ref[:]
    m0 = jnp.full((wg, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((wg, 1), jnp.float32)
    acc0 = jnp.zeros((wg, d), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[pl.ds(j * block_k, block_k), :]
        sblk = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [wg, bk] f32
        kv_f = m_ref[:, pl.ds(j * block_k, block_k)]       # (W, bk) f32
        kv_f = jnp.repeat(kv_f, g, axis=0)                 # (wg, bk)
        sblk = jnp.where(kv_f > 0, sblk, _NEG)
        m_new = jnp.maximum(m, jnp.max(sblk, axis=1, keepdims=True))
        p = jnp.exp(sblk - m_new)
        p = jnp.where(sblk <= _NEG / 2, 0.0, p)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, n_k, body, (m0, l0, acc0))
    o_ref[:] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _window_kernel_q(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref, o_ref,
                     *, block_k: int, g: int, scale: float):
    """Quantized-cache window kernel: int8 strips + (1, S) f32 scale
    strips dequantized after the DMA (scales are per cache POSITION, so
    they are shared by every query row)."""
    wg, d = q_ref.shape
    s = k_ref.shape[0]
    n_k = s // block_k

    q = q_ref[:]
    m0 = jnp.full((wg, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((wg, 1), jnp.float32)
    acc0 = jnp.zeros((wg, d), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        ks = ks_ref[0, pl.ds(j * block_k, block_k)]
        vs = vs_ref[0, pl.ds(j * block_k, block_k)]
        k_blk = (k_ref[pl.ds(j * block_k, block_k), :]
                 .astype(jnp.float32) * ks[:, None]).astype(q.dtype)
        v_blk = (v_ref[pl.ds(j * block_k, block_k), :]
                 .astype(jnp.float32) * vs[:, None]).astype(q.dtype)
        sblk = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        kv_f = jnp.repeat(m_ref[:, pl.ds(j * block_k, block_k)], g,
                          axis=0)
        sblk = jnp.where(kv_f > 0, sblk, _NEG)
        m_new = jnp.maximum(m, jnp.max(sblk, axis=1, keepdims=True))
        p = jnp.exp(sblk - m_new)
        p = jnp.where(sblk <= _NEG / 2, 0.0, p)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, n_k, body, (m0, l0, acc0))
    o_ref[:] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _window_gqa(q3, k3, v3, mask, ks3=None, vs3=None, block_k=512):
    """q3 [B·Hkv, W·G, D]; k3/v3 [B·Hkv, S, D]; mask [B, W, S] f32;
    quantized path adds ks3/vs3 [B·Hkv, 1, S] f32 scale strips."""
    bhkv, wg, d = q3.shape
    s = k3.shape[1]
    b, w = mask.shape[0], mask.shape[1]
    hkv = bhkv // b
    g = wg // w
    block_k = _fa._pick_block(s, block_k)
    scale = 1.0 / math.sqrt(d)
    mask_spec = pl.BlockSpec((None, w, s),
                             lambda i, hkv=hkv: (i // hkv, 0, 0))
    io_spec = pl.BlockSpec((None, wg, d), lambda i: (i, 0, 0))
    kv_spec = pl.BlockSpec((None, s, d), lambda i: (i, 0, 0))
    if ks3 is None:
        kernel = functools.partial(_window_kernel, block_k=block_k, g=g,
                                   scale=scale)
        in_specs = [io_spec, kv_spec, kv_spec, mask_spec]
        args = (q3, k3, v3, mask)
    else:
        kernel = functools.partial(_window_kernel_q, block_k=block_k,
                                   g=g, scale=scale)
        sc_spec = pl.BlockSpec((None, 1, s), lambda i: (i, 0, 0))
        in_specs = [io_spec, kv_spec, kv_spec, sc_spec, sc_spec,
                    mask_spec]
        args = (q3, k3, v3, ks3, vs3, mask)
    call = pl.pallas_call(
        kernel,
        grid=(bhkv,),
        in_specs=in_specs,
        out_specs=io_spec,
        out_shape=jax.ShapeDtypeStruct((bhkv, wg, d), q3.dtype),
        interpret=_interpret(),
    )
    return _fa.run_kernel(q3.dtype, call, *args)


def _window_composite(q, k_cache, v_cache, lengths, k_scale=None,
                      v_scale=None):
    """XLA reference math for the window variant. q [B, W, H, D];
    caches [B, Hkv, S, D]; lengths [B] int32 EXCLUDING the window
    (query i sees cache positions j <= lengths[b]+i)."""
    if k_scale is not None:
        k_cache = _dequant_cache(k_cache, k_scale, q.dtype)
        v_cache = _dequant_cache(v_cache, v_scale, q.dtype)
    b, w, h, d = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    qg = q.reshape(b, w, hkv, g, d)
    scores = jnp.einsum("bwkgd,bksd->bkwgs", qg, k_cache,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    limit = lengths.astype(jnp.int32)[:, None] + \
        jnp.arange(w, dtype=jnp.int32)[None, :] + 1        # [b, w]
    valid = jnp.arange(s)[None, None, :] < limit[:, :, None]
    scores = jnp.where(valid[:, None, :, None, :], scores, _NEG)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkwgs,bksd->bwkgd", probs, v_cache)
    return out.reshape(b, w, h, d).astype(q.dtype)


def decode_attention_window(q, k_cache, v_cache, lengths, k_scale=None,
                            v_scale=None):
    """Windowed multi-token attention over a static KV cache — the
    spec-decode verify primitive.

    q ``[B, W, H, D]`` — W consecutive new tokens' queries per slot
    (W = draft K + 1 in the verify step); k_cache/v_cache
    ``[B, Hkv, S, D]`` AFTER the window's k/v were written at positions
    ``lengths..lengths+W-1``; lengths ``[B]`` int32 — tokens cached
    BEFORE the window.  Query i attends ``j <= lengths[b]+i`` (itself
    included), so logits[i] is exactly what a sequential decode of
    token i would produce — that equivalence is the token-identity
    guarantee speculative decoding rests on.  ``W=1`` reduces to
    ``decode_attention`` with lengths+1.  Quantized caches pass their
    ``[B, Hkv, S]`` f32 scale planes.  Returns ``[B, W, H, D]``."""
    h, d = q.shape[2:]
    hkv = k_cache.shape[1]
    quantized = k_scale is not None
    supported = _dense_kernels_serve(h, d, k_cache, quantized)
    if not supported or not decode_attention_available():
        kernel_paths.note_composite("decode_attention_window", supported)
        return _window_composite(q, k_cache, v_cache, lengths,
                                 k_scale, v_scale)
    kernel_paths.note("decode_attention_window", "kernel")
    mesh, _tp = _tp_mesh(hkv, h)
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        specs = [P(None, None, "tp", None), P(None, "tp", None, None),
                 P(None, "tp", None, None), P(None)]
        args = [q, k_cache, v_cache, lengths]
        if quantized:
            specs += [P(None, "tp", None), P(None, "tp", None)]
            args += [k_scale, v_scale]
        return _shard_over_tp(_window_kernel_path, mesh, specs,
                              P(None, None, "tp", None), args)
    return _window_kernel_path(q, k_cache, v_cache, lengths, k_scale,
                               v_scale)


def _window_kernel_path(q, k_cache, v_cache, lengths, k_scale=None,
                        v_scale=None):
    """The dense window-kernel dispatch AFTER the support gate — also
    the shard_map body under tp."""
    b, w, h, d = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    limit = lengths.astype(jnp.int32)[:, None] + \
        jnp.arange(w, dtype=jnp.int32)[None, :] + 1
    mask = (jnp.arange(s)[None, None, :] <
            limit[:, :, None]).astype(jnp.float32)          # [b, w, s]
    # rows grouped (w, g): [b, w, hkv, g, d] -> [b, hkv, w, g, d]
    q3 = q.reshape(b, w, hkv, h // hkv, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b * hkv, w * (h // hkv), d)
    k3 = k_cache.reshape(b * hkv, s, d)
    v3 = v_cache.reshape(b * hkv, s, d)
    ks3 = vs3 = None
    if k_scale is not None:
        ks3 = k_scale.astype(jnp.float32).reshape(b * hkv, 1, s)
        vs3 = v_scale.astype(jnp.float32).reshape(b * hkv, 1, s)
    o3 = _window_gqa(q3, k3, v3, mask, ks3, vs3)
    return o3.reshape(b, hkv, w, h // hkv, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, w, h, d)


def _paged_window_composite(q, k_pool, v_pool, tables, lengths,
                            k_scale=None, v_scale=None):
    """Gather the slot's blocks dense, reuse the dense window composite
    — bitwise the dense path on identical cache contents."""
    ksg = vsg = None
    if k_scale is not None:
        ksg = _gather_pool(k_scale, tables)
        vsg = _gather_pool(v_scale, tables)
    return _window_composite(q, _gather_pool(k_pool, tables),
                             _gather_pool(v_pool, tables), lengths,
                             ksg, vsg)


def paged_decode_attention_window(q, k_pool, v_pool, tables, lengths,
                                  k_scale=None, v_scale=None):
    """Windowed multi-token attention over a PAGED KV cache — the
    spec-decode verify primitive for the paged layout.  q
    ``[B, W, H, D]``; pools/tables as :func:`paged_decode_attention`;
    lengths ``[B]`` int32 EXCLUDING the window (its k/v were already
    scattered through the block table at positions
    ``lengths..lengths+W-1``).  Query i attends ``j <= lengths[b]+i``.
    Pallas scalar-prefetch kernel when shapes allow, gather composite
    (ground truth) otherwise."""
    b, w, h, d = q.shape
    supported = _paged_supported(k_pool, h, d, k_scale is not None)
    if not supported or not paged_decode_attention_available():
        kernel_paths.note_composite("paged_decode_attention_window", supported)
        return _paged_window_composite(q, k_pool, v_pool, tables,
                                       lengths, k_scale, v_scale)
    kernel_paths.note("paged_decode_attention_window", "kernel")
    return _paged_window_dispatch(q, k_pool, v_pool, tables, lengths,
                                  k_scale, v_scale)


def _paged_window_dispatch(q, k_pool, v_pool, tables, lengths,
                           k_scale=None, v_scale=None):
    """The paged kernel dispatch AFTER the support gate: plain, or
    wrapped in shard_map over 'tp' on a serving mesh."""
    h, hkv = q.shape[2], k_pool.shape[1]
    mesh, _tp = _tp_mesh(hkv, h)
    if mesh is None:
        return _paged_window_kernel_path(q, k_pool, v_pool, tables,
                                         lengths, k_scale, v_scale)
    from jax.sharding import PartitionSpec as P
    specs = [P(None, None, "tp", None), P(None, "tp", None, None),
             P(None, "tp", None, None), P(None, None), P(None)]
    args = [q, k_pool, v_pool, tables, lengths]
    if k_scale is not None:
        specs += [P(None, "tp", None), P(None, "tp", None)]
        args += [k_scale, v_scale]
    return _shard_over_tp(_paged_window_kernel_path, mesh, specs,
                          P(None, None, "tp", None), args)


def _paged_window_kernel_path(q, k_pool, v_pool, tables, lengths,
                              k_scale=None, v_scale=None):
    """The paged kernel itself on [B, W, H, D] queries — also the
    shard_map body under tp (tables replicated: allocation is host
    state, each shard walks the same tables over its own head-slice of
    the pool)."""
    b, w, h, d = q.shape
    hkv = k_pool.shape[1]
    q3 = q.reshape(b, w, hkv, h // hkv, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b * hkv, w * (h // hkv), d)
    o3 = _paged_gqa(q3, k_pool, v_pool, tables, lengths, w,
                    k_scale, v_scale)
    return o3.reshape(b, hkv, w, h // hkv, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b, w, h, d)


# ---- chunked-prefill aliases -------------------------------------------
# Chunked prefill (ISSUE 20) IS the window attention with W = chunk:
# the engine scatters a [B, chunk] slice of each still-prefilling
# slot's prompt at positions lengths..lengths+chunk-1, and query i must
# see exactly j <= lengths[b]+i — the same staircase the spec verify
# needs.  The aliases give the chunk scheduler (and its tests) a name
# for that contract without duplicating a kernel; the support gate,
# tp shard_map path, int8 scale strips and composite oracles all come
# along for free.
chunk_prefill_attention = decode_attention_window
paged_chunk_prefill_attention = paged_decode_attention_window
