"""Paged KV cache: a fixed block pool + per-slot block tables.

The dense serving cache (``models.StaticKVCache``) preallocates, per
layer, ``[batch_slots, kv_heads, max_seq, head_dim]`` — every slot
owns ``max_seq`` positions whether it uses them or not, so slot count
(= concurrent users) is capped by ``slots × max_seq`` memory even when
every live request is short.  This module is the vLLM-style fix
(Kwon et al., *Efficient Memory Management for Large Language Model
Serving with PagedAttention*): K/V live in a pool of fixed-size blocks

    ``[layers, num_blocks, kv_heads, block_size, head_dim]``

(heads AHEAD of the block dimension: the paged kernels' per-head
``[block_size, head_dim]`` strip is then the two minor dimensions of
the pool, which is the only block shape the TPU lowering accepts for
every block size) and each slot holds a small BLOCK TABLE of pool indices.  A slot
consumes exactly ``ceil(len/block_size)`` blocks, so concurrency is
bounded by total memory, not by the worst-case sequence length — and
blocks can be SHARED between slots (refcounts), which is what makes
radix prefix caching (prefix_cache.py) free.

Split of responsibilities, mirroring the reference framework's
AllocatorFacade layer (PAPER.md §1 layer 1 — allocator policy lives
outside the kernels):

- **Device** (:class:`PagedKVCache`): the k/v pools only.  Statically
  shaped; every update inside the prefill/decode executables is a
  ``dynamic_update_slice``/scatter, so the zero-recompile invariant of
  the dense engine survives paging.  Registered as a pytree so it rides
  jit carries and donation.
- **Host** (:class:`BlockAllocator`): free-list + per-block refcounts.
  Block 0 is reserved as the NULL block — unused block-table entries
  point at it, so the executables never see an out-of-range index;
  whatever garbage lands there is masked by per-slot lengths.

Block tables and per-slot lengths stay host-side (numpy) and enter the
executables as ordinary ``[batch_slots, max_blocks]`` / ``[batch_slots]``
int32 operands each step: their shapes never change, and shipping a few
hundred int32s per step is noise next to the cache itself.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..models.gpt import KVLayerView, kv_step_bytes, kv_tick_reads
from ..observability import metrics as _metrics

__all__ = ["PagedKVCache", "BlockAllocator", "init_paged_cache",
           "blocks_for", "blocks_to_extend", "blocks_to_rows",
           "rows_to_blocks"]


def blocks_for(n_tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``n_tokens`` positions."""
    return -(-int(n_tokens) // int(block_size))


def blocks_to_extend(have_blocks: int, new_len: int,
                     block_size: int) -> int:
    """Additional blocks a slot holding ``have_blocks`` needs to cover
    ``new_len`` positions — the chunk-granular ensure-room arithmetic:
    a chunked prefill (and a multi-token spec commit) grows a slot by
    several tokens at once, so room is a delta in BLOCKS, not a
    yes/no on one."""
    return max(blocks_for(new_len, block_size) - int(have_blocks), 0)


def blocks_to_rows(blocks):
    """Pool blocks ``[n, Hkv, bs, ...]`` -> position-major rows
    ``[n·bs, Hkv, ...]`` (the paged prefill's working buffer).  Works for
    value blocks (trailing D) and scale blocks (no trailing dim)."""
    rows = jnp.swapaxes(blocks, 1, 2)
    return rows.reshape((-1,) + rows.shape[2:])


def rows_to_blocks(rows, block_size: int):
    """Inverse of :func:`blocks_to_rows`: ``[n·bs, Hkv, ...]`` ->
    ``[n, Hkv, bs, ...]``."""
    return jnp.swapaxes(
        rows.reshape((-1, int(block_size)) + rows.shape[1:]), 1, 2)


@dataclass
class PagedKVLayer(KVLayerView):
    """A PagedKVCache layer behind the slots' block ``tables``
    ``[B, MB]``: ``k``/``v`` ``[num_blocks, Hkv, bs, D]``, scale pools
    ``[num_blocks, Hkv, bs]``.  Position p of slot b lives at
    ``(tables[b, p // bs], p % bs)``; slots with no blocks (all-zero
    table rows) and positions past a table's last entry write into the
    reserved null block — masked garbage by construction."""

    tables: Optional[jax.Array] = None

    def _locate(self, pos):
        bs, (b, mb) = self.k.shape[2], self.tables.shape
        blk_pos = jnp.minimum(pos // bs, mb - 1)
        off = pos % bs
        rows = jnp.arange(b) if pos.ndim == 1 else jnp.arange(b)[:, None]
        return self.tables[rows, blk_pos], off

    def _put(self, pool, at, new):
        blk, off = at
        return pool.at[blk, :, off].set(new.astype(pool.dtype))

    def _attend_token(self, q, lens, at):
        from ..ops import paged_decode_attention
        return paged_decode_attention(q, self.k, self.v, self.tables,
                                      lens + 1, self.k_scale, self.v_scale)

    def _attend_window(self, q, lens):
        from ..ops import paged_decode_attention_window
        return paged_decode_attention_window(
            q, self.k, self.v, self.tables, lens, self.k_scale,
            self.v_scale)


class PagedKVCache:
    """Device half of the paged cache: ``k``/``v`` are
    ``[layers, num_blocks, kv_heads, block_size, head_dim]`` block
    pools.  Which blocks belong to which slot is the host allocator's
    business; the executables receive block tables as operands.

    Quantized form (``kv_dtype='int8'``/``'fp8'``): the value pools
    hold 8-bit values and ``k_scale``/``v_scale`` the per-(position,
    head) f32 scale pools ``[layers, num_blocks, kv_heads, block_size]``
    — the paged decode kernel streams both and dequantizes in VMEM.
    Full-precision pools (``k_scale is None``) stay the default and the
    parity oracle."""

    __slots__ = ("k", "v", "k_scale", "v_scale")

    def __init__(self, k, v, k_scale=None, v_scale=None):
        self.k, self.v = k, v
        self.k_scale, self.v_scale = k_scale, v_scale

    @property
    def num_layers(self):
        return self.k.shape[0]

    @property
    def dtype(self):
        return self.k.dtype

    @property
    def num_blocks(self):
        return self.k.shape[1]

    @property
    def block_size(self):
        return self.k.shape[3]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    tick_reads = staticmethod(kv_tick_reads)

    def step_bytes_per_slot(self, positions: int, tp: int = 1) -> int:
        return kv_step_bytes(self.num_layers, self.k.shape[2],
                             self.k.shape[4], self.dtype, self.quantized,
                             positions, tp)

    def layer(self, i, tables) -> PagedKVLayer:
        """Layer ``i``'s pools behind the slots' block tables, as a
        serving step's view."""
        k, v = self.k[i], self.v[i]
        scales = (self.k_scale[i], self.v_scale[i]) if self.quantized \
            else (None, None)
        return PagedKVLayer(k, v, *scales,
                            tables=jnp.asarray(tables, jnp.int32))

    def with_layer(self, i, kv: PagedKVLayer) -> "PagedKVCache":
        """The cache with layer ``i``'s pools written back."""
        k_scale, v_scale = self.k_scale, self.v_scale
        if self.quantized:
            k_scale = k_scale.at[i].set(kv.k_scale)
            v_scale = v_scale.at[i].set(kv.v_scale)
        return PagedKVCache(self.k.at[i].set(kv.k), self.v.at[i].set(kv.v),
                            k_scale, v_scale)

    def __repr__(self):
        return (f"PagedKVCache(layers={self.k.shape[0]}, "
                f"blocks={self.k.shape[1]}, block_size={self.k.shape[3]}, "
                f"kv_heads={self.k.shape[2]}, dtype={self.k.dtype}"
                f"{', quantized' if self.quantized else ''})")


jax.tree_util.register_pytree_node(
    PagedKVCache,
    lambda c: ((c.k, c.v, c.k_scale, c.v_scale), None),
    lambda aux, ch: PagedKVCache(*ch))


def init_paged_cache(model, num_blocks: int, block_size: int,
                     dtype=None, kv_dtype=None) -> PagedKVCache:
    """Allocate the zeroed block pool for ``model`` (a GPTForCausalLM /
    GPTModel).  ``num_blocks`` INCLUDES the reserved null block 0, so
    the usable capacity is ``num_blocks - 1`` blocks.  ``kv_dtype=
    'int8'``/``'fp8'`` (default from ``PADDLE_TPU_KV_DTYPE``) allocates
    8-bit value pools plus f32 scale pools."""
    from ..ops.quantized_matmul import kv_storage_dtype, resolve_kv_quant
    gpt = getattr(model, "gpt", model)
    cfg = gpt.cfg
    mode = resolve_kv_quant(kv_dtype)
    dt = kv_storage_dtype(mode) if mode else \
        (dtype or gpt.wte.weight.dtype)
    shape = (cfg.num_layers, int(num_blocks), cfg.num_kv_heads,
             int(block_size), cfg.head_dim)
    scales = (jnp.zeros(shape[:-1], jnp.float32),
              jnp.zeros(shape[:-1], jnp.float32)) if mode else (None, None)
    return PagedKVCache(jnp.zeros(shape, dt), jnp.zeros(shape, dt),
                        *scales)


class BlockAllocator:
    """Host-side pool bookkeeping: LIFO free-list + refcounts.

    Block ids run ``1..num_blocks-1`` (0 is the null block and is never
    handed out).  ``alloc`` refuses rather than over-commits — the
    scheduler turns a refusal into queueing/eviction/preemption, which
    is the whole point of admission-by-free-blocks.  ``incref`` is how
    a second owner (another slot sharing a prefix, or the radix cache
    pinning a node) holds a block; ``decref`` frees at zero.
    """

    _ids = itertools.count()

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is the "
                             "reserved null block)")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self._refs = np.zeros(self.num_blocks, np.int32)
        # LIFO: recently-freed blocks are re-used first (their pool rows
        # are warm in cache on CPU; harmless on TPU)
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        # alloc-attempt counter (successes AND refusals): the scheduler
        # contract that a blocked head-of-line request is NOT re-probed
        # every tick is asserted against this number
        self.probes = 0
        # pool pressure into the metrics registry (one gauge set per
        # alloc/decref — attribute arithmetic on a pre-bound child)
        pool = f"p{next(BlockAllocator._ids)}"
        self._m_in_use = _metrics.gauge(
            "kv_blocks_in_use", "paged KV blocks held",
            labels=("pool",)).labels(pool=pool)
        _metrics.gauge("kv_blocks_capacity", "allocatable pool blocks",
                       labels=("pool",)).labels(pool=pool).set(
            self.capacity)

    @property
    def capacity(self) -> int:
        """Allocatable blocks (null block excluded)."""
        return self.num_blocks - 1

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_in_use(self) -> int:
        return self.capacity - len(self._free)

    def refcount(self, block: int) -> int:
        return int(self._refs[block])

    def alloc(self, n: int) -> Optional[List[int]]:
        """n fresh blocks at refcount 1, or None when the pool cannot
        satisfy the request (caller queues/evicts/preempts)."""
        self.probes += 1
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._refs[b] = 1
        self._m_in_use.set(self.capacity - len(self._free))
        return out

    def incref(self, blocks) -> None:
        for b in blocks:
            if self._refs[b] <= 0:
                raise RuntimeError(f"incref on free block {b}")
            self._refs[b] += 1

    def decref(self, blocks) -> None:
        for b in blocks:
            r = int(self._refs[b]) - 1
            if r < 0:
                raise RuntimeError(f"double free of block {b}")
            self._refs[b] = r
            if r == 0:
                self._free.append(b)
        self._m_in_use.set(self.capacity - len(self._free))

    def check_leak_free(self) -> None:
        """Raise unless every block is back on the free list — the
        drain invariant the load-test smoke asserts."""
        if self.num_free != self.capacity:
            held = [b for b in range(1, self.num_blocks)
                    if self._refs[b] > 0]
            raise AssertionError(
                f"block pool leak: {self.num_free}/{self.capacity} free; "
                f"held blocks {held[:16]}{'...' if len(held) > 16 else ''}")
