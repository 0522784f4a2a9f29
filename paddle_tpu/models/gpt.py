"""GPT model family — the flagship decoder-only transformer.

Reference parity target: the GPT configs the driver benchmarks
(/root/repo/BASELINE.json config #4: GPT-3 1.3B/13B under Fleet hybrid
parallel; the reference repo itself ships the transformer building blocks
at python/paddle/nn/layer/transformer.py — no in-tree GPT — so the
architecture here is the standard GPT-3 decoder written TPU-first).

TPU-first design decisions:
- weights live in tensor-parallel layers (ColumnParallelLinear /
  RowParallelLinear / VocabParallelEmbedding) whose PartitionSpecs the
  compiled trainer (distributed.spmd.SpmdTrainer) hands to GSPMD: the
  attention qkv + mlp-up projections shard over 'tp' columns, the output
  projections shard over 'tp' rows — Megatron placement, one all-reduce
  per block half, riding ICI;
- attention routes through the Pallas flash-attention kernel when shapes
  allow (paddle_tpu.ops.flash_attention), XLA composite otherwise;
- `enable_recompute()` wraps every block in jax.checkpoint (remat), the
  strategy.recompute hook the trainer calls;
- static shapes everywhere; position ids are an iota baked at trace time.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field, replace
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer_base import Layer, ParamAttr
from ..nn.layer.common import Dropout, Embedding
from ..nn.layer.norm import LayerNorm
from ..nn.layer.container import LayerList
from ..tensor.manipulation import repeat_interleave
from ..tensor.math import matmul
from ..distributed.parallel_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    mark_sharding)
from ..distributed.mesh import PartitionSpec
from ..distributed.recompute import RecomputeWrapper

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM",
           "GPTPretrainingCriterion", "gpt_configs", "StaticKVCache"]


def window_positions(lengths, w: int):
    """Positions of the W tokens a step appends per slot: ``lengths``
    itself for one token a slot (``[B]``: the decode tick adds no
    window offsets, so its program stays what it was, operation for
    operation), ``lengths[b] + i`` as ``[B, W]`` for a window."""
    if w == 1:
        return lengths
    return lengths[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]


def kv_tick_reads(active, slot_len, window: int) -> dict:
    """The ``tick`` span's arguments for a cache of rows:
    ``kv_positions``, the cached positions the tick's attention has to
    read: the active slots' lengths with the `window` new tokens."""
    return {"kv_positions": int(np.dot(active, slot_len)) +
            window * int(np.sum(active))}


def kv_step_bytes(layers: int, kv_heads: int, head_dim: int, dtype,
                  quantized: bool, positions: int, tp: int = 1) -> int:
    """Bytes of k and v a decode step streams for one slot holding
    `positions` rows: 8-bit codes count with their f32 scale planes.  KV
    heads split over ``tp`` only when they divide evenly (the sharding
    helpers replicate otherwise)."""
    hkv = kv_heads // tp if kv_heads % tp == 0 else kv_heads
    kv = 2 * layers * positions * hkv * head_dim * jnp.dtype(dtype).itemsize
    if quantized:
        kv += 2 * layers * positions * hkv * 4
    return kv


@dataclass
class KVLayerView:
    """One layer of a serving KV cache, as a serving step sees it: the
    layer's buffers behind ONE operation, ``write_attend(q, k, v,
    lengths)``: write W tokens, then attend their queries.  Its default
    is the two halves one after the other.

    ``write(k, v, lengths)`` stores W new tokens' k and v
    ``[B, W, Hkv, D]`` for every slot at positions ``lengths[b] ..
    lengths[b]+W-1`` and returns the written view; ``attend(q)`` runs
    that window's queries ``[B, W, H, D]`` against it, query i seeing
    positions ``j <= lengths[b]+i``.  What the view hides: how a
    position becomes an address (``_locate`` / ``_put``: an index into a
    dense buffer, or a block and an offset through a block table), and
    how a value is stored (as it is, or as 8-bit codes beside f32 scale
    planes ``k_scale`` / ``v_scale``, quantized on the write and
    dequantized inside the attention kernel).

    W is a static shape and picks the kernel: one token a slot goes
    through the single-token entry points with a ``[B]`` index, a
    window through the window entry points with a ``[B, W]`` index.
    The two are not interchangeable on the chip until measured so: the
    shape of the write's scatter decides whether the compiler updates a
    donated buffer where it lies (PERF.md §6, PR 26)."""

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None
    # (lengths, address) of the last write, for its queries' attend
    window: Optional[tuple] = None

    def write_attend(self, q, k, v, lengths):
        """``(out [B, W, H, D], the written view)``; the write under the
        scope ``kv_write``, the attention under ``decode_attn``."""
        with jax.named_scope("kv_write"):
            kv = self.write(k, v, lengths)
        with jax.named_scope("decode_attn"):
            return kv.attend(q), kv

    def write(self, k, v, lengths) -> "KVLayerView":
        w = k.shape[1]
        lens = lengths.astype(jnp.int32)
        at = self._locate(window_positions(lens, w))
        new = (lambda x: x[:, 0]) if w == 1 else (lambda x: x)
        if self.k_scale is None:
            k_buf = self._put(self.k, at, new(k))
            v_buf = self._put(self.v, at, new(v))
            return replace(self, k=k_buf, v=v_buf, window=(lens, at))
        from ..ops.quantized_matmul import kv_quant_mode, quantize_kv
        mode = kv_quant_mode(self.k.dtype)
        kq, ks = quantize_kv(new(k), mode)      # scales [B, (W,) Hkv]
        vq, vs = quantize_kv(new(v), mode)
        return replace(
            self, k=self._put(self.k, at, kq), v=self._put(self.v, at, vq),
            k_scale=self._put(self.k_scale, at, ks),
            v_scale=self._put(self.v_scale, at, vs), window=(lens, at))

    def attend(self, q):
        """The queries of the window just written; ``[B, W, H, D]``."""
        lens, at = self.window
        token = q.shape[1] == 1
        if token:
            q = q[:, 0]
        if self.k_scale is None:
            # the kernels take storage-dtype MXU inputs; 8-bit codes
            # are dequantized to the query's dtype instead
            q = q.astype(self.k.dtype)
        if token:
            return self._attend_token(q, lens, at)[:, None]
        return self._attend_window(q, lens)


class DenseKVLayer(KVLayerView):
    """A StaticKVCache layer: ``k``/``v`` ``[B, Hkv, cap, D]``, scale
    planes ``[B, Hkv, cap]``.  A position is its own address, clamped
    to the buffer's last (a slot at capacity writes masked garbage
    there; callers bound generation, as the engine does)."""

    def _locate(self, pos):
        return jnp.minimum(pos, self.k.shape[2] - 1)

    def _put(self, buf, idx, new):
        from ..ops import write_kv
        return write_kv(buf, idx, new)

    def write_attend(self, q, k, v, lengths):
        """One token a slot into a cache without scale planes, where the
        decode kernel runs: the kernel stores the token's k and v itself
        and the step holds no scatter.  Everything else (a window, 8-bit
        codes, off the chip) writes and then attends."""
        from ..ops import decode_attention_writes, write_decode_attention
        if k.shape[1] != 1 or self.k_scale is not None or \
                not decode_attention_writes(q[:, 0], self.k):
            return super().write_attend(q, k, v, lengths)
        lens = lengths.astype(jnp.int32)
        at = self._locate(lens)
        with jax.named_scope("decode_attn"):
            out, k_buf, v_buf = write_decode_attention(
                q[:, 0].astype(self.k.dtype), k[:, 0], v[:, 0], self.k,
                self.v, at)
        return out[:, None], replace(self, k=k_buf, v=v_buf,
                                     window=(lens, at))

    def _attend_token(self, q, lens, idx):
        from ..ops import decode_attention
        return decode_attention(q, self.k, self.v, idx + 1,
                                self.k_scale, self.v_scale)

    def _attend_window(self, q, lens):
        from ..ops import decode_attention_window
        return decode_attention_window(q, self.k, self.v, lens,
                                       self.k_scale, self.v_scale)


class StaticKVCache:
    """Preallocated serving KV cache: ``k``/``v`` are TUPLES of one
    buffer per layer, each HEAD-MAJOR
    ``[batch_slots, kv_heads, max_seq, head_dim]``, and ``lengths`` is
    ``[batch_slots]`` int32 — valid tokens per slot.

    One buffer per layer, because a step reads and writes layer ``i``
    only inside layer ``i``: handed in donated, each buffer is updated
    where it lies (a decode tick writes ``batch_slots × kv_heads ×
    head_dim`` elements a layer and nothing else) and no layer is ever
    sliced out of, or written back into, a stacked array.  Head-major,
    because the decode kernels stream one (slot, kv head)'s
    ``[max_seq, head_dim]`` strip at a time: those are the buffer's two
    minor dimensions, so the kernels' ``[B·Hkv, S, D]`` view is a
    reshape, not a transpose (the paged pool is head-major for the same
    reason).

    Statically shaped on purpose (Pope et al., *Efficiently Scaling
    Transformer Inference*): every prefill/decode executable sees the
    same cache shapes, so generating N tokens never changes a shape and
    never recompiles.  All updates are functional (`lax.dynamic_update_
    slice` / scatter); under jit with donated cache operands XLA turns
    them into true in-place writes.  Registered as a pytree so it rides
    through jit/scan/while_loop carries.

    Quantized form (``kv_dtype='int8'``/``'fp8'`` in init_kv_cache):
    ``k``/``v`` hold 8-bit values and ``k_scale``/``v_scale`` the
    per-(position, head) f32 scale planes, tuples of
    ``[batch_slots, kv_heads, max_seq]`` per layer — decode streams half
    the bytes and dequantizes inside the fused attention kernel.  The
    fp cache (``k_scale is None``) stays the default; shapes are static
    either way, so the zero-recompile contract is unchanged.
    """

    __slots__ = ("k", "v", "lengths", "k_scale", "v_scale")

    def __init__(self, k, v, lengths, k_scale=None, v_scale=None):
        self.k, self.v, self.lengths = k, v, lengths
        self.k_scale, self.v_scale = k_scale, v_scale

    @property
    def num_layers(self):
        return len(self.k)

    @property
    def batch_slots(self):
        return self.k[0].shape[0]

    @property
    def kv_heads(self):
        return self.k[0].shape[1]

    @property
    def capacity(self):
        return self.k[0].shape[2]

    @property
    def dtype(self):
        return self.k[0].dtype

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    tick_reads = staticmethod(kv_tick_reads)

    def step_bytes_per_slot(self, positions: int, tp: int = 1) -> int:
        return kv_step_bytes(self.num_layers, self.kv_heads,
                             self.k[0].shape[3], self.dtype, self.quantized,
                             positions, tp)

    def with_lengths(self, lengths) -> "StaticKVCache":
        """The same buffers under new per-slot lengths."""
        return StaticKVCache(self.k, self.v, lengths, self.k_scale,
                             self.v_scale)

    def layer(self, i, tables=None) -> DenseKVLayer:
        """Layer ``i``'s own buffers, as a serving step's view."""
        scales = (self.k_scale[i], self.v_scale[i]) if self.quantized \
            else ()
        return DenseKVLayer(self.k[i], self.v[i], *scales)

    def with_layer(self, i, kv: DenseKVLayer) -> "StaticKVCache":
        """The cache with layer ``i``'s buffers taken back from a view:
        no layer is sliced out of, or written back into, anything
        larger."""
        def put(planes, new):
            if planes is None:
                return None
            return planes[:i] + (new,) + planes[i + 1:]
        return StaticKVCache(put(self.k, kv.k), put(self.v, kv.v),
                             self.lengths, put(self.k_scale, kv.k_scale),
                             put(self.v_scale, kv.v_scale))

    def __repr__(self):
        return (f"StaticKVCache(layers={self.num_layers}, "
                f"slots={self.batch_slots}, capacity={self.capacity}, "
                f"kv_heads={self.kv_heads}, dtype={self.dtype}"
                f"{', quantized' if self.quantized else ''})")


jax.tree_util.register_pytree_node(
    StaticKVCache,
    lambda c: ((c.k, c.v, c.lengths, c.k_scale, c.v_scale), None),
    lambda aux, ch: StaticKVCache(*ch))


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None  # GQA; None -> MHA
    ffn_hidden_size: Optional[int] = None  # None -> 4*hidden
    max_seq_len: int = 1024
    dropout: float = 0.0
    attn_dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    use_flash_attention: bool = True
    tie_word_embeddings: bool = True
    # fused LM loss: during training the model returns (hidden, wte) so
    # the criterion can run the blocked cross-entropy over vocab chunks
    # (ops.fused_cross_entropy) — the [B, S, V] logits tensor is never
    # materialized. Requires tie_word_embeddings.
    fused_ce: bool = False
    # AQT-style quantized compute: 'int8' (or 'fp8' where this jax has
    # float8) routes every block linear (qkv/out/up/down projections)
    # through ops.fake_quant_matmul — quantized forward, straight-
    # through backward — so training sees (and adapts to) quantization
    # noise while optimizer/params stay fp32/bf16.  Embeddings and the
    # LM head stay full precision (the standard sensitivity split).
    # None (default) keeps every path bitwise-identical to unquantized.
    quantize: Optional[str] = None
    tp_axis: str = "tp"
    # MoE (0 experts = dense; BASELINE.json config #5 switch-transformer).
    # These blocks take distributed.moe.MoELayer's CAPACITY path: softmax
    # top-k over all experts with a capacity factor, tokens over an
    # expert's capacity dropped, biased gelu experts, every expert held
    # (sharded over 'ep').  The same layer class has a DROPLESS path
    # beside it (capacity_factor=None: sigmoid scores with a correction
    # bias, no token dropped, a held_experts share through
    # ops.grouped_matmul), which models/nemotron_h.py uses; GPTConfig
    # does not reach it.
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    moe_every_n_layers: int = 1   # every Nth block is MoE
    moe_aux_loss_coeff: float = 0.01
    moe_z_loss_coeff: float = 0.0
    ep_axis: str = "ep"
    # sequence/context parallelism: ring attention over the 'sp' axis
    sequence_parallel: bool = False
    sp_axis: str = "sp"

    def __post_init__(self):
        if self.ffn_hidden_size is None:
            self.ffn_hidden_size = 4 * self.hidden_size
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.quantize is not None:
            from ..ops.quantized_matmul import _check_mode
            _check_mode(self.quantize)
            if self.moe_num_experts > 0:
                # the expert FFNs are raw einsums (distributed.moe), not
                # parallel linears — they would silently stay full
                # precision while the config said quantize='int8'
                raise NotImplementedError(
                    f"quantize={self.quantize!r} COMPUTE with MoE is "
                    f"not supported: expert FFN matmuls (the dominant "
                    f"MoE FLOPs) have no quantized path yet, and "
                    f"quantizing only attention would misattribute the "
                    f"measured MFU. Quantized KV CACHES are orthogonal "
                    f"and do work with MoE engines — pass "
                    f"kv_dtype='int8' to InferenceEngine/init_kv_cache "
                    f"instead")

    def is_moe_layer(self, layer_idx: int) -> bool:
        return (self.moe_num_experts > 0 and
                (layer_idx + 1) % self.moe_every_n_layers == 0)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    def num_params(self, include_embeddings=True):
        h, l, v = self.hidden_size, self.num_layers, self.vocab_size
        # qkv (h*(h+2*kv)) + out (h*h) + mlp (2*h*ffn) + biases/norms
        kv_dim = self.num_kv_heads * self.head_dim
        per_block = h * (h + 2 * kv_dim) + h * h + \
            2 * h * self.ffn_hidden_size + 13 * h
        total = l * per_block + 2 * h  # final norm
        if include_embeddings:
            total += v * h + self.max_seq_len * h
        return int(total)

    def flops_per_token(self, seq_len=None):
        """Model FLOPs per token (fwd+bwd, 6N + attention quadratic
        term)."""
        s = seq_len or self.max_seq_len
        n = self.num_params(include_embeddings=False)
        return 6 * n + 12 * self.num_layers * self.hidden_size * s


def gpt_configs():
    """Named configs; 1.3b/13b are the BASELINE.json targets."""
    return {
        "gpt3-tiny": GPTConfig(vocab_size=512, hidden_size=128,
                               num_layers=2, num_heads=4, max_seq_len=256),
        "gpt3-125m": GPTConfig(hidden_size=768, num_layers=12,
                               num_heads=12, max_seq_len=2048),
        "gpt3-350m": GPTConfig(hidden_size=1024, num_layers=24,
                               num_heads=16, max_seq_len=2048),
        "gpt3-1.3b": GPTConfig(hidden_size=2048, num_layers=24,
                               num_heads=16, max_seq_len=2048),
        "gpt3-6.7b": GPTConfig(hidden_size=4096, num_layers=32,
                               num_heads=32, max_seq_len=2048),
        "gpt3-13b": GPTConfig(hidden_size=5120, num_layers=40,
                              num_heads=40, max_seq_len=2048),
    }


class GPTAttention(Layer):
    """Causal self-attention, Megatron-sharded: fused qkv column-parallel
    (heads shard over tp), output row-parallel (one all-reduce)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.cfg = config
        h = config.hidden_size
        kv_dim = config.num_kv_heads * config.head_dim
        init = ParamAttr(initializer=I.Normal(0.0, config.initializer_range))
        self.qkv_proj = ColumnParallelLinear(
            h, h + 2 * kv_dim, weight_attr=init, has_bias=True,
            gather_output=False, axis_name=config.tp_axis,
            quantize=config.quantize)
        self.out_proj = RowParallelLinear(
            h, h, weight_attr=init, has_bias=True, input_is_parallel=True,
            axis_name=config.tp_axis, quantize=config.quantize)
        self.dropout = Dropout(config.dropout)

    def _sp_active(self, b, s) -> bool:
        """True when an ambient mesh (bound by the compiled trainer while
        tracing) carries a real 'sp' axis AND the shapes divide evenly —
        ragged batches fall back to dense attention instead of crashing
        the shard_map."""
        from ..distributed.mesh import get_mesh
        m = get_mesh()
        if (m is None or self.cfg.sp_axis not in m.axis_names or
                m.shape[self.cfg.sp_axis] <= 1):
            return False
        if s % m.shape[self.cfg.sp_axis]:
            return False
        dp = m.shape.get("dp", 1) if "dp" in m.axis_names else 1
        return b % dp == 0

    def _qkv_arrays(self, x):
        """qkv projection split into raw arrays q [B,S,H,D],
        k/v [B,S,Hkv,D].  Inference-path helper: reading ``.data``
        detaches from the eager autograd tape, which is why
        ``forward`` keeps its own Tensor-level split (training grads
        flow through the tape there)."""
        cfg = self.cfg
        b, s = x.shape[0], x.shape[1]
        with jax.named_scope("attn_proj"):
            qkv = self.qkv_proj(x if isinstance(x, Tensor) else Tensor(x))
            arr = qkv.data
            h_dim = cfg.hidden_size
            kv_dim = cfg.num_kv_heads * cfg.head_dim
            q = arr[:, :, :h_dim].reshape(b, s, cfg.num_heads,
                                          cfg.head_dim)
            k = arr[:, :, h_dim:h_dim + kv_dim].reshape(
                b, s, cfg.num_kv_heads, cfg.head_dim)
            v = arr[:, :, h_dim + kv_dim:].reshape(
                b, s, cfg.num_kv_heads, cfg.head_dim)
        return q, k, v

    def _proj_out(self, out_arr, b, s):
        with jax.named_scope("attn_proj"):
            out = Tensor(out_arr.reshape(b, s, -1))
            return self.dropout(self.out_proj(out))

    @staticmethod
    def _upgrade_cache(cache, b, hkv, d, cap, dtype):
        """Adopt any accepted cache form into the fixed-capacity triple
        ``(k_buf [B, Hkv, cap, D], v_buf, length)`` — head-major, the
        StaticKVCache layer layout.

        Accepted: the triple itself; the legacy 2-tuple ``(pk, pv)`` of
        dense past keys/values ``[B, past, Hkv, D]`` (padded into a
        fresh buffer — its static `past` length stays static, so
        adopting is compile-stable); and
        ``(None, None)`` / empty to start a fresh buffer.  The fixed
        capacity is what kills the per-token recompile: the old concat
        path changed the cache shape every generated token, forcing XLA
        to recompile each step and copy O(n²) bytes.
        """
        if len(cache) == 3:
            k_buf, v_buf, length = cache
            k_buf = k_buf.data if isinstance(k_buf, Tensor) else k_buf
            v_buf = v_buf.data if isinstance(v_buf, Tensor) else v_buf
            return k_buf, v_buf, length
        pk, pv = cache
        k_buf = jnp.zeros((b, hkv, cap, d), dtype)
        v_buf = jnp.zeros((b, hkv, cap, d), dtype)
        if pk is None:
            return k_buf, v_buf, 0
        pk = pk.data if isinstance(pk, Tensor) else jnp.asarray(pk)
        pv = pv.data if isinstance(pv, Tensor) else jnp.asarray(pv)
        k_buf = jax.lax.dynamic_update_slice(
            k_buf, jnp.swapaxes(pk, 1, 2).astype(dtype), (0, 0, 0, 0))
        v_buf = jax.lax.dynamic_update_slice(
            v_buf, jnp.swapaxes(pv, 1, 2).astype(dtype), (0, 0, 0, 0))
        return k_buf, v_buf, int(pk.shape[1])

    def _attend_fresh(self, q, k, v, b, s):
        """No-past causal attention on raw arrays — the same
        ring/flash/composite routing as the no-cache forward, shared by
        forward_prefill and the fresh-cache legacy path.  Returns raw
        [b, s, H, D]."""
        with jax.named_scope("attn_core"):
            cfg = self.cfg
            causal = s > 1
            if cfg.sequence_parallel and self._sp_active(b, s):
                from ..distributed.ring_attention import \
                    sequence_parallel_attention
                out = sequence_parallel_attention(
                    Tensor(q), Tensor(k), Tensor(v), sp_axis=cfg.sp_axis,
                    causal=causal)
                return out.data if isinstance(out, Tensor) else out
            if cfg.use_flash_attention:
                return F.flash_attention(Tensor(q), Tensor(k), Tensor(v),
                                         causal=causal, training=False).data
            kf, vf = k, v
            if cfg.num_kv_heads != cfg.num_heads:
                rep = cfg.num_heads // cfg.num_kv_heads
                kf = jnp.repeat(kf, rep, axis=2)
                vf = jnp.repeat(vf, rep, axis=2)
            return F.scaled_dot_product_attention(
                Tensor(q), Tensor(kf), Tensor(vf), is_causal=causal,
                training=False).data

    def _forward_with_cache(self, x, cache):
        """Fixed-capacity cached attention (the legacy ``cache=`` path,
        now recompile-free): write the s new tokens at ``length``, attend
        query i (absolute position length+i) against buffer keys
        ``j <= length + i``.  Single-token calls run the fused decode
        kernel (ops.decode_attention); a fresh cache's multi-token
        prefill keeps the ring/flash fast path.  Returns
        ``(out, (k_buf, v_buf, new_length))``.

        The buffer capacity is ``cfg.max_seq_len``; exceeding it raises
        in eager use (concrete length).  Under jit the length is traced
        and cannot be checked — writes past capacity clamp to the last
        position (callers must bound generation, as the engine does)."""
        cfg = self.cfg
        b, s = x.shape[0], x.shape[1]
        cap = cfg.max_seq_len
        q, k, v = self._qkv_arrays(x)
        k_buf, v_buf, length = self._upgrade_cache(
            cache, b, cfg.num_kv_heads, cfg.head_dim, cap, q.dtype)
        try:
            concrete_len = int(length)
        except Exception:  # traced inside jit/scan: unverifiable
            concrete_len = None
        if concrete_len is not None and concrete_len + s > cap:
            raise ValueError(
                f"kv cache overflow: {concrete_len} cached + {s} new "
                f"tokens > capacity {cap} (cfg.max_seq_len) — the old "
                f"concat cache grew past this silently; the static "
                f"cache cannot")
        # same offset for every row (the legacy API is uniform-length;
        # per-slot offsets live in StaticKVCache/step)
        k_buf = jax.lax.dynamic_update_slice(
            k_buf, jnp.swapaxes(k, 1, 2).astype(k_buf.dtype),
            (0, 0, length, 0))
        v_buf = jax.lax.dynamic_update_slice(
            v_buf, jnp.swapaxes(v, 1, 2).astype(v_buf.dtype),
            (0, 0, length, 0))
        new_len = length + s
        if s == 1:
            from .. import ops as _ops
            lens = jnp.broadcast_to(
                jnp.asarray(new_len, jnp.int32), (b,))
            out = _ops.decode_attention(
                q[:, 0].astype(k_buf.dtype), k_buf, v_buf, lens)
            out = out[:, None].astype(q.dtype)          # [b, 1, H, D]
        elif concrete_len == 0:
            # fresh-cache prefill: nothing valid in the buffer yet, so
            # this IS plain causal attention — keep the ring/flash path
            # instead of a [s, cap] masked composite
            out = self._attend_fresh(q, k, v, b, s)
        else:
            # multi-token continuation of a non-empty cache: rare, and
            # the one place the buffer is read position-major
            kf, vf = jnp.swapaxes(k_buf, 1, 2), jnp.swapaxes(v_buf, 1, 2)
            if cfg.num_kv_heads != cfg.num_heads:
                rep = cfg.num_heads // cfg.num_kv_heads
                kf = jnp.repeat(kf, rep, axis=2)
                vf = jnp.repeat(vf, rep, axis=2)
            # bool mask [1, 1, s, cap]: query i sees keys j <= length+i
            mask = (jnp.arange(cap)[None, :] <=
                    (jnp.asarray(length) + jnp.arange(s))[:, None])
            out = F.scaled_dot_product_attention(
                Tensor(q), Tensor(kf.astype(q.dtype)),
                Tensor(vf.astype(q.dtype)),
                attn_mask=mask[None, None], training=False).data
        out_t = self._proj_out(out, b, s)
        return out_t, (k_buf, v_buf, new_len)

    def forward_prefill(self, x):
        """Causal attention over a fresh prompt, also returning the
        prompt's k/v HEAD-MAJOR so the caller can write them into a
        StaticKVCache slot.  Returns ``(out, k [B,Hkv,S,D], v)``.  The
        flash path makes the same transpose of the same arrays for its
        own strips, so inside one jitted prefill XLA keeps one."""
        b, s = x.shape[0], x.shape[1]
        q, k, v = self._qkv_arrays(x)
        out = self._proj_out(self._attend_fresh(q, k, v, b, s), b, s)
        with jax.named_scope("attn_core"):
            return out, jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)

    def step(self, x, kv, lengths):
        """One serving step over one cache layer, for a decode tick
        (W = 1), a spec-decode verify window or a prefill chunk: write
        the W new tokens' k/v for every slot at positions
        ``lengths[b] .. lengths[b]+W-1`` (in place when the layer's
        buffers are donated), then attend query i against positions
        ``j <= lengths[b]+i``.  x is ``[B, W, hidden]``; ``kv`` the
        cache's view of this layer (a ``KVLayerView``: where a position
        lives and how a value is stored are its business); lengths
        ``[B]`` int32, the tokens already in the cache, EXCLUDING the
        window.  Rows
        past a slot's real tokens write masked garbage above its
        length, overwritten by the next step.  Returns
        ``(out, kv)``."""
        b, w = x.shape[0], x.shape[1]
        q, k, v = self._qkv_arrays(x)
        out, kv = kv.write_attend(q, k, v, lengths)     # [b, w, H, D]
        return self._proj_out(out.astype(q.dtype), b, w), kv

    def forward_prefill_paged(self, x, k_buf, v_buf, prefix_len):
        """Prefill attention over ONE slot's gathered block buffer:
        ``k_buf``/``v_buf`` are the slot's blocks laid out contiguously
        ``[cap_row, Hkv, D]`` (cap_row = max_blocks·block_size) with
        ``prefix_len`` tokens already valid (a radix-cache hit; 0 =
        cold).  Writes the s new k/v at ``prefix_len`` and attends
        suffix query i (absolute position prefix_len+i) against buffer
        keys ``j <= prefix_len + i``.

        ``prefix_len`` may be a PYTHON INT 0 — the engine compiles that
        as its own executable so the cold path keeps the exact
        ring/flash/composite attention of the dense prefill (bitwise
        parity with the dense engine); a traced prefix_len takes the
        masked composite over the whole buffer.  Returns
        ``(out, k_buf, v_buf)``."""
        cfg = self.cfg
        b, s = x.shape[0], x.shape[1]
        q, k, v = self._qkv_arrays(x)
        static_cold = isinstance(prefix_len, int) and prefix_len == 0
        off = jnp.asarray(prefix_len, jnp.int32)
        zero = jnp.asarray(0, jnp.int32)
        k_buf = jax.lax.dynamic_update_slice(
            k_buf, k[0].astype(k_buf.dtype), (off, zero, zero))
        v_buf = jax.lax.dynamic_update_slice(
            v_buf, v[0].astype(v_buf.dtype), (off, zero, zero))
        if static_cold:
            out = self._attend_fresh(q, k, v, b, s)
        else:
            cap = k_buf.shape[0]
            kf, vf = k_buf[None], v_buf[None]       # [1, cap, Hkv, D]
            if cfg.num_kv_heads != cfg.num_heads:
                rep = cfg.num_heads // cfg.num_kv_heads
                kf = jnp.repeat(kf, rep, axis=2)
                vf = jnp.repeat(vf, rep, axis=2)
            # query i sees buffer keys j <= prefix_len + i
            mask = (jnp.arange(cap)[None, :] <=
                    (off + jnp.arange(s))[:, None])
            out = F.scaled_dot_product_attention(
                Tensor(q), Tensor(kf.astype(q.dtype)),
                Tensor(vf.astype(q.dtype)),
                attn_mask=mask[None, None], training=False).data
        return self._proj_out(out, b, s), k_buf, v_buf

    def forward(self, x, attn_mask=None, cache=None):
        cfg = self.cfg
        b = x.shape[0]
        s = x.shape[1]
        if cache is not None:
            # generation path: fixed-capacity cache, static shapes (the
            # old concat-grown cache recompiled every generated token)
            if attn_mask is not None:
                raise NotImplementedError(
                    "attn_mask with a kv cache is not supported; pad "
                    "tokens are masked by the cache length instead")
            return self._forward_with_cache(x, cache)
        with jax.named_scope("attn_proj"):
            qkv = self.qkv_proj(x)
            h_dim = cfg.hidden_size
            kv_dim = cfg.num_kv_heads * cfg.head_dim
            q = qkv[:, :, :h_dim].reshape(
                [b, s, cfg.num_heads, cfg.head_dim])
            k = qkv[:, :, h_dim:h_dim + kv_dim].reshape(
                [b, s, cfg.num_kv_heads, cfg.head_dim])
            v = qkv[:, :, h_dim + kv_dim:].reshape(
                [b, s, cfg.num_kv_heads, cfg.head_dim])
        with jax.named_scope("attn_core"):
            out = self._attend(q, k, v, attn_mask, b, s)
        with jax.named_scope("attn_proj"):
            return self.dropout(self.out_proj(out))

    def _attend(self, q, k, v, attn_mask, b, s):
        """The training forward's attention between its two projections:
        ring, flash or composite; returns ``[b, s, H D]``."""
        cfg = self.cfg
        causal = s > 1
        if (cfg.sequence_parallel and attn_mask is None
                and self._sp_active(b, s)):
            # ring attention: seq dim sharded over 'sp', KV blocks rotate
            # around the ICI ring (distributed/ring_attention.py). K/V go
            # in UN-expanded (GQA): the ring rotates Hkv heads, not H.
            from ..distributed.ring_attention import \
                sequence_parallel_attention
            if cfg.attn_dropout and self.training:
                raise NotImplementedError(
                    "attn_dropout inside ring attention is not supported")
            out = sequence_parallel_attention(
                q, k, v, sp_axis=cfg.sp_axis, causal=causal)
            return out.reshape([b, s, -1])

        if cfg.use_flash_attention and attn_mask is None:
            # GQA goes in un-expanded: the Pallas kernel walks kv-head
            # groups on its grid, never materializing repeated K/V
            out = F.flash_attention(q, k, v, dropout=cfg.attn_dropout,
                                    causal=causal,
                                    training=self.training)
        else:
            if cfg.num_kv_heads != cfg.num_heads:
                rep = cfg.num_heads // cfg.num_kv_heads
                k = repeat_interleave(k, rep, axis=2)
                v = repeat_interleave(v, rep, axis=2)
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask,
                dropout_p=cfg.attn_dropout, is_causal=causal,
                training=self.training)
        return out.reshape([b, s, -1])


class GPTMLP(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        init = ParamAttr(initializer=I.Normal(0.0, config.initializer_range))
        out_init = ParamAttr(initializer=I.Normal(
            0.0, config.initializer_range / math.sqrt(
                2.0 * config.num_layers)))
        self.up_proj = ColumnParallelLinear(
            config.hidden_size, config.ffn_hidden_size, weight_attr=init,
            gather_output=False, axis_name=config.tp_axis,
            quantize=config.quantize)
        self.down_proj = RowParallelLinear(
            config.ffn_hidden_size, config.hidden_size,
            weight_attr=out_init, input_is_parallel=True,
            axis_name=config.tp_axis, quantize=config.quantize)
        self.dropout = Dropout(config.dropout)

    def forward(self, x):
        return self.dropout(self.down_proj(F.gelu(self.up_proj(x),
                                                  approximate=True)))


class GPTBlock(Layer):
    """Pre-LN decoder block (GPT-2/3 style). When the config marks this
    layer index as MoE the dense MLP is replaced by an expert-parallel
    MoELayer (switch-transformer block; BASELINE.json config #5)."""

    def __init__(self, config: GPTConfig, layer_idx: int = 0):
        super().__init__()
        self.ln_1 = LayerNorm(config.hidden_size,
                              epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = LayerNorm(config.hidden_size,
                              epsilon=config.layer_norm_epsilon)
        if config.is_moe_layer(layer_idx):
            from ..distributed.moe import MoELayer
            self.mlp = MoELayer(
                config.hidden_size, config.ffn_hidden_size,
                num_experts=config.moe_num_experts,
                top_k=config.moe_top_k,
                capacity_factor=config.moe_capacity_factor,
                aux_loss_coeff=config.moe_aux_loss_coeff,
                z_loss_coeff=config.moe_z_loss_coeff,
                ep_axis=config.ep_axis,
                weight_attr=ParamAttr(initializer=I.Normal(
                    0.0, config.initializer_range)),
                # depth-scaled residual-out init, same as GPTMLP.down_proj
                down_weight_attr=ParamAttr(initializer=I.Normal(
                    0.0, config.initializer_range / math.sqrt(
                        2.0 * config.num_layers))))
        else:
            self.mlp = GPTMLP(config)

    # A block's two scopes each hold the pre-norm, the sublayer and the
    # residual add, so that no operation falls between blocks.
    def _mlp_block(self, x):
        with jax.named_scope("mlp"):
            return x + self.mlp(self.ln_2(x))

    def forward(self, x, attn_mask=None):
        with jax.named_scope("attn"):
            x = x + self.attn(self.ln_1(x), attn_mask=attn_mask)
        return self._mlp_block(x)

    def forward_prefill(self, x):
        """Block forward that also surfaces this layer's k/v for the
        StaticKVCache write. Returns (x, k [B,Hkv,S,D], v)."""
        with jax.named_scope("attn"):
            a, k, v = self.attn.forward_prefill(self.ln_1(x))
            x = x + a
        return self._mlp_block(x), k, v

    def step(self, x, kv, lengths):
        """Block step over one cache layer's view (LN/MLP are
        position-wise, so only attention needs the window machinery).
        Returns ``(x, kv)``."""
        with jax.named_scope("attn"):
            a, kv = self.attn.step(self.ln_1(x), kv, lengths)
            x = x + a
        return self._mlp_block(x), kv

    def forward_prefill_paged(self, x, k_buf, v_buf, prefix_len):
        """Block prefill over one slot's gathered block buffer."""
        with jax.named_scope("attn"):
            a, k_buf, v_buf = self.attn.forward_prefill_paged(
                self.ln_1(x), k_buf, v_buf, prefix_len)
            x = x + a
        return self._mlp_block(x), k_buf, v_buf


class GPTModel(Layer):
    """Embeddings + N blocks + final norm. Returns hidden states."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.cfg = config
        self.wte = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size,
            weight_attr=ParamAttr(initializer=I.Normal(
                0.0, config.initializer_range)),
            axis_name=config.tp_axis)
        self.wpe = Embedding(config.max_seq_len, config.hidden_size,
                             weight_attr=ParamAttr(initializer=I.Normal(
                                 0.0, config.initializer_range)))
        self.drop = Dropout(config.dropout)
        self.blocks = LayerList([GPTBlock(config, layer_idx=i)
                                 for i in range(config.num_layers)])
        self.ln_f = LayerNorm(config.hidden_size,
                              epsilon=config.layer_norm_epsilon)
        self._recompute = False
        self._scan_layers = False
        self._zero3_axis = None

    def enable_recompute(self, policy=None):
        """strategy.recompute hook: remat every block. Applied in
        forward() (not by re-wrapping sublayers) so parameter names —
        and therefore state dicts/checkpoints — are unchanged.

        policy: a `distributed.recompute.checkpoint_policy` name.
        'dots' and 'dots_no_batch' keep the products' outputs (the
        matmuls' and, under the names its forward rule gives them, the
        flash attention kernel's output and log-sum-exp) and recompute
        only the cheap elementwise ops between them: no product and no
        kernel runs twice.  None or 'full' recomputes everything."""
        self._recompute = True
        self._recompute_policy = policy
        return self

    def enable_scan_layers(self, flag: bool = True):
        """Run the block stack as ONE jax.lax.scan over per-layer
        stacked parameters instead of a Python loop: the transformer
        body is traced (and XLA-compiled) once regardless of depth, so
        compile time drops from O(layers) to O(1) traced bodies, and
        per-iteration jax.checkpoint gives per-layer remat under the
        active recompute policy. Parameters stay per-layer Tensors
        (state dicts/checkpoints unchanged); the stacking happens at
        trace time. Falls back to the unrolled loop when the stack is
        not scannable (MoE blocks, live dropout, attention masks)."""
        self._scan_layers = bool(flag)
        return self

    def enable_zero3_overlap(self, axis: str = "dp"):
        """ZeRO-3 latency-hiding hook (SpmdTrainer sharding stage 3 +
        scan_layers): the layer scan runs under shard_map over `axis`
        with layer i+1's params all-gathered while layer i computes, and
        block grads leave the backward reduce-scattered (see
        distributed.zero3).  Per-trace preconditions (a dp>1 compile
        mesh, dp-divisible batch, no tensor-parallel specs on block
        params) are re-checked at trace time; when they fail the plain
        scan runs and GSPMD places the stage-3 gathers itself."""
        self._zero3_axis = axis
        return self

    def enable_quantize(self, mode: Optional[str] = "int8"):
        """strategy.qat hook: flip every block linear (qkv/out/up/down)
        onto the fake-quant AQT path (ops.fake_quant_matmul — quantized
        forward, straight-through backward) after construction.  ``None``
        restores the exact unquantized lowering.  Parameter names,
        dtypes and state dicts are untouched — only the forward matmul
        routing changes, so the optimizer never notices."""
        if mode is not None:
            from ..ops.quantized_matmul import _check_mode
            _check_mode(mode)
            if self.cfg.moe_num_experts > 0:
                raise NotImplementedError(
                    "enable_quantize on a MoE model is not supported: "
                    "expert FFN matmuls have no quantized COMPUTE path "
                    "yet (see GPTConfig.quantize). Quantized KV caches "
                    "are orthogonal and do work — pass kv_dtype='int8' "
                    "to InferenceEngine/init_kv_cache instead")
        self.cfg = replace(self.cfg, quantize=mode)
        for blk in self.blocks:
            for lin in (blk.attn.qkv_proj, blk.attn.out_proj):
                lin.quantize = mode
            for name in ("up_proj", "down_proj"):
                lin = getattr(blk.mlp, name, None)
                if lin is not None:
                    lin.quantize = mode
        return self

    def _zero3_mesh(self, x):
        """The compile mesh when the overlapped ZeRO-3 scan can run for
        this trace, else None."""
        if self._zero3_axis is None:
            return None
        from ..distributed.mesh import get_compile_mesh
        from ..distributed.zero3 import zero3_scan_available
        mesh = get_compile_mesh()
        arr = x.data if isinstance(x, Tensor) else x
        if not zero3_scan_available(mesh, self._zero3_axis, arr.shape[0]):
            return None
        # tensor-parallel block params keep the GSPMD path: their tp
        # placement and the manual dp gather would fight over layout
        for _, p in self.blocks[0].named_parameters():
            spec = getattr(p, "pspec", None)
            if spec and any(a in mesh.axis_names and mesh.shape[a] > 1
                            for a in tuple(spec) if a is not None):
                return None
        return mesh

    def _scan_ok(self, attn_mask) -> bool:
        cfg = self.cfg
        if (not self._scan_layers or attn_mask is not None
                or len(self.blocks) < 2):
            return False
        if cfg.moe_num_experts > 0 or cfg.sequence_parallel:
            return False  # heterogeneous blocks / shard_map inside scan
        if self.training and (cfg.dropout > 0 or cfg.attn_dropout > 0):
            return False  # one traced body would share dropout masks
        if any(b is not None for _, b in self.blocks[0].named_buffers()):
            return False
        return True

    def _forward_blocks_scanned(self, x):
        from ..distributed.recompute import checkpoint_policy
        from ..func import functional_call
        blk0 = self.blocks[0]
        names = [n for n, _ in blk0.named_parameters()]
        n_names = len(names)
        n_layers = len(self.blocks)
        flat = [dict(blk.named_parameters())[n]
                for blk in self.blocks for n in names]
        use_remat = self._recompute and self.training
        pol = checkpoint_policy(getattr(self, "_recompute_policy", None)) \
            if use_remat else None
        z3_mesh = self._zero3_mesh(x)

        def scan_fn(h, *flat_arrs):
            stacked = {
                name: jnp.stack([flat_arrs[b * n_names + j]
                                 for b in range(n_layers)])
                for j, name in enumerate(names)}

            if z3_mesh is not None:
                # ZeRO-3 overlapped gather: shard_map over dp with the
                # next layer's all-gather riding under this layer's
                # compute (distributed.zero3)
                from ..distributed.zero3 import scan_layers_zero3

                def call_block(layer_params, carry):
                    out, _ = functional_call(blk0, layer_params, {},
                                             carry)
                    return out

                return scan_layers_zero3(
                    call_block, stacked, h, z3_mesh, self._zero3_axis,
                    use_remat=use_remat, policy=pol)

            def body(carry, layer_params):
                out, _ = functional_call(blk0, layer_params, {}, carry)
                return out, None

            if use_remat:
                # prevent_cse=False: scan bodies don't need the CSE
                # barrier, and it costs performance
                body = jax.checkpoint(body, policy=pol,
                                      prevent_cse=False)
            out, _ = jax.lax.scan(body, h, stacked)
            return out

        from ..core.autograd import apply
        return apply(scan_fn, x, *flat,
                     name="gpt_scan_layers_zero3" if z3_mesh is not None
                     else "gpt_scan_layers")

    def _final_norm(self, x, scope: str):
        """The final norm under the scope of what follows it: ``head_ce``
        in training (head and loss), ``head`` in serving."""
        with jax.named_scope(scope):
            return self.ln_f(x)

    # ---- serving path: static KV cache --------------------------------
    def init_kv_cache(self, batch_slots: int, capacity: Optional[int] = None,
                      dtype=None, kv_dtype=None) -> StaticKVCache:
        """Allocate the fixed-shape serving cache: per layer one k and
        one v buffer ``[batch_slots, kv_heads, capacity, head_dim]``
        (head-major; zeros; per-slot lengths 0), every buffer its own
        array so that each can be donated and updated in place.
        ``capacity`` defaults to max_seq_len; ``dtype`` defaults to the
        embedding dtype.  ``kv_dtype='int8'`` (or ``'fp8'``; default
        from ``PADDLE_TPU_KV_DTYPE``) stores 8-bit values plus
        per-(position, head) f32 scale planes
        ``[batch_slots, kv_heads, capacity]`` — half the decode HBM
        traffic, dequantized inside the fused kernel."""
        from ..ops.quantized_matmul import (kv_storage_dtype,
                                            resolve_kv_quant)
        cfg = self.cfg
        cap = int(capacity or cfg.max_seq_len)
        mode = resolve_kv_quant(kv_dtype)
        dt = kv_storage_dtype(mode) if mode else \
            (dtype or self.wte.weight.dtype)
        shape = (int(batch_slots), cfg.num_kv_heads, cap, cfg.head_dim)

        def per_layer(shp, dtype):
            return tuple(jnp.zeros(shp, dtype)
                         for _ in range(cfg.num_layers))

        scales = (per_layer(shape[:-1], jnp.float32),
                  per_layer(shape[:-1], jnp.float32)) if mode \
            else (None, None)
        return StaticKVCache(per_layer(shape, dt), per_layer(shape, dt),
                             jnp.zeros((int(batch_slots),), jnp.int32),
                             *scales)

    def forward_prefill(self, input_ids, cache: StaticKVCache, slot,
                        prompt_len):
        """Prefill ONE slot: run the causal forward over a (possibly
        padded) prompt ``input_ids [1, s_bucket]``, write every layer's
        k/v ``[1, Hkv, s, D]`` into that layer's buffer at
        ``(slot, 0, 0, 0)``, and set ``lengths[slot] = prompt_len``.
        Tokens past ``prompt_len`` are bucket padding: their k/v land
        beyond the recorded length and are masked out of every later
        decode step.  Returns ``(hidden [1, s, H], cache)``."""
        ids = input_ids.data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        s = ids.shape[1]
        pos = Tensor(jnp.arange(s, dtype=jnp.int32)[None, :])
        with jax.named_scope("embed"):
            x = self.drop(self.wte(Tensor(ids)) + self.wpe(pos))
        slot = jnp.asarray(slot, jnp.int32)
        zero = jnp.asarray(0, jnp.int32)

        def put(buf, new):
            return jax.lax.dynamic_update_slice(
                buf, new.astype(buf.dtype),
                (slot,) + (zero,) * (buf.ndim - 1))

        if cache.quantized:
            from ..ops.quantized_matmul import kv_quant_mode, quantize_kv
            mode = kv_quant_mode(cache.dtype)
        for i, blk in enumerate(self.blocks):
            x, k, v = blk.forward_prefill(x)        # k/v [1, Hkv, s, D]
            new = (k, v)
            if cache.quantized:
                # attention ran on the full-precision k/v (bitwise the
                # dense prefill); only the STORED copy is quantized
                k, k_s = quantize_kv(k, mode)       # scales [1, Hkv, s]
                v, v_s = quantize_kv(v, mode)
                new = (k, v, k_s, v_s)
            kv = cache.layer(i)
            with jax.named_scope("kv_write"):
                cache = cache.with_layer(i, DenseKVLayer(*(
                    put(buf, rows) for buf, rows in
                    zip((kv.k, kv.v, kv.k_scale, kv.v_scale), new))))
        lengths = cache.lengths.at[slot].set(
            jnp.asarray(prompt_len, jnp.int32))
        return self._final_norm(x, "head"), cache.with_lengths(lengths)

    def step(self, tokens, cache, lengths, advance=None, tables=None):
        """One serving step for every slot, W tokens a slot: the decode
        tick (``tokens [B]``, W = 1), the spec-decode verify / draft
        catch-up window and the chunked-prefill step (``tokens
        [B, W]``).  Token i of slot b is embedded at position
        ``lengths[b]+i``; each block is handed the cache's view of its
        layer (``cache.layer(i, tables)``; ``tables [B, MB]`` are the
        slots' block tables where the cache keeps its layers in a block
        pool, None where every slot owns its rows), writes the window's
        k/v there, attends query i against positions
        ``j <= lengths[b]+i``, and the layer is taken back
        (``cache.with_layer``).

        ``lengths [B]`` int32 is the tokens already in the cache,
        EXCLUDING the window: ``cache.lengths``, or the scheduler's own
        mirror (a slot retired between chunks must not leave a stale
        in-graph length behind; a block pool has no in-graph lengths at
        all).  ``advance [B]`` advances the cache's in-graph lengths to
        ``lengths + advance`` (capped at the capacity); None leaves
        them — the spec tick advances by what it commits, a scheduler
        that owns the block accounting advances its own.  Rows
        whose real tokens are fewer than W (retired slots, chunk
        padding) write masked garbage above their new length,
        overwritten by the next step.  Returns
        ``(hidden [B, W, H], cache)``."""
        cfg = self.cfg
        toks = tokens.data if isinstance(tokens, Tensor) \
            else jnp.asarray(tokens)
        lens = jnp.asarray(lengths, jnp.int32)
        b = lens.shape[0]
        w = toks.size // b
        pos = jnp.minimum(window_positions(lens, w), cfg.max_seq_len - 1)
        with jax.named_scope("embed"):
            x = self.drop(self.wte(Tensor(toks.reshape(b, w))) +
                          self.wpe(Tensor(pos.reshape(b, w))))
        if advance is not None:
            cache = cache.with_lengths(jnp.minimum(
                lens + jnp.asarray(advance, jnp.int32), cache.capacity))
        for i, blk in enumerate(self.blocks):
            x, kv = blk.step(x, cache.layer(i, tables), lens)
            cache = cache.with_layer(i, kv)
        return self._final_norm(x, "head"), cache

    # ---- serving path: paged KV cache ---------------------------------
    def forward_prefill_paged(self, input_ids, cache, table_row,
                              prefix_len):
        """Prefill ONE slot over a PAGED cache: ``input_ids [1, s]`` is
        the (bucket-padded) DIVERGENT SUFFIX — tokens ``prefix_len`` of
        the prompt onward; ``table_row [max_blocks]`` int32 maps the
        slot's positions to pool blocks (shared radix-cache blocks for
        the prefix, fresh blocks for the suffix, null block 0 beyond).
        Per layer: gather the slot's blocks contiguous, write the suffix
        k/v at ``prefix_len``, attend, scatter the blocks back.  Pool
        shapes never change, so one executable serves any prefix length
        (``prefix_len`` rides in as a traced scalar; the engine compiles
        the common cold case — a static Python 0 — separately to keep
        the dense prefill's exact attention path).  Returns
        ``(hidden [1, s, H], cache)``."""
        ids = input_ids.data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        cfg = self.cfg
        s = ids.shape[1]
        from ..inference.paged_kv import blocks_to_rows, rows_to_blocks
        bs = cache.block_size
        off = jnp.asarray(prefix_len, jnp.int32)
        pos = jnp.minimum(off + jnp.arange(s, dtype=jnp.int32),
                          cfg.max_seq_len - 1)
        with jax.named_scope("embed"):
            x = self.drop(self.wte(Tensor(ids)) +
                          self.wpe(Tensor(pos[None, :])))
        table_row = jnp.asarray(table_row, jnp.int32)
        cache_k, cache_v = cache.k, cache.v
        k_sc, v_sc = cache.k_scale, cache.v_scale
        quantized = k_sc is not None
        if quantized:
            from ..ops.quantized_matmul import (dequantize_kv,
                                                kv_quant_mode,
                                                quantize_kv)
            mode = kv_quant_mode(cache_k.dtype)
        for i, blk in enumerate(self.blocks):
            if quantized:
                # gather int8 blocks + scale planes, DEQUANTIZE into an
                # f32 working buffer, then requantize on the scatter
                # back.  The buffer must stay f32 end to end: in f32,
                # requantization of untouched prefix positions is exact
                # (amax positions quantize to ±127, so round(q·s/s')
                # reproduces q bit for bit) — a bf16 buffer would round
                # q·s first and drift the shared prefix codes on every
                # radix-cache hit.  Attention dtype is unaffected: the
                # block casts the buffer to q.dtype before attending.
                k_buf = dequantize_kv(
                    blocks_to_rows(cache_k[i][table_row]),
                    blocks_to_rows(k_sc[i][table_row]), jnp.float32)
                v_buf = dequantize_kv(
                    blocks_to_rows(cache_v[i][table_row]),
                    blocks_to_rows(v_sc[i][table_row]), jnp.float32)
            else:
                k_buf = blocks_to_rows(cache_k[i][table_row])
                v_buf = blocks_to_rows(cache_v[i][table_row])
            x, k_buf, v_buf = blk.forward_prefill_paged(
                x, k_buf, v_buf, prefix_len)
            # duplicate table entries (trailing null-block slots) scatter
            # identical gathered-back values — benign by construction
            if quantized:
                kq, ks = quantize_kv(k_buf, mode)
                vq, vs = quantize_kv(v_buf, mode)
                cache_k = cache_k.at[i, table_row].set(
                    rows_to_blocks(kq, bs))
                cache_v = cache_v.at[i, table_row].set(
                    rows_to_blocks(vq, bs))
                k_sc = k_sc.at[i, table_row].set(
                    rows_to_blocks(ks, bs).astype(k_sc.dtype))
                v_sc = v_sc.at[i, table_row].set(
                    rows_to_blocks(vs, bs).astype(v_sc.dtype))
            else:
                cache_k = cache_k.at[i, table_row].set(
                    rows_to_blocks(k_buf, bs))
                cache_v = cache_v.at[i, table_row].set(
                    rows_to_blocks(v_buf, bs))
        return self._final_norm(x, "head"), \
            type(cache)(cache_k, cache_v, k_sc, v_sc)

    def forward(self, input_ids, attn_mask=None):
        from ..distributed.recompute import recompute as _rc
        s = input_ids.shape[1]
        pos = Tensor(jnp.arange(s, dtype=jnp.int32)[None, :])
        with jax.named_scope("embed"):
            x = self.drop(self.wte(input_ids) + self.wpe(pos))
        if self._scan_ok(attn_mask):
            return self._final_norm(self._forward_blocks_scanned(x),
                                    "head_ce")
        for blk in self.blocks:
            if self._recompute and self.training:
                # mask passed positionally so the checkpointed region
                # treats it as a traced input
                pol = getattr(self, "_recompute_policy", None)
                x = _rc(blk, x, policy=pol) if attn_mask is None else \
                    _rc(blk, x, attn_mask, policy=pol)
            else:
                x = blk(x) if attn_mask is None else blk(x, attn_mask)
        return self._final_norm(x, "head_ce")


class GPTForCausalLM(Layer):
    """LM head on top; logits share the (vocab-sharded) embedding matrix
    when tie_word_embeddings (GPT-3 convention)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.cfg = config
        self.gpt = GPTModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size,
                weight_attr=ParamAttr(initializer=I.Normal(
                    0.0, config.initializer_range)),
                has_bias=False, gather_output=True,
                axis_name=config.tp_axis)

    def enable_recompute(self, policy=None):
        self.gpt.enable_recompute(policy=policy)
        return self

    def enable_scan_layers(self, flag: bool = True):
        self.gpt.enable_scan_layers(flag)
        return self

    def enable_zero3_overlap(self, axis: str = "dp"):
        self.gpt.enable_zero3_overlap(axis)
        return self

    def enable_quantize(self, mode: Optional[str] = "int8"):
        self.gpt.enable_quantize(mode)
        self.cfg = self.gpt.cfg
        return self

    def _tp_size(self) -> int:
        from ..distributed.mesh import get_mesh
        m = get_mesh()
        if m is None or self.cfg.tp_axis not in m.axis_names:
            return 1
        return m.shape[self.cfg.tp_axis]

    def forward(self, input_ids, attn_mask=None):
        x = self.gpt(input_ids, attn_mask=attn_mask)
        if (self.cfg.fused_ce and self.training
                and self.cfg.tie_word_embeddings
                and self._tp_size() == 1):
            # blocked-CE training path: hand (hidden, lm weight) to the
            # criterion instead of projecting to [B, S, V] logits — the
            # projection happens inside the fused loss, vocab chunk by
            # vocab chunk (eval/generation still produce full logits).
            # Skipped on tp>1 meshes: the blocked loop's dynamic vocab
            # slices would force GSPMD to all-gather the vocab-sharded
            # LM head every step, costing more than the logits save
            return x, self.gpt.wte.weight
        with jax.named_scope("head_ce"):
            if self.cfg.tie_word_embeddings:
                w = self.gpt.wte.weight  # [V, H], vocab-sharded over tp
                return matmul(x, w, transpose_y=True)
            return self.lm_head(x)

    # ---- serving path -------------------------------------------------
    def init_kv_cache(self, batch_slots: int, capacity: Optional[int] = None,
                      dtype=None, kv_dtype=None) -> StaticKVCache:
        return self.gpt.init_kv_cache(batch_slots, capacity, dtype,
                                      kv_dtype)

    def _head_logits(self, hidden):
        """hidden Tensor [..., H] -> logits Tensor [..., V]."""
        with jax.named_scope("head"):
            if self.cfg.tie_word_embeddings:
                return matmul(hidden, self.gpt.wte.weight,
                              transpose_y=True)
            return self.lm_head(hidden)

    def prefill(self, input_ids, cache: StaticKVCache, slot, prompt_len):
        """Prefill one slot; returns ``(logits [1, V], cache)`` — the
        logits of the LAST real prompt token (position prompt_len-1),
        i.e. the distribution of the first generated token."""
        h, cache = self.gpt.forward_prefill(input_ids, cache, slot,
                                            prompt_len)
        harr = h.data                                     # [1, s, H]
        last = jax.lax.dynamic_slice(
            harr, (jnp.asarray(0, jnp.int32),
                   jnp.asarray(prompt_len, jnp.int32) - 1,
                   jnp.asarray(0, jnp.int32)),
            (1, 1, harr.shape[-1]))[:, 0]                 # [1, H]
        logits = self._head_logits(Tensor(last))
        return logits.data, cache

    def decode_step(self, tokens, cache: StaticKVCache, active):
        """One decode step for all slots; returns
        ``(logits [B, V], cache)``."""
        h, cache = self.gpt.step(tokens, cache, cache.lengths,
                                 advance=active)
        logits = self._head_logits(h)                     # [B, 1, V]
        return logits.data[:, 0], cache

    def verify_step(self, tokens, cache: StaticKVCache):
        """Windowed multi-token step for all slots (spec-decode verify /
        draft catch-up); returns ``(logits [B, W, V], cache)`` — the
        logits at every window position, i.e. logits[:, i] is the
        next-token distribution after consuming tokens[:, :i+1].
        Lengths are NOT advanced: the caller (the spec tick) advances
        them by the count it commits, which it only knows after the
        acceptance rule has run on these logits."""
        h, cache = self.gpt.step(tokens, cache, cache.lengths)
        logits = self._head_logits(h)                     # [B, W, V]
        return logits.data, cache

    def verify_step_paged(self, tokens, cache, tables, lengths):
        """Paged windowed multi-token step for all slots; returns
        ``(logits [B, W, V], cache)``."""
        h, cache = self.gpt.step(tokens, cache, lengths, tables=tables)
        logits = self._head_logits(h)
        return logits.data, cache

    def _chunk_last_logits(self, h, advance):
        """Gather each slot's LAST-real-chunk-token hidden state
        (position ``advance[b]-1`` in the window; clamped to 0 for
        non-participating rows, whose logits the scheduler ignores)
        and project to logits [B, V] — one head matmul per tick
        instead of [B, C, V]."""
        harr = h.data                                     # [B, C, H]
        idx = jnp.clip(jnp.asarray(advance, jnp.int32) - 1, 0,
                       harr.shape[1] - 1)
        last = jnp.take_along_axis(harr, idx[:, None, None],
                                   axis=1)[:, 0]          # [B, H]
        logits = self._head_logits(Tensor(last))
        return logits.data

    def prefill_chunk(self, tokens, cache: StaticKVCache, lengths,
                      advance):
        """Chunked-prefill step for all slots (dense cache); returns
        ``(logits [B, V], cache)`` — logits after each slot's last
        real chunk token, i.e. the first-generated-token distribution
        for slots whose chunk completes their prompt."""
        h, cache = self.gpt.step(tokens, cache, lengths,
                                 advance=advance)
        return self._chunk_last_logits(h, advance), cache

    def prefill_chunk_paged(self, tokens, cache, tables, lengths,
                            advance):
        """Paged chunked-prefill step for all slots; returns
        ``(logits [B, V], cache)``."""
        h, cache = self.gpt.step(tokens, cache, lengths, tables=tables)
        return self._chunk_last_logits(h, advance), cache

    def prefill_paged(self, input_ids, cache, table_row, prefix_len,
                      suffix_len):
        """Paged prefill of one slot; ``input_ids`` is the bucket-padded
        divergent suffix and ``suffix_len`` its real token count.
        Returns ``(logits [1, V], cache)`` — the logits of the last real
        suffix token (= the first generated token's distribution)."""
        h, cache = self.gpt.forward_prefill_paged(
            input_ids, cache, table_row, prefix_len)
        harr = h.data                                     # [1, s, H]
        last = jax.lax.dynamic_slice(
            harr, (jnp.asarray(0, jnp.int32),
                   jnp.asarray(suffix_len, jnp.int32) - 1,
                   jnp.asarray(0, jnp.int32)),
            (1, 1, harr.shape[-1]))[:, 0]                 # [1, H]
        logits = self._head_logits(Tensor(last))
        return logits.data, cache

    def decode_step_paged(self, tokens, cache, tables, lengths):
        """One paged decode step for all slots; returns
        ``(logits [B, V], cache)``."""
        h, cache = self.gpt.step(tokens, cache, lengths, tables=tables)
        logits = self._head_logits(h)                     # [B, 1, V]
        return logits.data[:, 0], cache

    def generate(self, input_ids, max_new_tokens: int = 32,
                 eos_id: Optional[int] = None, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0, seed: int = 0,
                 include_prompt: bool = False):
        """Single-request convenience wrapper over the serving engine
        (inference.engine.InferenceEngine): prefill the prompt, decode
        greedily (temperature=0) or by temperature/top-k/top-p sampling,
        stop at ``eos_id``/``max_new_tokens``.  Returns a 1-D numpy
        array of generated token ids.

        Builds a 1-slot engine per call (compiles on first use; the
        persistent compile cache makes repeat processes cheap).  For
        throughput serving use InferenceEngine directly.
        """
        from ..inference.engine import InferenceEngine
        ids = np.asarray(
            input_ids.numpy() if isinstance(input_ids, Tensor)
            else input_ids).reshape(-1).astype(np.int32)
        eng = InferenceEngine(self, batch_slots=1,
                              top_k=top_k, seed=seed)
        # engine.generate routes through the admission queue: on a busy
        # engine the call BLOCKS until a slot frees instead of raising
        gen = np.asarray(eng.generate(
            ids, max_new_tokens=max_new_tokens, eos_id=eos_id,
            temperature=temperature, top_p=top_p), np.int32)
        if include_prompt:
            return np.concatenate([ids, gen])
        return gen


class GPTEmbeddingStage(Layer):
    """Pipeline 'pre' stage: token + position embedding (shares the
    underlying parameters with the source model)."""

    def __init__(self, wte, wpe, drop):
        super().__init__()
        self.wte, self.wpe, self.drop = wte, wpe, drop

    def forward(self, input_ids):
        s = input_ids.shape[1]
        pos = Tensor(jnp.arange(s, dtype=jnp.int32)[None, :])
        return self.drop(self.wte(input_ids) + self.wpe(pos))


class GPTHeadStage(Layer):
    """Pipeline 'post' stage: final norm + untied LM head."""

    def __init__(self, ln_f, lm_head):
        super().__init__()
        self.ln_f, self.lm_head = ln_f, lm_head

    def forward(self, h):
        return self.lm_head(self.ln_f(h))


def gpt_pipeline_parts(model: "GPTForCausalLM"):
    """Split a GPTForCausalLM into (pre, blocks, post) stage views for
    GPipeTrainer — the analogue of the reference PipelineOptimizer's
    program split by op_device (fluid/optimizer.py:3718), but the split
    is BY CONSTRUCTION (embedding / N identical blocks / head) instead
    of by annotation. Requires tie_word_embeddings=False: tied weights
    would put one parameter on two pipeline stages."""
    if model.cfg.tie_word_embeddings:
        raise ValueError(
            "pipeline parallelism needs tie_word_embeddings=False (tied "
            "embedding+head would live on both the first and last stage)")
    pre = GPTEmbeddingStage(model.gpt.wte, model.gpt.wpe, model.gpt.drop)
    post = GPTHeadStage(model.gpt.ln_f, model.lm_head)
    return pre, list(model.gpt.blocks), post


class GPTPretrainingCriterion(Layer):
    """Shifted-token cross entropy with optional loss mask (the reference
    trains GPT with a masked LM loss over ignored pad positions)."""

    def __init__(self, ignore_index: int = -100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, logits, labels, loss_mask=None):
        # logits: [B, S, V]; labels: [B, S] already shifted by the data
        # pipeline (labels[t] = input_ids[t+1]). With config.fused_ce
        # the model hands over (hidden [B, S, H], lm weight [V, H])
        # instead and the loss runs blockwise over the vocab without
        # ever materializing the logits tensor.
        with jax.named_scope("head_ce"):
            flat_labels = labels.reshape([-1])
            if isinstance(logits, (tuple, list)) and len(logits) == 2:
                hidden, w = logits
                h = hidden.shape[-1]
                losses = F.fused_linear_cross_entropy(
                    hidden.reshape([-1, h]), w, flat_labels,
                    reduction="none", ignore_index=self.ignore_index)
            else:
                v = logits.shape[-1]
                losses = F.cross_entropy(logits.reshape([-1, v]), flat_labels,
                                         reduction="none",
                                         ignore_index=self.ignore_index)
            if loss_mask is not None:
                m = loss_mask.reshape([-1]).astype("float32")
                return (losses.reshape([-1]) * m).sum() / m.sum()
            return losses.mean()
