"""Granite 4.0-H (IBM; ``model_type`` ``granitemoehybrid`` with
``num_local_experts`` 0: dense), served through ``InferenceEngine``: a
stack whose every layer is a mixer AND a SwiGLU feed-forward, the mixer
a Mamba-2 state-space layer or causal grouped-query attention as
``layer_types`` says, under four scalar multipliers::

    h = E[ids] * embedding_multiplier
    for each layer:  u = RMSNorm_in(h)
                     h = h + residual_multiplier * Mixer(u)
                     v = RMSNorm_post(h);  [g | w] = v W_in
                     h = h + residual_multiplier * (silu(g) * w) W_out
    logits = RMSNorm_f(h) E^T / logits_scaling          (tied head)

- Mamba-2 is ``models.nemotron_h.mamba2_mixer``'s mathematics (the
  function is shared) at this family's sizes: one B/C group, a biased
  convolution of ``mamba_d_conv`` taps, chunk ``mamba_chunk_size``, the
  gated RMSNorm over all ``mamba_n_heads * mamba_d_head`` channels.
- Attention has no bias and NO rotary embedding (``position_embedding_
  type`` ``nope``) and scales its scores by ``attention_multiplier``, not
  by ``head_dim ** -0.5``: the kernels' scale is the latter, so the
  model folds ``attention_multiplier * sqrt(head_dim)`` into ``q``.

Two kinds of layer keep two kinds of state, so the serving cache is a
``HybridStateCache``: rows of keys and values for an attention layer
(two KV heads of 64 to a row of 128: ``GraniteAttention``), a
float32 state ``[slots, H, P, N]`` and the convolution's last
``mamba_d_conv - 1`` inputs for a Mamba layer.  The two entry points the
engine jits are ``prefill`` (one slot, a padded bucket: rows written,
state and window stopped at the prompt's last real token and REPLACING
the slot's) and ``decode_step`` (one token for every slot; an inactive
slot keeps its state, its window and its length).  The model serves;
``forward`` gives a whole sequence's logits through the same mixers.
The configuration's keys keep the published names.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer_base import Layer, ParamAttr
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.norm import RMSNorm
from .brumby import _HostZeros
from .gpt import DenseKVLayer
from .nemotron_h import Mamba2Sizes, mamba2_mixer
from .recurrent_cache import (HybridStateCache, KVRows, MambaLayerView,
                              MambaState)

__all__ = ["GraniteHybridConfig", "GraniteHybridModel",
           "GraniteHybridForCausalLM"]

_F32 = jnp.float32
_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    shared_intermediate_size: int = 8192
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = _PERIOD * 4
    rms_norm_eps: float = 1e-5
    # the four multipliers
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    # attention layers
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    # Mamba-2 layers: d_inner = mamba_n_heads * mamba_d_head
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    # positions the engine may serve to (max_position_embeddings); sizes
    # nothing but the cache a caller asks for
    max_seq_len: int = 131072
    initializer_range: float = 0.02
    # the linear maps and the embedding as placeholders for a caller that
    # then assigns loaded weights (models.brumby._HostZeros)
    placeholder_params: bool = False
    _zeros: Optional[_HostZeros] = field(default=None, init=False,
                                         repr=False, compare=False)

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        odd = set(self.layer_types) - {"mamba", "attention"}
        if odd or len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types takes 'mamba' and 'attention', one a layer "
                f"({self.num_hidden_layers}); got {self.layer_types!r}")
        if self.num_attention_heads % self.num_key_value_heads or \
                self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads on "
                f"{self.num_key_value_heads} KV heads at hidden "
                f"{self.hidden_size}")
        self._zeros = _HostZeros() if self.placeholder_params else None

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def rows_packed(self) -> int:
        """KV heads that share one cached row (GraniteAttention): as many
        as fill 128 channels and divide the KV heads."""
        packed = 1
        while packed * 2 * self.head_dim <= 128 and \
                self.num_key_value_heads % (packed * 2) == 0:
            packed *= 2
        return packed

    @property
    def mamba_sizes(self) -> Mamba2Sizes:
        return Mamba2Sizes(self.mamba_n_heads, self.mamba_d_head,
                           self.mamba_n_groups, self.mamba_d_state,
                           self.mamba_chunk_size, self.rms_norm_eps)

    @property
    def conv_channels(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head + \
            2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def state_bytes_per_slot(self) -> int:
        """The mathematics' own recurrent state of one sequence, all
        Mamba layers: a float32 ``[H, P, N]`` a layer."""
        return self.layer_types.count("mamba") * self.mamba_n_heads * \
            self.mamba_d_head * self.mamba_d_state * 4


def _attr(cfg: GraniteHybridConfig):
    return ParamAttr(initializer=cfg._zeros or
                     I.Normal(0.0, cfg.initializer_range))


def _linear(cfg, n_in, n_out) -> Linear:
    return Linear(n_in, n_out, weight_attr=_attr(cfg), bias_attr=False)


class _Conv1dParams(Layer):
    """The depthwise convolution's ``weight [C, K]`` and ``bias [C]``."""

    def __init__(self, cfg, channels):
        super().__init__()
        self.weight = self.create_parameter(
            [channels, cfg.mamba_d_conv],
            attr=ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range)))
        self.bias = self.create_parameter([channels], is_bias=True)


class GraniteMambaMixer(Layer):
    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.sizes = sz = cfg.mamba_sizes
        self.in_proj = _linear(
            cfg, cfg.hidden_size,
            sz.inner + cfg.conv_channels + sz.heads)
        self.conv1d = _Conv1dParams(cfg, cfg.conv_channels)
        self.dt_bias = self.create_parameter([sz.heads], is_bias=True)
        self.A_log = self.create_parameter(
            [sz.heads], default_initializer=I.Constant(0.0))
        self.D = self.create_parameter(
            [sz.heads], default_initializer=I.Constant(1.0))
        self.norm = RMSNorm(sz.inner, epsilon=cfg.rms_norm_eps)
        self.out_proj = _linear(cfg, sz.inner, cfg.hidden_size)

    def step(self, x, view: MambaLayerView, real=None):
        """``x [B, W, hidden]`` (an array) over one layer's view; ``real
        [B]`` of the W positions are real.  Returns ``(out, view)``."""
        return mamba2_mixer(
            x, self.in_proj.weight.data, self.conv1d.weight.data,
            self.conv1d.bias.data, self.dt_bias.data, self.A_log.data,
            self.D.data, self.norm.weight.data, self.out_proj.weight.data,
            self.sizes, view, real)


class GraniteAttention(Layer):
    """Rows are cached PACKED: ``rows_packed`` KV heads side by side in
    one row of ``rows_packed * head_dim`` channels (two heads of 64 in a
    row of 128 at the published sizes).  The device's own layout of a
    bf16 buffer whose minor dimension is 64 puts the positions minor, so
    a cache ``[slots, 8, capacity, 64]`` is transposed on the way into
    the decode kernel and back every tick (four layers' k and v: 10 GB
    of traffic a tick at 64 slots; seen in the program compiled for a
    described v5e, PR 48).  Packed, the buffer is ``[slots, 4, capacity,
    128]``, the same bytes, and the kernel takes it as it lies.  A query
    head reads its KV head's half of the row: its query is spread with
    zeros over the other half, so the scores are its own head's exactly,
    and its half of the output row is taken back."""

    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        h, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        self.q_proj = _linear(cfg, cfg.hidden_size, h * d)
        self.k_proj = _linear(cfg, cfg.hidden_size, hkv * d)
        self.v_proj = _linear(cfg, cfg.hidden_size, hkv * d)
        self.o_proj = _linear(cfg, h * d, cfg.hidden_size)
        # the kernels scale scores by their row's width ** -0.5; what is
        # left of attention_multiplier goes into q (0.125 over a plain
        # head at the published sizes)
        self.q_scale = cfg.attention_multiplier * math.sqrt(d)
        self.packed = cfg.rows_packed

    def _qkv(self, x):
        """``x [B, W, hidden]`` (a Tensor) -> arrays ``q [B, W, H, d]``
        (not yet scaled), ``k``/``v [B, W, Hkv, d]``."""
        cfg = self.cfg
        b, w = x.shape[0], x.shape[1]
        with jax.named_scope("attn_proj"):
            heads = lambda proj, n: proj(x).data.reshape(
                b, w, n, cfg.head_dim)
            return (heads(self.q_proj, cfg.num_attention_heads),
                    heads(self.k_proj, cfg.num_key_value_heads),
                    heads(self.v_proj, cfg.num_key_value_heads))

    def _pack(self, rows):
        """``[B, W, Hkv, d]`` -> ``[B, W, Hkv / packed, packed d]``."""
        b, w, hkv, d = rows.shape
        return rows.reshape(b, w, hkv // self.packed, self.packed * d)

    def _half_of(self, heads: int):
        """``[H, packed]``: which part of its packed row each query head
        reads, one-hot."""
        group = heads // self.cfg.num_key_value_heads
        part = (jnp.arange(heads) // group) % self.packed
        return part[:, None] == jnp.arange(self.packed)[None, :]

    def _out(self, out, like):
        b, w = out.shape[0], out.shape[1]
        with jax.named_scope("attn_proj"):
            return self.o_proj(Tensor(out.astype(like.dtype).reshape(
                b, w, -1))).data

    def fresh(self, x):
        """Causal attention over whole sequences with nothing cached;
        returns ``(out, DenseKVLayer(k, v))``, the rows a prefill writes
        to its slot: packed, head-major ``[B, Hkv / packed, W, packed
        d]``."""
        q, k, v = self._qkv(x)
        with jax.named_scope("attn_core"):
            out = F.flash_attention(
                Tensor((q * self.q_scale).astype(q.dtype)), Tensor(k),
                Tensor(v), causal=True, training=False).data
            rows = DenseKVLayer(jnp.swapaxes(self._pack(k), 1, 2),
                                jnp.swapaxes(self._pack(v), 1, 2))
        return self._out(out, q), rows

    def step(self, x, kv: DenseKVLayer, lengths):
        """The W new tokens of every slot written at ``lengths[b] ..``
        and attended (``KVLayerView.write_attend``) over packed rows."""
        q, k, v = self._qkv(x)
        b, w, h, d = q.shape
        with jax.named_scope("attn_proj"):
            half = self._half_of(h)
            scale = self.q_scale * math.sqrt(self.packed)
            wide = (q * scale).astype(q.dtype)[:, :, :, None, :] * \
                half[:, :, None].astype(q.dtype)
            wide = wide.reshape(b, w, h, self.packed * d)
        out, kv = kv.write_attend(wide, self._pack(k), self._pack(v),
                                  lengths)
        with jax.named_scope("attn_proj"):
            out = jnp.sum(out.reshape(b, w, h, self.packed, d) *
                          half[:, :, None].astype(out.dtype), axis=3)
        return self._out(out, q), kv


class GraniteSharedMLP(Layer):
    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.width = cfg.shared_intermediate_size
        self.input_linear = _linear(cfg, cfg.hidden_size, 2 * self.width)
        self.output_linear = _linear(cfg, self.width, cfg.hidden_size)

    def forward(self, x):
        gw = self.input_linear(x)
        return self.output_linear(
            F.silu(gw[:, :, :self.width]) * gw[:, :, self.width:])


class GraniteHybridLayer(Layer):
    def __init__(self, cfg: GraniteHybridConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.residual = cfg.residual_multiplier
        self.input_layernorm = RMSNorm(cfg.hidden_size,
                                       epsilon=cfg.rms_norm_eps)
        if kind == "mamba":
            self.mamba = GraniteMambaMixer(cfg)
        else:
            self.self_attn = GraniteAttention(cfg)
        self.post_attention_layernorm = RMSNorm(cfg.hidden_size,
                                                epsilon=cfg.rms_norm_eps)
        self.shared_mlp = GraniteSharedMLP(cfg)

    def step(self, x, view, lengths=None, real=None):
        """One serving step of this layer over its view (a Tensor in, a
        Tensor out): a ``MambaLayerView`` (``real [B]`` of the W tokens
        real), a ``DenseKVLayer`` (the tokens written at ``lengths``), or
        None for an attention layer with nothing cached (a prefill, a
        plain forward: the view returned holds the rows).  Each of the
        two scopes holds its pre-norm, the sub-layer and the residual
        add.  Returns ``(x, view)``."""
        with jax.named_scope(self.kind if self.kind == "mamba" else "attn"):
            u = self.input_layernorm(x)
            if self.kind == "mamba":
                m, view = self.mamba.step(u.data, view, real)
            elif view is None:
                m, view = self.self_attn.fresh(u)
            else:
                m, view = self.self_attn.step(u, view, lengths)
            x = x + Tensor(m) * self.residual
        with jax.named_scope("mlp"):
            return x + self.shared_mlp(
                self.post_attention_layernorm(x)) * self.residual, view


class GraniteHybridModel(Layer):
    """Embedding, the layers, final norm: hidden states."""

    def __init__(self, cfg: GraniteHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = Embedding(cfg.vocab_size, cfg.hidden_size,
                                      weight_attr=_attr(cfg))
        self.layers = LayerList([GraniteHybridLayer(cfg, kind)
                                 for kind in cfg.layer_types])
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def embed(self, ids):
        with jax.named_scope("embed"):
            return self.embed_tokens(Tensor(ids)) * \
                self.cfg.embedding_multiplier

    def final_norm(self, x):
        with jax.named_scope("head"):
            return self.norm(x)


def empty_cache(cfg: GraniteHybridConfig, batch_slots: int, capacity: int,
                dtype) -> HybridStateCache:
    """One entry a layer: rows ``[slots, Hkv / packed, capacity, packed
    d]`` (k and v, ``dtype``; ``GraniteAttention`` says why packed) for an
    attention layer, a float32 state ``[slots, H, P, N]`` and a window
    ``[slots, K - 1, C]`` (``dtype``) for a Mamba layer."""
    slots, sz = int(batch_slots), cfg.mamba_sizes
    rows = (slots, cfg.num_key_value_heads // cfg.rows_packed,
            int(capacity), cfg.rows_packed * cfg.head_dim)

    def entry(kind):
        if kind == "attention":
            return KVRows(jnp.zeros(rows, dtype), jnp.zeros(rows, dtype))
        return MambaState(
            jnp.zeros((slots, sz.heads, sz.head_dim, sz.state), _F32),
            jnp.zeros((slots, cfg.mamba_d_conv - 1, cfg.conv_channels),
                      dtype))
    return HybridStateCache(
        [entry(kind) for kind in cfg.layer_types],
        jnp.zeros((slots,), jnp.int32), cfg.state_bytes_per_slot,
        cfg.mamba_chunk_size)


class GraniteHybridForCausalLM(Layer):
    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        self.cfg = config
        self.model = GraniteHybridModel(config)
        if config._zeros is not None:
            config._zeros.made.clear()

    def _logits(self, hidden):
        """``[..., H]`` array -> float32 logits ``[..., V]``: the tied
        head, accumulated in float32, over ``logits_scaling``."""
        with jax.named_scope("head"):
            table = self.model.embed_tokens.weight.data      # [V, H]
            logits = jax.lax.dot_general(
                hidden, table,
                (((hidden.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=_F32)
            return logits / self.cfg.logits_scaling

    def forward(self, input_ids):
        """Logits ``[B, S, V]`` of whole sequences, from zero states."""
        ids = input_ids.data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        x = self.model.embed(ids)
        for layer in self.model.layers:
            view = MambaLayerView(None, None, self.cfg.mamba_chunk_size) \
                if layer.kind == "mamba" else None
            x, _ = layer.step(x, view)
        return Tensor(self._logits(self.model.final_norm(x).data))

    # ---- serving path -------------------------------------------------
    def init_kv_cache(self, batch_slots: int, capacity=None, dtype=None,
                      kv_dtype=None) -> HybridStateCache:
        """``empty_cache`` at the embedding's dtype unless ``dtype``
        says otherwise.  The state's precision is the mechanism's:
        ``dtype`` does not reach it, and there is nothing to quantize."""
        if kv_dtype is not None:
            raise ValueError(f"{type(self).__name__} keeps a float32 "
                             f"recurrent state beside its rows: "
                             f"kv_dtype={kv_dtype!r} is not supported")
        return empty_cache(self.cfg, batch_slots,
                           capacity or self.cfg.max_seq_len,
                           dtype or self.model.embed_tokens.weight.dtype)

    def prefill(self, input_ids, cache: HybridStateCache, slot,
                prompt_len):
        """Prefill ONE slot over a (possibly padded) prompt ``input_ids
        [1, bucket]``: an attention layer's rows are written from
        position 0 (those past ``prompt_len`` lie beyond the recorded
        length), a Mamba layer's state and window stop at token
        ``prompt_len - 1`` and replace the slot's, and ``lengths[slot] =
        prompt_len``.  Returns ``(logits [1, V] of the last real token,
        cache)``."""
        ids = input_ids.data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        plen = jnp.asarray(prompt_len, jnp.int32)
        x = self.model.embed(ids)
        for i, layer in enumerate(self.model.layers):
            x, view = layer.step(x, cache.fresh(i), real=plen[None])
            with jax.named_scope("ssm_state_write" if layer.kind == "mamba"
                                 else "kv_write"):
                cache = cache.with_slot(i, slot, view)
        h = self.model.final_norm(x).data
        zero = jnp.asarray(0, jnp.int32)
        last = jax.lax.dynamic_slice(
            h, (zero, plen - 1, zero), (1, 1, h.shape[-1]))[:, 0]
        lengths = cache.lengths.at[jnp.asarray(slot, jnp.int32)].set(plen)
        return self._logits(last), cache.with_lengths(lengths)

    def decode_step(self, tokens, cache: HybridStateCache, active):
        """One token for every slot at position ``cache.lengths``; a
        slot with ``active == 0`` keeps its states, its windows and its
        length (its rows take masked garbage above its length).  Returns
        ``(logits [B, V], cache)``."""
        toks = tokens.data if isinstance(tokens, Tensor) \
            else jnp.asarray(tokens)
        lens = cache.lengths
        on = jnp.asarray(active, jnp.int32)
        x = self.model.embed(toks.reshape(lens.shape[0], 1))
        for i, layer in enumerate(self.model.layers):
            x, view = layer.step(x, cache.layer(i), lens, on)
            cache = cache.with_layer(i, view)
        h = self.model.final_norm(x).data[:, 0]
        ends = lens + on
        if cache.has_rows:
            ends = jnp.minimum(ends, cache.capacity)
        return self._logits(h), cache.with_lengths(ends)
