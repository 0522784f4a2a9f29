"""Brumby (Manifest AI, Brumby-14B-Base; ``model_type`` ``brumby``): the
Qwen3 dense block with every attention layer replaced by POWER RETENTION
(arXiv:2507.04239), served through ``InferenceEngine``.

``x = embed(ids)``; for each layer ``x = x + Retention(RMSNorm(x))``, then
``x = x + (silu(w W_gate) * (w W_up)) W_down`` with ``w = RMSNorm(x)``;
then ``RMSNorm`` and an untied head.  No bias but the gate's.

Retention, for a query head ``h`` in the group of KV head ``j``:
``q = RoPE(RMSNorm_q(u W_q))``, ``k = RoPE(RMSNorm_k(u W_k))``,
``v = u W_v``, ``gamma = log sigmoid(u W_g + b_g + gate_bias_shift)``
(one number a KV head), and ``y = ops.power_retention`` of them: degree
2, a float32 state a KV head that all its query heads read.  RoPE is the
rotate-half form at ``rope_theta``; the angles are computed from the
positions (a slot's length), there is no table, so nothing in the model
is sized by ``max_seq_len``.

Every layer is alike and none holds keys and values: the serving cache
is a ``RecurrentStateCache`` (one state a layer and slot), and the two
entry points the engine jits are ``prefill`` (one slot, a padded bucket:
the state stops at the prompt's last real token and REPLACES the slot's)
and ``decode_step`` (one token for every slot; an inactive slot keeps
its state).  The model serves; ``forward`` gives a whole sequence's
logits through the same mixer (no tape: it does not train).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer_base import Layer, ParamAttr
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.norm import RMSNorm
from .recurrent_cache import RecurrentStateCache, RetentionLayerView

__all__ = ["BrumbyConfig", "BrumbyModel", "BrumbyForCausalLM"]

_F32 = jnp.float32


@dataclass
class BrumbyConfig:
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    # positions the engine may serve to; sizes nothing
    max_seq_len: int = 32768
    # power retention: the prefill's chunk, the normaliser's eps, and a
    # constant added to the gate's bias (0 is the plain biased map; a
    # benchmark's zero-mean random biases use it to stand where trained
    # ones do, with exp(gamma) near 1)
    retention_chunk: int = 256
    retention_eps: float = 1e-6
    gate_bias_shift: float = 0.0
    initializer_range: float = 0.02
    # the linear maps and the embedding as placeholders, for a caller that
    # then assigns loaded weights: float32 normals of the published size
    # (16.8 GB at 8 layers) do not fit a 16 GB chip beside the weights
    # being loaded.  See _HostZeros.
    placeholder_params: bool = False
    _zeros: Optional["_HostZeros"] = field(default=None, init=False,
                                           repr=False, compare=False)

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads on "
                f"{self.num_key_value_heads} KV heads")
        self._zeros = _HostZeros() if self.placeholder_params else None

    @property
    def state_bytes_per_slot(self) -> int:
        """The mathematics' own state of one sequence, all layers: a
        float32 ``[d (d + 1) / 2, d + 1]`` a KV head."""
        d = self.head_dim
        return self.num_hidden_layers * self.num_key_value_heads * \
            (d * (d + 1) // 2) * (d + 1) * 4


class _HostZeros(I.Initializer):
    """A placeholder parameter: bf16 zeros in HOST memory, one buffer a
    shape (a placeholder is replaced, never written, so the layers'
    share theirs: 3.5 GB and three seconds at the published size where
    a buffer a parameter would be 8.4 GB)."""

    def __init__(self):
        self.made = {}

    def __call__(self, shape, dtype=None, key=None):
        shape = tuple(shape)
        if shape not in self.made:
            self.made[shape] = jax.device_put(
                np.zeros(shape, jnp.bfloat16), jax.devices("cpu")[0])
        return self.made[shape]


def _attr(cfg: BrumbyConfig):
    return ParamAttr(initializer=cfg._zeros or
                     I.Normal(0.0, cfg.initializer_range))


def _linear(cfg, n_in, n_out, bias=False) -> Linear:
    return Linear(n_in, n_out, weight_attr=_attr(cfg),
                  bias_attr=None if bias else False)


def rope(x, positions, theta: float):
    """Rotate-half rotary embedding of ``x [B, W, heads, d]`` at
    ``positions [B, W]``, float32, angles computed here."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=_F32) / d)
    ang = positions.astype(_F32)[..., None] * inv           # [B, W, d/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


class BrumbyRetention(Layer):
    def __init__(self, cfg: BrumbyConfig):
        super().__init__()
        self.cfg = cfg
        h, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        self.q_proj = _linear(cfg, cfg.hidden_size, h * d)
        self.k_proj = _linear(cfg, cfg.hidden_size, hkv * d)
        self.v_proj = _linear(cfg, cfg.hidden_size, hkv * d)
        self.o_proj = _linear(cfg, h * d, cfg.hidden_size)
        self.g_proj = _linear(cfg, cfg.hidden_size, hkv, bias=True)
        self.q_norm = RMSNorm(d, epsilon=cfg.rms_norm_eps)
        self.k_norm = RMSNorm(d, epsilon=cfg.rms_norm_eps)

    def _project(self, x, positions):
        """``x [B, W, hidden]`` -> ``q [B, W, H, d]``, ``k``, ``v [B, W,
        Hkv, d]`` and ``log_g [B, W, Hkv]``; q, k and the gate float32."""
        cfg = self.cfg
        b, w = x.shape[0], x.shape[1]
        heads = lambda proj, n: proj(x).data.reshape(b, w, n, cfg.head_dim)
        normed = lambda norm, t: norm(Tensor(t.astype(_F32))).data
        q = normed(self.q_norm, heads(self.q_proj, cfg.num_attention_heads))
        k = normed(self.k_norm, heads(self.k_proj, cfg.num_key_value_heads))
        v = heads(self.v_proj, cfg.num_key_value_heads)
        log_g = jax.nn.log_sigmoid(
            self.g_proj(x).data.astype(_F32) + cfg.gate_bias_shift)
        return (rope(q, positions, cfg.rope_theta),
                rope(k, positions, cfg.rope_theta), v, log_g)

    def step(self, x, view: RetentionLayerView, positions, real=None):
        """One serving step over one layer's state: the decode tick
        (W = 1, every slot) or a prefill (one slot, a bucket of W).  x
        is ``[B, W, hidden]`` (a Tensor); ``positions [B, W]`` the
        tokens' positions; ``real [B]`` how many of the W tokens are
        real (None: all): the others leave the state as it was.  Returns
        ``(out, view)``."""
        b, w = x.shape[0], x.shape[1]
        with jax.named_scope("retention_proj"):
            q, k, v, log_g = self._project(x, positions)
        with jax.named_scope("retention_step" if w == 1
                             else "retention_chunk"):
            y, view = view.absorb(k, v, log_g, real).read(q)
        with jax.named_scope("retention_proj"):
            y = Tensor(y.astype(x.dtype).reshape(b, w, -1))
            return self.o_proj(y), view


class BrumbyMLP(Layer):
    def __init__(self, cfg: BrumbyConfig):
        super().__init__()
        self.gate_proj = _linear(cfg, cfg.hidden_size, cfg.intermediate_size)
        self.up_proj = _linear(cfg, cfg.hidden_size, cfg.intermediate_size)
        self.down_proj = _linear(cfg, cfg.intermediate_size, cfg.hidden_size)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class BrumbyDecoderLayer(Layer):
    def __init__(self, cfg: BrumbyConfig):
        super().__init__()
        eps = cfg.rms_norm_eps
        self.input_layernorm = RMSNorm(cfg.hidden_size, epsilon=eps)
        self.self_attn = BrumbyRetention(cfg)
        self.post_attention_layernorm = RMSNorm(cfg.hidden_size, epsilon=eps)
        self.mlp = BrumbyMLP(cfg)

    def step(self, x, view, positions, real=None):
        """Each of the two scopes holds its pre-norm, the sub-layer and
        the residual add.  Returns ``(x, view)``."""
        with jax.named_scope("retention"):
            a, view = self.self_attn.step(self.input_layernorm(x), view,
                                          positions, real)
            x = x + a
        with jax.named_scope("mlp"):
            return x + self.mlp(self.post_attention_layernorm(x)), view


class BrumbyModel(Layer):
    """Embedding, the layers, final norm: hidden states."""

    def __init__(self, cfg: BrumbyConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = Embedding(cfg.vocab_size, cfg.hidden_size,
                                      weight_attr=_attr(cfg))
        self.layers = LayerList([BrumbyDecoderLayer(cfg)
                                 for _ in range(cfg.num_hidden_layers)])
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def step(self, ids, views, positions, real=None):
        """``ids``/``positions [B, W]`` through every layer, layer ``i``
        over ``views[i]``.  Returns ``(hidden [B, W, H], views)``."""
        with jax.named_scope("embed"):
            x = self.embed_tokens(Tensor(ids))
        out = []
        for layer, view in zip(self.layers, views):
            x, view = layer.step(x, view, positions, real)
            out.append(view)
        with jax.named_scope("head"):
            return self.norm(x), out


class BrumbyForCausalLM(Layer):
    def __init__(self, config: BrumbyConfig):
        super().__init__()
        self.cfg = config
        self.model = BrumbyModel(config)
        self.lm_head = _linear(config, config.hidden_size, config.vocab_size)
        if config._zeros is not None:
            config._zeros.made.clear()

    def _logits(self, hidden):
        """``[..., H]`` array -> float32 logits ``[..., V]``."""
        with jax.named_scope("head"):
            return jnp.matmul(hidden, self.lm_head.weight.data,
                              preferred_element_type=_F32)

    def forward(self, input_ids):
        """Logits ``[B, S, V]`` of whole sequences, from a zero state."""
        ids = input_ids.data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        cfg = self.cfg
        b, s = ids.shape
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        fresh = RetentionLayerView(None, cfg.retention_chunk,
                                   cfg.retention_eps)
        h, _ = self.model.step(ids, [fresh] * cfg.num_hidden_layers, pos)
        return Tensor(self._logits(h.data))

    # ---- serving path -------------------------------------------------
    def init_kv_cache(self, batch_slots: int, capacity=None, dtype=None,
                      kv_dtype=None) -> RecurrentStateCache:
        """One float32 state a layer and slot.  ``capacity`` (the
        engine's ``max_seq_len``) sizes nothing; the state's precision is
        the mechanism's, so ``dtype`` is not taken either."""
        cfg = self.cfg
        if kv_dtype is not None:
            raise ValueError(f"{type(self).__name__} keeps a float32 "
                             f"recurrent state: kv_dtype={kv_dtype!r} has "
                             f"no rows of keys and values to quantize")
        return RecurrentStateCache.zeros(
            cfg.num_hidden_layers, batch_slots, cfg.num_key_value_heads,
            cfg.head_dim, cfg.state_bytes_per_slot, cfg.retention_chunk,
            cfg.retention_eps)

    def prefill(self, input_ids, cache: RecurrentStateCache, slot,
                prompt_len):
        """Prefill ONE slot from a zero state over a (possibly padded)
        prompt ``input_ids [1, bucket]``: every layer's state stops at
        token ``prompt_len - 1`` and replaces the slot's, and
        ``lengths[slot] = prompt_len``.  Returns ``(logits [1, V] of the
        last real token, cache)``."""
        ids = input_ids.data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        plen = jnp.asarray(prompt_len, jnp.int32)
        pos = jnp.arange(ids.shape[1], dtype=jnp.int32)[None, :]
        h, views = self.model.step(
            ids, [cache.fresh()] * cache.num_layers, pos, plen[None])
        with jax.named_scope("retention_chunk"):
            for i, view in enumerate(views):
                cache = cache.with_slot(i, slot, view)
        zero = jnp.asarray(0, jnp.int32)
        last = jax.lax.dynamic_slice(
            h.data, (zero, plen - 1, zero), (1, 1, h.shape[-1]))[:, 0]
        lengths = cache.lengths.at[jnp.asarray(slot, jnp.int32)].set(plen)
        return self._logits(last), cache.with_lengths(lengths)

    def decode_step(self, tokens, cache: RecurrentStateCache, active):
        """One token for every slot at position ``cache.lengths``; a
        slot with ``active == 0`` keeps its state and its length.
        Returns ``(logits [B, V], cache)``."""
        toks = tokens.data if isinstance(tokens, Tensor) \
            else jnp.asarray(tokens)
        lens = cache.lengths
        on = jnp.asarray(active, jnp.int32)
        b = lens.shape[0]
        h, views = self.model.step(
            toks.reshape(b, 1),
            [cache.layer(i) for i in range(cache.num_layers)],
            jnp.minimum(lens, self.cfg.max_seq_len - 1)[:, None], on)
        for i, view in enumerate(views):
            cache = cache.with_layer(i, view)
        return self._logits(h.data[:, 0]), cache.with_lengths(lens + on)
