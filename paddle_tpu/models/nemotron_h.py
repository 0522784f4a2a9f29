"""Nemotron-H: a hybrid stack whose every layer is ONE mixer.

``x = embed(ids)``; for each character of ``hybrid_override_pattern``,
``x = x + Mixer(RMSNorm(x))`` with Mixer a Mamba-2 state-space layer for
``M``, a mixture of experts for ``E`` and causal grouped-query attention
for ``*``; then ``RMSNorm`` and an untied head.  No bias anywhere except
the convolution's.  (``model_type`` ``nemotron_h``; the configuration
keys keep the published names.)

- Mamba-2: ``[z | xBC | dt] = x W_in``; ``xBC`` through a causal
  depthwise convolution and silu, split into ``x_t [H, P]`` and
  ``B_t, C_t [G, N]``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  the recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``,
  ``y_t = C_t . h_t + D x_t`` runs as ``ops.ssd_scan`` (chunked); then a
  grouped RMSNorm of ``y silu(z)`` and ``W_out``.
- MoE: ``distributed.moe.MoELayer`` on its dropless path (sigmoid scores
  over all ``n_routed_experts``, top ``num_experts_per_tok`` by score +
  correction bias, normalised, times ``routed_scaling_factor``; relu^2
  experts without gate) holding ``held_experts`` of them, plus a shared
  expert of the same form that every token passes.
- Attention: bias-free GQA through the flash kernel, softmax scale
  ``head_dim ** -0.5``, no rotary embedding (the family carries position
  in its Mamba layers).

The stack is heterogeneous, so it is unrolled; ``enable_recompute``
remats each layer (there is no ``enable_scan_layers``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.autograd import apply
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer_base import Layer, ParamAttr
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.norm import RMSNorm

__all__ = ["NemotronHConfig", "NemotronHModel", "NemotronHForCausalLM"]


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    hybrid_override_pattern: str = "MEMEM*EME"
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02
    max_seq_len: int = 8192
    # attention (*)
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # Mamba-2 (M): d_inner = mamba_num_heads * mamba_head_dim
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    # mixture of experts (E)
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    # this chip's share of the routed experts, [lo, hi); None holds all
    held_experts: Optional[Tuple[int, int]] = None
    # hand (hidden, head weight) to the criterion, which runs the blocked
    # cross-entropy (ops.fused_cross_entropy) without the [B, S, V] logits
    fused_ce: bool = False

    def __post_init__(self):
        odd = set(self.hybrid_override_pattern) - set("ME*")
        if odd or not self.hybrid_override_pattern:
            raise ValueError(f"hybrid_override_pattern takes M, E and *, "
                             f"got {self.hybrid_override_pattern!r}")
        if self.held_experts is not None:
            self.held_experts = tuple(int(v) for v in self.held_experts)

    @property
    def num_layers(self) -> int:
        return len(self.hybrid_override_pattern)

    @property
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim


def _normal(cfg: NemotronHConfig) -> ParamAttr:
    return ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range))


def _linear(cfg, n_in, n_out) -> Linear:
    return Linear(n_in, n_out, weight_attr=_normal(cfg), bias_attr=False)


class _Conv1dParams(Layer):
    """The depthwise convolution's ``weight [C, K]`` and ``bias [C]``."""

    def __init__(self, cfg, channels):
        super().__init__()
        self.weight = self.create_parameter(
            [channels, cfg.conv_kernel], attr=_normal(cfg))
        self.bias = self.create_parameter([channels], is_bias=True)


@dataclass(frozen=True)
class Mamba2Sizes:
    """What the Mamba-2 mixer's mathematics is sized by."""
    heads: int
    head_dim: int
    groups: int
    state: int
    chunk: int
    eps: float

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim


def _gated(y, z):
    return y * jax.nn.silu(z)


def mamba2_mixer(x, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, norm_w,
                 w_out, sz: Mamba2Sizes, view=None, real=None):
    """The Mamba-2 mixer on arrays, ``x [b, s, hidden]``.  ``view`` None:
    whole sequences from a zero state (training, a plain forward);
    returns the output.  With a serving cache's view of this layer
    (``recurrent_cache.MambaLayerView``) it is one serving step, the s
    positions continuing the view's window and state and ``real [b]`` of
    them real (None: all); returns ``(output, the view after them)``."""
    from ..ops.ssd_scan import causal_conv1d, ssd_scan
    b, s, _ = x.shape
    d_in, heads, p = sz.inner, sz.heads, sz.head_dim
    g, n = sz.groups, sz.state
    f32 = jnp.float32
    with jax.named_scope("mamba_proj"):
        zxbcdt = jnp.matmul(x, w_in)
        z = zxbcdt[..., :d_in]
        xbc = zxbcdt[..., d_in:2 * d_in + 2 * g * n]
        dt = zxbcdt[..., 2 * d_in + 2 * g * n:]
    with jax.named_scope("mamba_conv"):
        if view is None:
            xbc = causal_conv1d(xbc, conv_w, conv_b)
        else:
            xbc, view = view.convolve(xbc, conv_w, conv_b, real)
        xbc = jax.nn.silu(xbc)
        xs = xbc[..., :d_in].reshape(b, s, heads, p)
        b_mat = xbc[..., d_in:d_in + g * n].reshape(b, s, g, n)
        c_mat = xbc[..., d_in + g * n:].reshape(b, s, g, n)
    with jax.named_scope("mamba_gate_norm"):
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    a_neg = -jnp.exp(a_log.astype(f32))
    if view is None:
        y = ssd_scan(xs, dt, a_neg, b_mat, c_mat, chunk=sz.chunk)
    else:
        y, view = view.absorb(xs, dt, a_neg, b_mat, c_mat, real).read()
    with jax.named_scope("mamba_gate_norm"):
        y = y.astype(f32) + d_skip.astype(f32)[:, None] * \
            xs.astype(f32)
        # RMSNorm over groups of d_inner / n_groups channels of
        # y silu(z)
        y = _gated(y.reshape(b, s, d_in), z.astype(f32))
        yg = y.reshape(b, s, g, d_in // g)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                                + sz.eps)
        y = (yg.reshape(b, s, d_in) *
             norm_w.astype(f32)).astype(x.dtype)
    with jax.named_scope("mamba_proj"):
        out = jnp.matmul(y, w_out)
    return out if view is None else (out, view)


class Mamba2Mixer(Layer):
    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        d_in, heads = cfg.mamba_inner, cfg.mamba_num_heads
        bc = 2 * cfg.n_groups * cfg.ssm_state_size
        self.in_proj = _linear(cfg, cfg.hidden_size, 2 * d_in + bc + heads)
        self.conv1d = _Conv1dParams(cfg, d_in + bc)
        self.dt_bias = self.create_parameter([heads], is_bias=True)
        self.A_log = self.create_parameter(
            [heads], default_initializer=I.Constant(0.0))
        self.D = self.create_parameter(
            [heads], default_initializer=I.Constant(1.0))
        self.norm = RMSNorm(d_in, epsilon=cfg.layer_norm_epsilon)
        self.out_proj = _linear(cfg, d_in, cfg.hidden_size)

    def _fn(self, x, *weights):
        cfg = self.cfg
        return mamba2_mixer(x, *weights, Mamba2Sizes(
            cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
            cfg.ssm_state_size, cfg.chunk_size, cfg.layer_norm_epsilon))

    def forward(self, x):
        return apply(self._fn, x, self.in_proj.weight, self.conv1d.weight,
                     self.conv1d.bias, self.dt_bias, self.A_log, self.D,
                     self.norm.weight, self.out_proj.weight,
                     name="mamba2_mixer")


class NemotronHAttention(Layer):
    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        q_dim = cfg.num_attention_heads * cfg.head_dim
        kv_dim = cfg.num_key_value_heads * cfg.head_dim
        self.q_proj = _linear(cfg, cfg.hidden_size, q_dim)
        self.k_proj = _linear(cfg, cfg.hidden_size, kv_dim)
        self.v_proj = _linear(cfg, cfg.hidden_size, kv_dim)
        self.o_proj = _linear(cfg, q_dim, cfg.hidden_size)

    def forward(self, x):
        cfg = self.cfg
        b, s = x.shape[0], x.shape[1]
        h, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        with jax.named_scope("attn_proj"):
            q = self.q_proj(x).reshape([b, s, h, d])
            k = self.k_proj(x).reshape([b, s, hkv, d])
            v = self.v_proj(x).reshape([b, s, hkv, d])
        # GQA goes in un-expanded: the kernel walks kv-head groups (off
        # the chip the entry point expands them for its composite)
        with jax.named_scope("attn_core"):
            out = F.flash_attention(q, k, v, causal=True,
                                    training=self.training)
            out = out.reshape([b, s, h * d])
        with jax.named_scope("attn_proj"):
            return self.o_proj(out)


class NemotronHMLP(Layer):
    """``W_down relu(W_up x)^2``: the shared expert (and an expert's form)."""

    def __init__(self, cfg: NemotronHConfig, width: int):
        super().__init__()
        self.up_proj = _linear(cfg, cfg.hidden_size, width)
        self.down_proj = _linear(cfg, width, cfg.hidden_size)

    @staticmethod
    def _fn(x, w_up, w_down):
        with jax.named_scope("shared_expert"):
            return jnp.matmul(
                jnp.square(jax.nn.relu(jnp.matmul(x, w_up))), w_down)

    def forward(self, x):
        return apply(self._fn, x, self.up_proj.weight, self.down_proj.weight,
                     name="shared_expert")


class NemotronHMoE(Layer):
    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        from ..distributed.moe import MoELayer
        self.routed = MoELayer(
            cfg.hidden_size, cfg.moe_intermediate_size,
            num_experts=cfg.n_routed_experts, top_k=cfg.num_experts_per_tok,
            capacity_factor=None, normalize_gates=cfg.norm_topk_prob,
            routed_scaling=cfg.routed_scaling_factor,
            held_experts=cfg.held_experts, activation="relu2",
            weight_attr=_normal(cfg))
        self.shared_experts = NemotronHMLP(
            cfg, cfg.moe_shared_expert_intermediate_size)

    def forward(self, x):
        return self.routed(x) + self.shared_experts(x)


_MIXERS = {"M": Mamba2Mixer, "*": NemotronHAttention, "E": NemotronHMoE}
# a block's named scope: its pre-norm, its mixer and the residual add
_BLOCK_SCOPES = {"M": "mamba", "*": "attn", "E": "moe"}


class NemotronHBlock(Layer):
    def __init__(self, cfg: NemotronHConfig, kind: str):
        super().__init__()
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        self.mixer = _MIXERS[kind](cfg)
        self.scope = _BLOCK_SCOPES[kind]

    def forward(self, x):
        with jax.named_scope(self.scope):
            return x + self.mixer(self.norm(x))


class NemotronHModel(Layer):
    """Embedding, the pattern's layers, final norm: hidden states."""

    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = Embedding(cfg.vocab_size, cfg.hidden_size,
                                    weight_attr=_normal(cfg))
        self.layers = LayerList([NemotronHBlock(cfg, kind)
                                 for kind in cfg.hybrid_override_pattern])
        self.norm_f = RMSNorm(cfg.hidden_size,
                              epsilon=cfg.layer_norm_epsilon)
        self._recompute = False
        self._recompute_policy = None

    def enable_recompute(self, policy=None):
        """strategy.recompute hook: remat every layer (applied in
        forward, so parameter names are unchanged)."""
        self._recompute = True
        self._recompute_policy = policy
        return self

    def forward(self, input_ids):
        from ..distributed.recompute import recompute
        with jax.named_scope("embed"):
            x = self.embeddings(input_ids)
        for layer in self.layers:
            if self._recompute and self.training:
                x = recompute(layer, x, policy=self._recompute_policy)
            else:
                x = layer(x)
        with jax.named_scope("head_ce"):
            return self.norm_f(x)


class _Head(Layer):
    """The untied head's ``weight [V, H]``."""

    def __init__(self, cfg):
        super().__init__()
        self.weight = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], attr=_normal(cfg))


class NemotronHForCausalLM(Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.cfg = config
        self.backbone = NemotronHModel(config)
        self.lm_head = _Head(config)

    def enable_recompute(self, policy=None):
        self.backbone.enable_recompute(policy=policy)
        return self

    def forward(self, input_ids):
        x = self.backbone(input_ids)
        if self.cfg.fused_ce and self.training:
            # the criterion projects vocabulary block by block
            return x, self.lm_head.weight
        with jax.named_scope("head_ce"):
            return apply(lambda h, w: jnp.matmul(h, w.T), x,
                         self.lm_head.weight, name="lm_head")
