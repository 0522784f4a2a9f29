"""Kimi-Linear: a hybrid stack of Kimi Delta Attention (KDA) and latent
attention (MLA) layers over dense and mixture-of-experts feed-forwards.

``x = embed(ids)``; for layer ``l = 1..L``: ``x = x + Mixer_l(RMSNorm(x))``,
then ``x = x + FFN_l(RMSNorm(x))``; then ``RMSNorm`` and an untied head.
No bias in any linear map.  (``model_type`` ``kimi_linear``; the
configuration keys keep the published names, ``linear_attn_config``
included; layers are numbered from 1 as its lists number them.)

- KDA (layers in ``linear_attn_config.kda_layers``): ``q, k, v = silu(
  conv4(x W))``, q and k l2-normalised over a head's channels, q scaled
  by ``head_dim ** -0.5``; a log-decay per head AND key channel ``g =
  -exp(A_log) softplus((x W_fa) W_fb + dt_bias)``; ``beta = sigmoid(x
  W_b)``; the delta rule ``S_t = (I - beta k k^T) Diag(exp(g)) S_{t-1} +
  beta k v^T``, ``o_t = S_t^T q_t`` runs as ``ops.kda_scan`` (chunked);
  then a per-head RMSNorm of ``o`` times ``sigmoid((x W_ga) W_gb)`` and
  ``W_o``.
- MLA (layers in ``linear_attn_config.full_attn_layers``): ``q = x W_q``
  at ``qk_nope_head_dim + qk_rope_head_dim`` a head; ``[c | k_pe] = x
  W_kva``; ``[k_nope | v] = RMSNorm(c) W_kvb``; ``k = [k_nope | k_pe]``
  with ``k_pe`` shared by the heads; causal attention through the flash
  kernel at its two widths (192 for the scores, 128 for the values).
  ``mla_use_nope``: NO rotary embedding is applied, the "rope" channels
  are plain channels.
- Feed-forward: the first ``first_k_dense_replace`` layers a SwiGLU MLP
  of ``intermediate_size``; every later layer ``distributed.moe.MoELayer``
  on its dropless path (sigmoid scores over all ``num_experts``, top
  ``num_experts_per_token`` by score + correction bias, renormalised,
  times ``routed_scaling_factor``; gated SwiGLU experts) holding
  ``held_experts`` of them, plus a shared SwiGLU expert that every token
  passes.

The stack is heterogeneous, so it is unrolled; ``enable_recompute``
remats each layer, mixer and feed-forward one by one (there is no
``enable_scan_layers``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.autograd import apply
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer_base import Layer
from ..nn.layer.common import Embedding
from ..nn.layer.container import LayerList
from ..nn.layer.norm import RMSNorm
# the bias-free linear map, its normal init and the untied head are the
# other hybrid stack's
from .nemotron_h import _Head, _linear, _normal

__all__ = ["KimiLinearConfig", "KimiLinearModel", "KimiLinearForCausalLM"]

_F32 = jnp.float32


def _published_linear_attn() -> dict:
    full = [4, 8, 12, 16, 20, 24, 27]
    return {"full_attn_layers": full, "head_dim": 128,
            "kda_layers": [i for i in range(1, 28) if i not in full],
            "num_heads": 32, "short_conv_kernel_size": 4}


@dataclass
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    num_hidden_layers: int = 27
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    max_seq_len: int = 8192
    # which layer mixes how, and KDA's sizes (layers counted from 1; the
    # lists may run past num_hidden_layers, as a cut stack's do)
    linear_attn_config: dict = field(default_factory=_published_linear_attn)
    kda_chunk_size: int = 64
    # latent attention
    num_attention_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    # feed-forward
    intermediate_size: int = 9216
    first_k_dense_replace: int = 1
    num_experts: int = 256
    num_experts_per_token: int = 8
    moe_intermediate_size: int = 1024
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    moe_renormalize: bool = True
    # this chip's share of the routed experts, [lo, hi); None holds all
    held_experts: Optional[Tuple[int, int]] = None
    # hand (hidden, head weight) to the criterion, which runs the blocked
    # cross-entropy (ops.fused_cross_entropy) without the [B, S, V] logits
    fused_ce: bool = False

    def __post_init__(self):
        if self.held_experts is not None:
            self.held_experts = tuple(int(v) for v in self.held_experts)
        for i in range(1, self.num_hidden_layers + 1):
            self.mixer_kind(i)

    def mixer_kind(self, layer: int) -> str:
        """'kda' or 'mla' for layer `layer` (from 1)."""
        la = self.linear_attn_config
        kinds = [kind for kind, key in (("kda", "kda_layers"),
                                        ("mla", "full_attn_layers"))
                 if layer in la[key]]
        if len(kinds) != 1:
            raise ValueError(f"layer {layer} is in {len(kinds)} of "
                             f"linear_attn_config's two lists")
        return kinds[0]

    def ffn_kind(self, layer: int) -> str:
        return "dense" if layer <= self.first_k_dense_replace else "moe"

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


class _ConvWeight(Layer):
    """A depthwise causal convolution's ``weight [C, K]`` (no bias)."""

    def __init__(self, cfg, channels, taps):
        super().__init__()
        self.weight = self.create_parameter([channels, taps],
                                            attr=_normal(cfg))


def _rms(x, weight, eps):
    """RMSNorm over the last axis in float32."""
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        weight.astype(_F32)


class KDAMixer(Layer):
    L2_EPS = 1e-6
    PAIRS_AT_ONCE = 8       # (row, head) pairs a rematerialised group

    def __init__(self, cfg: KimiLinearConfig):
        super().__init__()
        self.cfg = cfg
        la = cfg.linear_attn_config
        self.heads, self.head_dim = la["num_heads"], la["head_dim"]
        d, inner = cfg.hidden_size, self.heads * self.head_dim
        taps = la["short_conv_kernel_size"]
        self.q_proj = _linear(cfg, d, inner)
        self.k_proj = _linear(cfg, d, inner)
        self.v_proj = _linear(cfg, d, inner)
        self.q_conv1d = _ConvWeight(cfg, inner, taps)
        self.k_conv1d = _ConvWeight(cfg, inner, taps)
        self.v_conv1d = _ConvWeight(cfg, inner, taps)
        self.f_a_proj = _linear(cfg, d, self.head_dim)
        self.f_b_proj = _linear(cfg, self.head_dim, inner)
        self.A_log = self.create_parameter(
            [self.heads], default_initializer=I.Constant(0.0))
        self.dt_bias = self.create_parameter([inner], is_bias=True)
        self.b_proj = _linear(cfg, d, self.heads)
        self.o_norm = RMSNorm(self.head_dim, epsilon=cfg.rms_norm_eps)
        self.g_a_proj = _linear(cfg, d, self.head_dim)
        self.g_b_proj = _linear(cfg, self.head_dim, inner)
        self.o_proj = _linear(cfg, inner, d)

    def _mix_group(self, q, k, v, f, b, gate, c_q, c_k, c_v, a_log, dt_bias,
                   norm_w):
        """A group of heads from the projections' outputs to the gated
        output: ``q``/``k``/``v``/``f``/``gate [b, s, n d]`` and ``b [b, s,
        n]`` of ``n`` heads, the convolutions' rows, ``A_log`` and
        ``dt_bias`` of those heads -> ``[b, s, n d]``."""
        from ..ops.kda_scan import kda_scan
        from ..ops.ssd_scan import causal_conv1d
        bsz, s, _ = q.shape
        d = self.head_dim
        heads = lambda t: t.reshape(bsz, s, -1, d)

        def unit(t):                    # l2 norm over a head's channels
            t = heads(t).astype(_F32)
            return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) +
                                     self.L2_EPS)

        with jax.named_scope("kda_conv"):
            q, k, v = (jax.nn.silu(causal_conv1d(t, c))
                       for t, c in ((q, c_q), (k, c_k), (v, c_v)))
        with jax.named_scope("kda_gates"):
            dtype = v.dtype
            q = (unit(q) * d ** -0.5).astype(dtype)
            k = unit(k).astype(dtype)
            g = -jnp.exp(a_log.astype(_F32))[:, None] * heads(
                jax.nn.softplus(f.astype(_F32) + dt_bias.astype(_F32)))
            beta = jax.nn.sigmoid(b.astype(_F32))
        o = kda_scan(q, k, heads(v), g, beta, chunk=self.cfg.kda_chunk_size)
        with jax.named_scope("kda_gates"):
            gate = jax.nn.sigmoid(heads(gate).astype(_F32))
            return (_rms(o, norm_w, self.cfg.rms_norm_eps) * gate).astype(
                dtype).reshape(bsz, s, -1)

    def _fn(self, x, w_q, w_k, w_v, c_q, c_k, c_v, w_fa, w_fb, a_log,
            dt_bias, w_b, norm_w, w_ga, w_gb, w_o):
        bsz, h, d = x.shape[0], self.heads, self.head_dim
        with jax.named_scope("kda_proj"):
            q, k, v = (jnp.matmul(x, w) for w in (w_q, w_k, w_v))
        with jax.named_scope("kda_gates"):
            f = jnp.matmul(jnp.matmul(x, w_fa), w_fb)
            b = jnp.matmul(x, w_b)
            gate = jnp.matmul(jnp.matmul(x, w_ga), w_gb)
        # Heads are independent from here to the output projection, and
        # the scan's backward keeps 0.15 GiB a (row, head) pair at 8192
        # positions: groups of heads go one after another, each
        # rematerialised, so the backward keeps the projections' outputs
        # and one group's intermediates.  A group's rows of W_o are
        # applied inside its step and summed in float32, so that nothing
        # outside the steps needs the scan's output: the layer's own remat
        # then has no scan to run again.  What the scope kda_groups
        # holds beside the finer scopes inside it is that loop's price:
        # regrouping copies, slices and stacking.
        with jax.named_scope("kda_groups"):
            n = max(m for m in range(1, h + 1) if h % m == 0 and
                    m * bsz <= max(self.PAIRS_AT_ONCE, bsz))
            # [.., h c] -> [h / n, .., n c]
            by_group = lambda t, c: jnp.moveaxis(
                t.reshape(t.shape[:-1] + (h // n, n * c)), -2, 0)
            # [h d, .] by head
            rows = lambda t: t.reshape(h // n, n * d, -1)

            def add_group(out, group):
                *inputs, w_o_rows = group
                y = self._mix_group(*inputs, norm_w)
                with jax.named_scope("kda_proj"):
                    return out + jnp.matmul(
                        y, w_o_rows, preferred_element_type=_F32), None

            out, _ = jax.lax.scan(
                jax.checkpoint(add_group), jnp.zeros(x.shape, _F32),
                (by_group(q, d), by_group(k, d), by_group(v, d),
                 by_group(f, d), by_group(b, 1), by_group(gate, d),
                 rows(c_q), rows(c_k), rows(c_v),
                 a_log.reshape(h // n, n), by_group(dt_bias, d), rows(w_o)))
        return out.astype(x.dtype)

    def forward(self, x):
        return apply(
            self._fn, x, self.q_proj.weight, self.k_proj.weight,
            self.v_proj.weight, self.q_conv1d.weight, self.k_conv1d.weight,
            self.v_conv1d.weight, self.f_a_proj.weight, self.f_b_proj.weight,
            self.A_log, self.dt_bias, self.b_proj.weight, self.o_norm.weight,
            self.g_a_proj.weight, self.g_b_proj.weight, self.o_proj.weight,
            name="kda_mixer")


class MLAttention(Layer):
    def __init__(self, cfg: KimiLinearConfig):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.num_attention_heads, cfg.hidden_size
        self.q_proj = _linear(cfg, d, h * cfg.qk_head_dim)
        self.kv_a_proj_with_mqa = _linear(
            cfg, d, cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        self.kv_a_layernorm = RMSNorm(cfg.kv_lora_rank,
                                      epsilon=cfg.rms_norm_eps)
        self.kv_b_proj = _linear(
            cfg, cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim +
                                        cfg.v_head_dim))
        self.o_proj = _linear(cfg, h * cfg.v_head_dim, d)

    def _qkv(self, x, w_q, w_kva, norm_w, w_kvb):
        cfg = self.cfg
        b, s, _ = x.shape
        h, nope, rank = cfg.num_attention_heads, cfg.qk_nope_head_dim, \
            cfg.kv_lora_rank
        with jax.named_scope("mla_proj"):
            q = jnp.matmul(x, w_q).reshape(b, s, h, cfg.qk_head_dim)
            kva = jnp.matmul(x, w_kva)
            c = _rms(kva[..., :rank], norm_w, cfg.rms_norm_eps).astype(
                x.dtype)
            kv = jnp.matmul(c, w_kvb).reshape(b, s, h,
                                              nope + cfg.v_head_dim)
            k_pe = jnp.broadcast_to(kva[:, :, None, rank:],
                                    (b, s, h, cfg.qk_rope_head_dim))
            k = jnp.concatenate([kv[..., :nope], k_pe], axis=-1)
            return q, k, kv[..., nope:]

    def forward(self, x):
        cfg = self.cfg
        q, k, v = apply(self._qkv, x, self.q_proj.weight,
                        self.kv_a_proj_with_mqa.weight,
                        self.kv_a_layernorm.weight, self.kv_b_proj.weight,
                        name="mla_qkv")
        # the scores run over 192 channels, the values over 128: the
        # kernel takes both widths as they are (no padding to 256)
        with jax.named_scope("mla_attn"):
            out = F.flash_attention(q, k, v, causal=True,
                                    training=self.training)
        with jax.named_scope("mla_proj"):
            return self.o_proj(out.reshape(
                [x.shape[0], x.shape[1],
                 cfg.num_attention_heads * cfg.v_head_dim]))


class KimiMLP(Layer):
    """``W_down (silu(x W_gate) * (x W_up))``: the leading dense layers'
    feed-forward and the shared expert (`scope` names it in a trace)."""

    def __init__(self, cfg: KimiLinearConfig, width: int, scope: str):
        super().__init__()
        self.scope = scope
        self.gate_proj = _linear(cfg, cfg.hidden_size, width)
        self.up_proj = _linear(cfg, cfg.hidden_size, width)
        self.down_proj = _linear(cfg, width, cfg.hidden_size)

    def _fn(self, x, w_gate, w_up, w_down):
        with jax.named_scope(self.scope):
            return jnp.matmul(jax.nn.silu(jnp.matmul(x, w_gate)) *
                              jnp.matmul(x, w_up), w_down)

    def forward(self, x):
        return apply(self._fn, x, self.gate_proj.weight, self.up_proj.weight,
                     self.down_proj.weight, name=self.scope)


class KimiMoE(Layer):
    def __init__(self, cfg: KimiLinearConfig):
        super().__init__()
        from ..distributed.moe import MoELayer
        self.routed = MoELayer(
            cfg.hidden_size, cfg.moe_intermediate_size,
            num_experts=cfg.num_experts, top_k=cfg.num_experts_per_token,
            capacity_factor=None, normalize_gates=cfg.moe_renormalize,
            routed_scaling=cfg.routed_scaling_factor,
            held_experts=cfg.held_experts, activation="swiglu",
            weight_attr=_normal(cfg))
        self.shared_experts = KimiMLP(
            cfg, cfg.num_shared_experts * cfg.moe_intermediate_size,
            "shared_expert")

    def forward(self, x):
        return self.routed(x) + self.shared_experts(x)


class KimiDecoderLayer(Layer):
    def __init__(self, cfg: KimiLinearConfig, layer: int):
        super().__init__()
        eps = cfg.rms_norm_eps
        self.input_layernorm = RMSNorm(cfg.hidden_size, epsilon=eps)
        self.self_attn = {"kda": KDAMixer, "mla": MLAttention}[
            cfg.mixer_kind(layer)](cfg)
        self.post_attention_layernorm = RMSNorm(cfg.hidden_size, epsilon=eps)
        self.mlp = KimiMLP(cfg, cfg.intermediate_size, "dense_mlp") \
            if cfg.ffn_kind(layer) == "dense" else KimiMoE(cfg)
        # the two sub-layers' named scopes, each over its pre-norm, the
        # sub-layer and the residual add
        self.scopes = (cfg.mixer_kind(layer),
                       "dense_mlp" if cfg.ffn_kind(layer) == "dense"
                       else "moe")

    PARTS = ("mixer", "ffn")

    def forward(self, x, part=None):
        """Both sub-layers, or the one `part` names (the model remats
        them one by one)."""
        if part != "ffn":
            with jax.named_scope(self.scopes[0]):
                x = x + self.self_attn(self.input_layernorm(x))
        if part != "mixer":
            with jax.named_scope(self.scopes[1]):
                x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class KimiLinearModel(Layer):
    """Embedding, the layers, final norm: hidden states."""

    def __init__(self, cfg: KimiLinearConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = Embedding(cfg.vocab_size, cfg.hidden_size,
                                      weight_attr=_normal(cfg))
        self.layers = LayerList([
            KimiDecoderLayer(cfg, i)
            for i in range(1, cfg.num_hidden_layers + 1)])
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self._recompute = False
        self._recompute_policy = None

    def enable_recompute(self, policy=None):
        """strategy.recompute hook: remat every layer, its two sub-layers
        one by one, so that the backward holds a mixer's intermediates or
        a feed-forward's and never both (applied in forward, so parameter
        names are unchanged)."""
        self._recompute = True
        self._recompute_policy = policy
        return self

    def forward(self, input_ids):
        from ..distributed.recompute import recompute
        with jax.named_scope("embed"):
            x = self.embed_tokens(input_ids)
        for layer in self.layers:
            if self._recompute and self.training:
                for part in layer.PARTS:
                    x = recompute(layer, x, policy=self._recompute_policy,
                                  part=part)
            else:
                x = layer(x)
        with jax.named_scope("head_ce"):
            return self.norm(x)


class KimiLinearForCausalLM(Layer):
    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        self.cfg = config
        self.model = KimiLinearModel(config)
        self.lm_head = _Head(config)

    def enable_recompute(self, policy=None):
        self.model.enable_recompute(policy=policy)
        return self

    def forward(self, input_ids):
        x = self.model(input_ids)
        if self.cfg.fused_ce and self.training:
            # the criterion projects vocabulary block by block
            return x, self.lm_head.weight
        with jax.named_scope("head_ce"):
            return apply(lambda h, w: jnp.matmul(h, w.T), x,
                         self.lm_head.weight, name="lm_head")
