"""Plain reference of the Brumby block (Manifest AI, Brumby-14B-Base: the
Qwen3-14B block with every attention layer replaced by power retention,
arXiv:2507.04239) in its ATTENTION form: float32 ``jax.numpy`` at
``highest`` matmul precision, the whole sequence at once, every score
written out.  No state, no chunks, no ``phi``, no kernel, no cache, none
of the program's code and none of its arrays.

For a layer, with ``x`` the residual stream ``[S, hidden]`` and ``d`` the
head width:

    u = RMSNorm(x);  q = RoPE(RMSNorm_q(u W_q)), k = RoPE(RMSNorm_k(u W_k)),
    v = u W_v  (H query heads on Hkv KV heads, h reads j = h // (H / Hkv))
    gamma = log sigmoid(u W_g + b_g + gate_bias_shift)         [S, Hkv]
    a[t, s] = exp(sum_{r=s+1..t} gamma_r) * (q_t . k_s / sqrt(d)) ** 2, s <= t
    y_t = sum_s a[t, s] v_s / (sum_s a[t, s] + eps)
    x = x + concat_h(y) W_o;  x = x + (silu(w W_gate) * (w W_up)) W_down,
    w = RMSNorm(x)

then a final RMSNorm and an untied head.  RoPE is the rotate-half form at
``rope_theta``, angles from the positions, no table.

The weights arrive in bf16 (8.4 GB at the cell's size) and a float32 copy
would not fit beside them, so ``stack`` keeps the leaves as they are and
``logits`` upcasts ONE LAYER at a time, works the scores by blocks of
query rows and the head by blocks of the vocabulary, and hands the logits
back on the host.

``precision="fp8"`` is the control of the correctness check: every
matmul operand rounded to e4m3 with one scale a tensor before a float32
product, the precision step below the configuration's bf16.
"""
from __future__ import annotations

import functools

import numpy as np

LAYER_LEAVES = {
    "input_layernorm.weight": lambda m: (m["hidden_size"],),
    "self_attn.q_proj.weight": lambda m: (
        m["hidden_size"], m["num_attention_heads"] * m["head_dim"]),
    "self_attn.k_proj.weight": lambda m: (
        m["hidden_size"], m["num_key_value_heads"] * m["head_dim"]),
    "self_attn.v_proj.weight": lambda m: (
        m["hidden_size"], m["num_key_value_heads"] * m["head_dim"]),
    "self_attn.o_proj.weight": lambda m: (
        m["num_attention_heads"] * m["head_dim"], m["hidden_size"]),
    "self_attn.q_norm.weight": lambda m: (m["head_dim"],),
    "self_attn.k_norm.weight": lambda m: (m["head_dim"],),
    "self_attn.g_proj.weight": lambda m: (
        m["hidden_size"], m["num_key_value_heads"]),
    "self_attn.g_proj.bias": lambda m: (m["num_key_value_heads"],),
    "post_attention_layernorm.weight": lambda m: (m["hidden_size"],),
    "mlp.gate_proj.weight": lambda m: (
        m["hidden_size"], m["intermediate_size"]),
    "mlp.up_proj.weight": lambda m: (
        m["hidden_size"], m["intermediate_size"]),
    "mlp.down_proj.weight": lambda m: (
        m["intermediate_size"], m["hidden_size"]),
}
QUERY_ROWS = 512            # rows of scores worked at once
VOCAB_BLOCK = 16384         # columns of the head worked at once


def param_spec(m: dict) -> dict:
    """name -> shape, in the checkpoint's naming (linear maps stored
    ``[in, out]``)."""
    spec = {"model.embed_tokens.weight": (m["vocab_size"], m["hidden_size"]),
            "model.norm.weight": (m["hidden_size"],),
            "lm_head.weight": (m["hidden_size"], m["vocab_size"])}
    for i in range(m["num_hidden_layers"]):
        for leaf, shape in LAYER_LEAVES.items():
            spec[f"model.layers.{i}.{leaf}"] = shape(m)
    return spec


def num_params(m: dict) -> int:
    return sum(int(np.prod(s)) for s in param_spec(m).values())


def state_bytes_per_slot(m: dict) -> int:
    """The mathematics' own recurrent state of one sequence, all layers:
    a float32 ``[d (d + 1) / 2, d + 1]`` a KV head (the symmetric degree-2
    expansion of the key beside the value and the normaliser)."""
    d = m["head_dim"]
    return m["num_hidden_layers"] * m["num_key_value_heads"] * \
        (d * (d + 1) // 2) * (d + 1) * 4


def stack(flat: dict, m: dict) -> dict:
    """The flat leaves as one tree, layer by layer, in the dtype they
    came in (nothing is copied)."""
    return {"embed": flat["model.embed_tokens.weight"],
            "norm": flat["model.norm.weight"], "head": flat["lm_head.weight"],
            "layers": [{leaf: flat[f"model.layers.{i}.{leaf}"]
                        for leaf in LAYER_LEAVES}
                       for i in range(m["num_hidden_layers"])]}


def _matmul(precision: str):
    """(matmul, einsum) at highest precision, their operands rounded
    first as `precision` says."""
    import jax
    import jax.numpy as jnp
    if precision == "float32":
        q = lambda x: x
    elif precision == "fp8":
        def q(x):
            scale = jnp.max(jnp.abs(x)) / 448.0
            scale = jnp.where(scale > 0, scale, 1.0)
            return (x / scale).astype(jnp.float8_e4m3fn).astype(
                jnp.float32) * scale
    else:
        raise ValueError(f"reference precision {precision!r}")
    top = jax.lax.Precision.HIGHEST
    return (lambda a, b: jnp.matmul(q(a), q(b), precision=top),
            lambda spec, a, b: jnp.einsum(spec, q(a), q(b), precision=top))


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """``x [S, heads, d]`` at positions 0..S-1, rotate-half."""
    import jax.numpy as jnp
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + turned * sin


def retention(q, k, v, gamma, eps, ein):
    """The attention form: ``q [S, H, d]``, ``k``/``v [S, Hkv, d]``,
    ``gamma [S, Hkv]`` -> ``[S, H, d]``, by blocks of query rows."""
    import jax.numpy as jnp
    s, h, d = q.shape
    hkv = k.shape[1]
    q = q.reshape(s, hkv, h // hkv, d)
    cum = jnp.cumsum(gamma, axis=0).T                  # [Hkv, S]
    rows = []
    for lo in range(0, s, QUERY_ROWS):
        hi = min(lo + QUERY_ROWS, s)
        seen = jnp.arange(lo, hi)[:, None] >= jnp.arange(s)[None, :]
        decay = jnp.where(seen, cum[:, lo:hi, None] - cum[:, None, :],
                          -jnp.inf)                    # [Hkv, rows, S]
        score = ein("tjgd,sjd->jgts", q[lo:hi], k) / jnp.sqrt(jnp.float32(d))
        a = jnp.exp(decay)[:, None] * score * score    # [Hkv, G, rows, S]
        y = ein("jgts,sjd->tjgd", a, v) / \
            (a.sum(-1).transpose(2, 0, 1)[..., None] + eps)
        rows.append(y.reshape(hi - lo, h, d))
    return jnp.concatenate(rows, axis=0)


def _layer(m: dict, precision: str):
    import jax
    import jax.numpy as jnp
    mm, ein = _matmul(precision)
    h, hkv, d = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    eps = m["rms_norm_eps"]

    def layer(x, leaves):
        p = {name: w.astype(jnp.float32) for name, w in leaves.items()}
        s = x.shape[0]
        u = _rms(x, p["input_layernorm.weight"], eps)
        q = _rms(mm(u, p["self_attn.q_proj.weight"]).reshape(s, h, d),
                 p["self_attn.q_norm.weight"], eps)
        k = _rms(mm(u, p["self_attn.k_proj.weight"]).reshape(s, hkv, d),
                 p["self_attn.k_norm.weight"], eps)
        v = mm(u, p["self_attn.v_proj.weight"]).reshape(s, hkv, d)
        q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
        gamma = jax.nn.log_sigmoid(
            mm(u, p["self_attn.g_proj.weight"]) + p["self_attn.g_proj.bias"]
            + m.get("gate_bias_shift", 0.0))
        y = retention(q, k, v, gamma, m.get("retention_eps", 1e-6), ein)
        x = x + mm(y.reshape(s, h * d), p["self_attn.o_proj.weight"])
        w = _rms(x, p["post_attention_layernorm.weight"], eps)
        gate = mm(w, p["mlp.gate_proj.weight"])
        return x + mm(jax.nn.silu(gate) * mm(w, p["mlp.up_proj.weight"]),
                      p["mlp.down_proj.weight"])

    return layer


@functools.lru_cache(maxsize=4)
def _programs(model_items: tuple, precision: str):
    import jax
    import jax.numpy as jnp
    m = dict(model_items)
    mm, _ = _matmul(precision)

    def embed(table, ids):
        return table[ids].astype(jnp.float32)

    def head(x, norm, block):
        return mm(_rms(x, norm.astype(jnp.float32), m["rms_norm_eps"]),
                  block.astype(jnp.float32))

    return jax.jit(embed), jax.jit(_layer(m, precision)), jax.jit(head)


def _hashable(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


def logits(m: dict, stacked: dict, ids, precision: str = "float32"):
    """``ids [S]`` -> logits ``[S, V]`` float32, ON THE HOST (numpy)."""
    import jax
    import jax.numpy as jnp
    embed, layer, head = _programs(_hashable(m), precision)
    with jax.default_matmul_precision("highest"):
        x = embed(stacked["embed"], jnp.asarray(ids, jnp.int32))
        for leaves in stacked["layers"]:
            x = layer(x, leaves)
        vocab = m["vocab_size"]
        out = np.empty((x.shape[0], vocab), np.float32)
        for lo in range(0, vocab, VOCAB_BLOCK):
            hi = min(lo + VOCAB_BLOCK, vocab)
            out[:, lo:hi] = np.asarray(
                head(x, stacked["norm"], stacked["head"][:, lo:hi]))
    return out
