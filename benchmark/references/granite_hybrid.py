"""Plain reference of the Granite 4.0-H block (IBM, ``model_type``
``granitemoehybrid`` with no experts; HF ``GraniteMoeHybrid``, whose
Mamba layer is Bamba's Mamba-2 mixer): float32 ``jax.numpy`` at
``highest`` matmul precision, one sequence at once.  No chunks, no cache,
no kernel, none of the program's code and none of its arrays.

    h = E[ids] * embedding_multiplier
    for each layer i:
        u = RMSNorm_in(h)
        h = h + residual_multiplier * (Mamba2(u) | Attention(u))   layer_types[i]
        v = RMSNorm_post(h);  [g | w] = v W_in
        h = h + residual_multiplier * (silu(g) * w) W_out          shared_mlp
    logits = RMSNorm_f(h) E^T / logits_scaling                     tied head

    Mamba2(u):  [z | xBC | dt] = u W_in
        xBC = silu(conv1d_depthwise(xBC, mamba_d_conv taps, causal) + bias)
        x [H, P], B [G, N], C [G, N] = split(xBC)
        dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
        out = (RMSNorm_groups(y * silu(z)) * w_norm) W_out
    Attention(u): q H heads, k/v Hkv heads of hidden / H, no bias, no
        rotary; softmax(q k^T * attention_multiplier) v, causal.

The recurrence is SEQUENTIAL, one ``lax.scan`` step a position: it shares
nothing with the program's chunked dual or its single-token step.
Attention is a masked softmax by blocks of query rows.

The weights arrive in bf16 (6.4 GB at the cell's size), so ``stack`` keeps
the leaves as they are, ``logits`` upcasts ONE LAYER at a time and works
the head by blocks of the vocabulary, and hands the logits back on the
host.

Departures from the published description, each also in the
configuration's ``assumed``: linear weights stored ``[in, out]`` and the
convolution's ``[channels, taps]``; no clamp on ``dt`` (the published
``time_step_limit`` is (0, inf)); the gated norm's groups are
``mamba_n_groups`` (one: the norm runs over all ``H P`` channels).

``precision="fp8"`` is the control of the correctness check: every
matmul operand (the projections, attention's two products, the head)
rounded to e4m3 with one scale a tensor before a float32 product, the
precision step below the configuration's bf16; the recurrence and the
convolution stay float32.
"""
from __future__ import annotations

import functools

import numpy as np

from .brumby import _hashable, _matmul, _rms

QUERY_ROWS = 512            # rows of scores worked at once
VOCAB_BLOCK = 16384         # columns of the head worked at once


def _sizes(m: dict) -> dict:
    d_inner = m["mamba_n_heads"] * m["mamba_d_head"]
    bc = 2 * m["mamba_n_groups"] * m["mamba_d_state"]
    return {"d_inner": d_inner, "conv": d_inner + bc,
            "head_dim": m["hidden_size"] // m["num_attention_heads"]}


def layer_spec(m: dict, kind: str) -> dict:
    d, z = m["hidden_size"], _sizes(m)
    if kind == "mamba":
        h = m["mamba_n_heads"]
        mixer = {"mamba.in_proj.weight": (d, z["d_inner"] + z["conv"] + h),
                 "mamba.conv1d.weight": (z["conv"], m["mamba_d_conv"]),
                 "mamba.conv1d.bias": (z["conv"],),
                 "mamba.dt_bias": (h,), "mamba.A_log": (h,),
                 "mamba.D": (h,), "mamba.norm.weight": (z["d_inner"],),
                 "mamba.out_proj.weight": (z["d_inner"], d)}
    else:
        q = m["num_attention_heads"] * z["head_dim"]
        kv = m["num_key_value_heads"] * z["head_dim"]
        mixer = {"self_attn.q_proj.weight": (d, q),
                 "self_attn.k_proj.weight": (d, kv),
                 "self_attn.v_proj.weight": (d, kv),
                 "self_attn.o_proj.weight": (q, d)}
    f = m["shared_intermediate_size"]
    return {"input_layernorm.weight": (d,), **mixer,
            "post_attention_layernorm.weight": (d,),
            "shared_mlp.input_linear.weight": (d, 2 * f),
            "shared_mlp.output_linear.weight": (f, d)}


def param_spec(m: dict) -> dict:
    """name -> shape, in the checkpoint's naming (linear maps stored
    ``[in, out]``); the head is the embedding."""
    spec = {"model.embed_tokens.weight": (m["vocab_size"], m["hidden_size"]),
            "model.norm.weight": (m["hidden_size"],)}
    for i, kind in enumerate(m["layer_types"]):
        for leaf, shape in layer_spec(m, kind).items():
            spec[f"model.layers.{i}.{leaf}"] = shape
    return spec


def num_params(m: dict) -> int:
    return sum(int(np.prod(s)) for s in param_spec(m).values())


def state_bytes_per_slot(m: dict) -> int:
    """The mathematics' own recurrent state of one sequence, all Mamba
    layers: a float32 ``[H, P, N]`` a layer."""
    return list(m["layer_types"]).count("mamba") * m["mamba_n_heads"] * \
        m["mamba_d_head"] * m["mamba_d_state"] * 4


def stack(flat: dict, m: dict) -> dict:
    """The flat leaves as one tree, layer by layer, in the dtype they
    came in (nothing is copied)."""
    return {"embed": flat["model.embed_tokens.weight"],
            "norm": flat["model.norm.weight"],
            "layers": [{leaf: flat[f"model.layers.{i}.{leaf}"]
                        for leaf in layer_spec(m, kind)}
                       for i, kind in enumerate(m["layer_types"])]}


def recurrence(xs, dt, a_neg, b_mat, c_mat):
    """Position by position: ``xs [S, H, P]``, ``dt [S, H]``, ``a_neg
    [H]``, ``b_mat``/``c_mat [S, G, N]`` -> ``[S, H, P]`` (no D skip)."""
    import jax
    import jax.numpy as jnp
    _, h, p = xs.shape
    g, n = b_mat.shape[1:]

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        b_h = jnp.repeat(b_t, h // g, axis=0)               # [H, N]
        c_h = jnp.repeat(c_t, h // g, axis=0)
        state = jnp.exp(dt_t * a_neg)[:, None, None] * state + \
            (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        return state, jnp.sum(state * c_h[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32),
                        (xs, dt, b_mat, c_mat))
    return y


def mamba2(m: dict, u, p: dict, mm):
    import jax
    import jax.numpy as jnp
    z_ = _sizes(m)
    s = u.shape[0]
    d_in, h, hp = z_["d_inner"], m["mamba_n_heads"], m["mamba_d_head"]
    g, n, k = m["mamba_n_groups"], m["mamba_d_state"], m["mamba_d_conv"]
    zxbcdt = mm(u, p["mamba.in_proj.weight"])
    z, xbc, dt = (zxbcdt[:, :d_in], zxbcdt[:, d_in:d_in + z_["conv"]],
                  zxbcdt[:, d_in + z_["conv"]:])
    padded = jnp.pad(xbc, [(k - 1, 0), (0, 0)])
    w = p["mamba.conv1d.weight"]
    xbc = p["mamba.conv1d.bias"] + sum(
        padded[j:j + s] * w[:, j] for j in range(k))
    xbc = jax.nn.silu(xbc)
    xs = xbc[:, :d_in].reshape(s, h, hp)
    b_mat = xbc[:, d_in:d_in + g * n].reshape(s, g, n)
    c_mat = xbc[:, d_in + g * n:].reshape(s, g, n)
    dt = jax.nn.softplus(dt + p["mamba.dt_bias"])
    y = recurrence(xs, dt, -jnp.exp(p["mamba.A_log"]), b_mat, c_mat)
    y = y + p["mamba.D"][:, None] * xs
    y = y.reshape(s, d_in) * jax.nn.silu(z)
    yg = y.reshape(s, g, d_in // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                            + m["rms_norm_eps"])
    return mm(yg.reshape(s, d_in) * p["mamba.norm.weight"],
              p["mamba.out_proj.weight"])


def attention(m: dict, u, p: dict, mm, ein):
    """Causal grouped-query attention, no positions, scores scaled by
    ``attention_multiplier``; by blocks of query rows."""
    import jax
    import jax.numpy as jnp
    s = u.shape[0]
    h, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    d = _sizes(m)["head_dim"]
    q = mm(u, p["self_attn.q_proj.weight"]).reshape(s, hkv, h // hkv, d)
    k = mm(u, p["self_attn.k_proj.weight"]).reshape(s, hkv, d)
    v = mm(u, p["self_attn.v_proj.weight"]).reshape(s, hkv, d)
    rows = []
    for lo in range(0, s, QUERY_ROWS):
        hi = min(lo + QUERY_ROWS, s)
        seen = jnp.arange(lo, hi)[:, None] >= jnp.arange(s)[None, :]
        score = ein("tjgd,sjd->jgts", q[lo:hi], k) * \
            m["attention_multiplier"]
        prob = jax.nn.softmax(jnp.where(seen, score, -jnp.inf), axis=-1)
        rows.append(ein("jgts,sjd->tjgd", prob, v).reshape(hi - lo, h * d))
    return mm(jnp.concatenate(rows, axis=0), p["self_attn.o_proj.weight"])


def _layer(m: dict, kind: str, precision: str):
    import jax
    import jax.numpy as jnp
    mm, ein = _matmul(precision)
    eps, res = m["rms_norm_eps"], m["residual_multiplier"]
    f = m["shared_intermediate_size"]

    def layer(x, leaves):
        p = {name: w.astype(jnp.float32) for name, w in leaves.items()}
        u = _rms(x, p["input_layernorm.weight"], eps)
        mixed = mamba2(m, u, p, mm) if kind == "mamba" \
            else attention(m, u, p, mm, ein)
        x = x + res * mixed
        v = _rms(x, p["post_attention_layernorm.weight"], eps)
        gw = mm(v, p["shared_mlp.input_linear.weight"])
        return x + res * mm(jax.nn.silu(gw[:, :f]) * gw[:, f:],
                            p["shared_mlp.output_linear.weight"])

    return layer


@functools.lru_cache(maxsize=4)
def _programs(model_items: tuple, precision: str):
    import jax
    import jax.numpy as jnp
    m = dict(model_items)
    mm, _ = _matmul(precision)

    def embed(table, ids):
        return table[ids].astype(jnp.float32) * m["embedding_multiplier"]

    def head(x, norm, block):
        return mm(_rms(x, norm.astype(jnp.float32), m["rms_norm_eps"]),
                  block.astype(jnp.float32).T) / m["logits_scaling"]

    return (jax.jit(embed),
            {kind: jax.jit(_layer(m, kind, precision))
             for kind in ("mamba", "attention")}, jax.jit(head))


def _model_items(m: dict) -> tuple:
    return _hashable(m) + (("layer_types", tuple(m["layer_types"])),)


def logits(m: dict, stacked: dict, ids, precision: str = "float32"):
    """``ids [S]`` -> logits ``[S, V]`` float32, ON THE HOST (numpy)."""
    import jax
    import jax.numpy as jnp
    embed, layers, head = _programs(_model_items(m), precision)
    with jax.default_matmul_precision("highest"):
        x = embed(stacked["embed"], jnp.asarray(ids, jnp.int32))
        for kind, leaves in zip(m["layer_types"], stacked["layers"]):
            x = layers[kind](x, leaves)
        vocab = m["vocab_size"]
        out = np.empty((x.shape[0], vocab), np.float32)
        for lo in range(0, vocab, VOCAB_BLOCK):
            hi = min(lo + VOCAB_BLOCK, vocab)
            out[:, lo:hi] = np.asarray(
                head(x, stacked["norm"], stacked["embed"][lo:hi]))
    return out
