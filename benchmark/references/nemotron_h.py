"""Plain reference of the Nemotron-H hybrid stack (``model_type``
``nemotron_h``; NVIDIA-Nemotron-3-Nano-30B-A3B's config.json): forward,
next-token loss, gradients and Adam in straightforward ``jax.numpy`` and
float32 at ``highest`` matmul precision.  No kernels, none of the
program's code and none of its arrays.

``x = embed(ids)``; for each character of ``hybrid_override_pattern``,
``x = x + Mixer(RMSNorm(x))``: ``M`` Mamba-2, ``E`` mixture of experts,
``*`` causal grouped-query attention; then RMSNorm and an untied head.

- Mamba-2 is the SEQUENTIAL recurrence, one ``lax.scan`` step a position
  (``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = C_t . h_t +
  D x_t``), checkpointed in segments so that its backward fits: it
  shares nothing with the program's chunked algorithm.
- The experts are a loop over the experts HELD (``held_experts``): the
  router scores all ``n_routed_experts`` in float32 (sigmoid; top k by
  score + correction bias; weights the chosen scores, normalised, times
  ``routed_scaling_factor``), each held expert is run densely over all
  positions and weighted by what the router gave it there.  Pairs on
  absent experts are left out; the shared expert is added for everyone.
- Attention goes through blocks of queries (the scores of 8192 positions
  by 32 heads do not fit at once), each block checkpointed.

Departures and assumptions, each also in the configuration's ``assumed``:
no rotary embedding in the attention layers; linear weights stored
``[in, out]``; Adam in the form of Paddle's adam_op (as
``references/gpt.py``).  ``precision="fp8"`` is the correctness check's
control: the operands of every projection and expert product are rounded
to 8 bits (``references/gpt.py`` has the recipe); the router, the
recurrence and attention's two products stay float32.
"""
from __future__ import annotations

import functools

import numpy as np

from .gpt import _matmul

DEFAULTS = dict(
    hidden_size=2688, hybrid_override_pattern="MEMEM*EME",
    layer_norm_epsilon=1e-5, num_attention_heads=32, num_key_value_heads=2,
    head_dim=128, mamba_num_heads=64, mamba_head_dim=64, n_groups=8,
    ssm_state_size=128, conv_kernel=4, n_routed_experts=128,
    num_experts_per_tok=6, moe_intermediate_size=1856,
    moe_shared_expert_intermediate_size=3712, routed_scaling_factor=2.5,
    norm_topk_prob=True, held_experts=None)
SCAN_SEGMENT = 128          # positions a checkpointed segment of the scan
QUERY_BLOCK = 512           # queries a checkpointed block of attention


def cfg(m: dict) -> dict:
    c = {**DEFAULTS, **m}
    lo, hi = c["held_experts"] or (0, c["n_routed_experts"])
    c["held"] = (int(lo), int(hi))
    c["d_inner"] = c["mamba_num_heads"] * c["mamba_head_dim"]
    c["bc"] = 2 * c["n_groups"] * c["ssm_state_size"]
    return c


def layer_spec(c: dict, kind: str) -> dict:
    d = c["hidden_size"]
    if kind == "M":
        di, h = c["d_inner"], c["mamba_num_heads"]
        leaves = {"mixer.in_proj.weight": (d, 2 * di + c["bc"] + h),
                  "mixer.conv1d.weight": (di + c["bc"], c["conv_kernel"]),
                  "mixer.conv1d.bias": (di + c["bc"],),
                  "mixer.dt_bias": (h,), "mixer.A_log": (h,),
                  "mixer.D": (h,), "mixer.norm.weight": (di,),
                  "mixer.out_proj.weight": (di, d)}
    elif kind == "*":
        q = c["num_attention_heads"] * c["head_dim"]
        kv = c["num_key_value_heads"] * c["head_dim"]
        leaves = {"mixer.q_proj.weight": (d, q),
                  "mixer.k_proj.weight": (d, kv),
                  "mixer.v_proj.weight": (d, kv),
                  "mixer.o_proj.weight": (q, d)}
    else:
        held = c["held"][1] - c["held"][0]
        f, fs = c["moe_intermediate_size"], \
            c["moe_shared_expert_intermediate_size"]
        leaves = {"mixer.routed.gate": (d, c["n_routed_experts"]),
                  "mixer.routed.e_score_correction_bias":
                      (c["n_routed_experts"],),
                  "mixer.routed.experts.w_up": (held, d, f),
                  "mixer.routed.experts.w_down": (held, f, d),
                  "mixer.shared_experts.up_proj.weight": (d, fs),
                  "mixer.shared_experts.down_proj.weight": (fs, d)}
    return {"norm.weight": (d,), **leaves}


def param_spec(m: dict) -> dict:
    """name -> shape, in the program's naming."""
    c = cfg(m)
    d = c["hidden_size"]
    spec = {"backbone.embeddings.weight": (m["vocab_size"], d),
            "backbone.norm_f.weight": (d,),
            "lm_head.weight": (m["vocab_size"], d)}
    for i, kind in enumerate(c["hybrid_override_pattern"]):
        for leaf, shape in layer_spec(c, kind).items():
            spec[f"backbone.layers.{i}.{leaf}"] = shape
    return spec


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """The model's operations a token, forward and backward, by formula
    whatever implements them: 6 x the weights a token meets outside the
    embedding (a routed expert at the EXPECTED pairs a token that fall on
    held experts, top_k x held / all), plus causal attention's two
    products (6 s per query channel: half the square) and the recurrence
    (5 operations a state element a position, times 3).  Recomputed
    operations are not counted."""
    c = cfg(m)
    d = c["hidden_size"]
    held = c["held"][1] - c["held"][0]
    pairs = c["num_experts_per_tok"] * held / c["n_routed_experts"]
    total = 6.0 * d * m["vocab_size"]
    for kind in c["hybrid_override_pattern"]:
        if kind == "M":
            di = c["d_inner"]
            total += 6.0 * (d * (2 * di + c["bc"] + c["mamba_num_heads"]) +
                            di * d + (di + c["bc"]) * c["conv_kernel"])
            total += 15.0 * di * c["ssm_state_size"]
        elif kind == "*":
            q = c["num_attention_heads"] * c["head_dim"]
            kv = c["num_key_value_heads"] * c["head_dim"]
            total += 6.0 * (2 * d * q + 2 * d * kv) + 6.0 * seq_len * q
        else:
            total += 6.0 * (
                d * c["n_routed_experts"] +
                2 * d * c["moe_shared_expert_intermediate_size"] +
                pairs * 2 * d * c["moe_intermediate_size"])
    return total


def stack(flat: dict, m: dict) -> dict:
    """The flat leaves as one tree: the stack is heterogeneous, so a list
    of per-layer dicts and not a leading layer axis."""
    c = cfg(m)
    layers = []
    for i, kind in enumerate(c["hybrid_override_pattern"]):
        pre = f"backbone.layers.{i}."
        layers.append({leaf: flat[pre + leaf]
                       for leaf in layer_spec(c, kind)})
    return {"embed": flat["backbone.embeddings.weight"],
            "norm_f": flat["backbone.norm_f.weight"],
            "head": flat["lm_head.weight"], "layers": layers}


def unstack_names(tree: dict) -> dict:
    out = {"backbone.embeddings.weight": tree["embed"],
           "backbone.norm_f.weight": tree["norm_f"],
           "lm_head.weight": tree["head"]}
    for i, layer in enumerate(tree["layers"]):
        for leaf, v in layer.items():
            out[f"backbone.layers.{i}.{leaf}"] = v
    return out


# ---------------------------------------------------------------------------
# the layers, one row [S, d] at a time
# ---------------------------------------------------------------------------
def route(c: dict, x, gate, bias):
    """(idx [S, k], weight [S, k]) of the router, float32 throughout."""
    import jax
    import jax.numpy as jnp
    score = jax.nn.sigmoid(jnp.matmul(x, gate,
                                      precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(score + bias, c["num_experts_per_tok"])
    w = jnp.take_along_axis(score, idx, axis=-1)
    if c["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * c["routed_scaling_factor"]


def moe_routed(c: dict, x, p: dict, mm, held=None):
    """The part of the routed result that the experts `held` give (the
    configuration's own share unless told otherwise)."""
    import jax
    import jax.numpy as jnp
    lo, hi = held or c["held"]
    idx, w = route(c, x, p["mixer.routed.gate"],
                   p["mixer.routed.e_score_correction_bias"])
    y = jnp.zeros_like(x)
    for e in range(lo, hi):
        coef = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        up = mm(x, p["mixer.routed.experts.w_up"][e - lo])
        y = y + coef[:, None] * mm(
            jnp.square(jax.nn.relu(up)),
            p["mixer.routed.experts.w_down"][e - lo])
    return y


def shared_expert(x, p: dict, mm):
    import jax
    import jax.numpy as jnp
    up = mm(x, p["mixer.shared_experts.up_proj.weight"])
    return mm(jnp.square(jax.nn.relu(up)),
              p["mixer.shared_experts.down_proj.weight"])


def recurrence(xs, dt, a_neg, b_mat, c_mat):
    """The state-space recurrence, position by position: ``xs [S, H, P]``,
    ``dt [S, H]``, ``a_neg [H]``, ``b_mat``/``c_mat [S, G, N]`` ->
    ``[S, H, P]`` (without the D skip)."""
    import jax
    import jax.numpy as jnp
    s, h, p = xs.shape
    g, n = b_mat.shape[1:]
    rep = h // g
    pad = (-s) % SCAN_SEGMENT
    widen = lambda t: jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
    seg = lambda t: widen(t).reshape((-1, SCAN_SEGMENT) + t.shape[1:])

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        b_h = jnp.repeat(b_t, rep, axis=0)                  # [H, N]
        c_h = jnp.repeat(c_t, rep, axis=0)
        state = jnp.exp(dt_t * a_neg)[:, None, None] * state + \
            (dt_t[:, None] * x_t)[:, :, None] * b_h[:, None, :]
        return state, jnp.sum(state * c_h[:, None, :], axis=-1)

    @jax.checkpoint
    def segment(state, inp):
        return jax.lax.scan(step, state, inp)

    _, y = jax.lax.scan(segment, jnp.zeros((h, p, n), jnp.float32),
                        (seg(xs), seg(dt), seg(b_mat), seg(c_mat)))
    return y.reshape(-1, h, p)[:s]


def _layer_fns(c: dict, precision: str):
    import jax
    import jax.numpy as jnp
    mm = _matmul(precision)
    hi = jax.lax.Precision.HIGHEST
    eps = c["layer_norm_epsilon"]

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * w

    def mamba(x, p):
        s = x.shape[0]
        di, h, hd = c["d_inner"], c["mamba_num_heads"], c["mamba_head_dim"]
        g, n, k = c["n_groups"], c["ssm_state_size"], c["conv_kernel"]
        zxbcdt = mm(x, p["mixer.in_proj.weight"])
        z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + c["bc"]],
                      zxbcdt[:, 2 * di + c["bc"]:])
        padded = jnp.pad(xbc, [(k - 1, 0), (0, 0)])
        conv = p["mixer.conv1d.bias"] + sum(
            padded[j:j + s] * p["mixer.conv1d.weight"][:, j]
            for j in range(k))
        xbc = jax.nn.silu(conv)
        xs = xbc[:, :di].reshape(s, h, hd)
        b_mat = xbc[:, di:di + g * n].reshape(s, g, n)
        c_mat = xbc[:, di + g * n:].reshape(s, g, n)
        dt = jax.nn.softplus(dt + p["mixer.dt_bias"])
        y = recurrence(xs, dt, -jnp.exp(p["mixer.A_log"]), b_mat, c_mat)
        y = y + p["mixer.D"][:, None] * xs
        y = (y.reshape(s, di) * jax.nn.silu(z)).reshape(s, g, di // g)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        return mm(y.reshape(s, di) * p["mixer.norm.weight"],
                  p["mixer.out_proj.weight"])

    def attention(x, p):
        s = x.shape[0]
        nh, nkv, d = c["num_attention_heads"], c["num_key_value_heads"], \
            c["head_dim"]
        q = mm(x, p["mixer.q_proj.weight"]).reshape(s, nh, d)
        k = mm(x, p["mixer.k_proj.weight"]).reshape(s, nkv, d)
        v = mm(x, p["mixer.v_proj.weight"]).reshape(s, nkv, d)
        k = jnp.repeat(k, nh // nkv, axis=1).transpose(1, 0, 2)
        v = jnp.repeat(v, nh // nkv, axis=1).transpose(1, 0, 2)
        qb = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

        @jax.checkpoint
        def block(args):
            q_blk, first = args                              # [qb, nh, d]
            sc = jnp.einsum("qhd,hkd->hqk", q_blk, k,
                            precision=hi) * d ** -0.5
            seen = (first + jnp.arange(qb))[:, None] >= jnp.arange(s)[None]
            sc = jnp.where(seen[None], sc, -1e30)
            return jnp.einsum("hqk,hkd->qhd", jax.nn.softmax(sc, -1), v,
                              precision=hi)

        o = jax.lax.map(block, (q.reshape(s // qb, qb, nh, d),
                                jnp.arange(0, s, qb)))
        return mm(o.reshape(s, nh * d), p["mixer.o_proj.weight"])

    def moe(x, p):
        return moe_routed(c, x, p, mm) + shared_expert(x, p, mm)

    mixers = {"M": mamba, "*": attention, "E": moe}

    def layer(kind):
        def run(x, p):
            p = {k: v.astype(jnp.float32) for k, v in p.items()}
            return x + mixers[kind](rms(x, p["norm.weight"]), p)
        return jax.checkpoint(run)

    return rms, layer


def _forward(m: dict, precision: str):
    """(tree of params, ids [S]) -> hidden states [S, d] after norm_f."""
    import jax.numpy as jnp
    c = cfg(m)
    rms, layer = _layer_fns(c, precision)
    fns = [layer(kind) for kind in c["hybrid_override_pattern"]]

    def forward(params, ids):
        x = params["embed"].astype(jnp.float32)[ids]
        for fn, p in zip(fns, params["layers"]):
            x = fn(x, p)
        return rms(x, params["norm_f"].astype(jnp.float32))

    return forward


def _key(m: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))


@functools.lru_cache(maxsize=None)
def _logits_fn(model_items: tuple, precision: str):
    import jax
    import jax.numpy as jnp
    forward = _forward(dict(model_items), precision)

    def logits(params, ids):
        return jnp.matmul(forward(params, ids),
                          params["head"].astype(jnp.float32).T,
                          precision=jax.lax.Precision.HIGHEST)
    return jax.jit(logits)


def logits(m: dict, params: dict, ids, precision: str = "float32"):
    """Next-token logits [S, V] after every position of ids [S] (the tree
    `stack` gives)."""
    return _logits_fn(_key(m), precision)(params, ids)


def _batch_loss(m: dict, precision: str):
    """(tree, ids [B, S], labels [B, S]) -> the mean next-token loss; the
    rows go through a scan, each checkpointed."""
    import jax
    import jax.numpy as jnp
    forward = _forward(m, precision)

    @jax.checkpoint
    def row_loss(params, ids, labels):
        lg = jnp.matmul(forward(params, ids), params["head"].T,
                        precision=jax.lax.Precision.HIGHEST)
        lse = jax.nn.logsumexp(lg, -1)
        return jnp.sum(lse - jnp.take_along_axis(
            lg, labels[:, None], -1)[:, 0])

    def batch_loss(params, ids, labels):
        def one(total, row):
            return total + row_loss(params, *row), None
        total, _ = jax.lax.scan(one, jnp.zeros((), jnp.float32),
                                (ids, labels))
        return total / ids.size

    return batch_loss


def train_steps(m: dict, flat_params: dict, batches, opt: dict,
                precision: str = "float32") -> dict:
    """Follow the first len(batches) optimizer steps: batches is a list of
    (ids [B, S], labels [B, S]).  The rows of a batch go through one at a
    time inside one program (a scan over rows), so the device holds one
    row's activations and one gradient; the starting point, and Adam's
    moments between updates, are kept on the host.  Returns the losses, the per-leaf norm of the first step's
    gradient and of the parameters' change over all the steps, under the
    flat names."""
    import jax
    import jax.numpy as jnp
    lr, b1, b2, eps = (opt["learning_rate"], opt.get("beta1", 0.9),
                       opt.get("beta2", 0.999), opt.get("epsilon", 1e-8))
    tmap = jax.tree_util.tree_map

    batch_grad = jax.jit(jax.value_and_grad(_batch_loss(m, precision)))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adam(params, m1, m2, grads, t):
        bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
        step = lr * jnp.sqrt(bc2) / bc1

        def one(p, a, b, g):
            a = b1 * a + (1 - b1) * g
            b = b2 * b + (1 - b2) * g * g
            return p - step * a / (jnp.sqrt(b) + eps), a, b
        out = tmap(one, params, m1, m2, grads)
        pick = lambda i: tmap(lambda o: o[i], out,
                              is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2)

    leaf_norms = jax.jit(lambda tree: tmap(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree))

    params = stack({k: v.astype(jnp.float32)
                    for k, v in flat_params.items()}, m)
    del flat_params
    # the host keeps the starting point and, while a batch's gradient is
    # computed, Adam's two moments: 667 M parameters x (weights, both
    # moments, the gradient and its row's share) do not fit the chip
    start = jax.device_get(params)
    m1 = tmap(lambda p: np.zeros(p.shape, np.float32), start)
    m2 = tmap(lambda p: np.zeros(p.shape, np.float32), start)
    losses, grad_norms = [], None
    for t, (ids, labels) in enumerate(batches, start=1):
        loss, grads = batch_grad(params, jnp.asarray(ids),
                                 jnp.asarray(labels))
        losses.append(float(loss))
        if t == 1:
            grad_norms = jax.device_get(leaf_norms(grads))
        params, m1, m2 = adam(params, m1, m2, grads,
                              jnp.asarray(t, jnp.float32))
        if t < len(batches):
            m1, m2 = jax.device_get((m1, m2))
    del m1, m2, grads
    delta = jax.jit(lambda a, b: tmap(jnp.subtract, a, b),
                    donate_argnums=(0,))(params, start)
    delta_norms = jax.device_get(leaf_norms(delta))
    flat = lambda tree: {k: float(v) for k, v in unstack_names(tree).items()}
    return {"losses": losses, "grad_norms": flat(grad_norms),
            "delta_norms": flat(delta_norms)}
