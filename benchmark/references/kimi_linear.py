"""Plain reference of the Kimi-Linear hybrid stack (``model_type``
``kimi_linear``; Kimi-Linear-48B-A3B-Instruct's config.json): forward,
next-token loss, gradients and Adam in straightforward ``jax.numpy`` and
float32 at ``highest`` matmul precision.  No kernels, none of the
program's code and none of its arrays.

``x = embed(ids)``; for layer ``l = 1..L``: ``x = x + Mixer_l(RMSNorm(x))``
and ``x = x + FFN_l(RMSNorm(x))``; then RMSNorm and an untied head.

- KDA (``linear_attn_config.kda_layers``) is the recurrence POSITION BY
  POSITION, one ``lax.scan`` step a position over the ``[H, K, V]`` state
  (``S = exp(g_t) o S``; ``u = beta_t (v_t - S^T k_t)``; ``S = S + k_t
  u^T``; ``o_t = S^T q_t``), checkpointed in segments so that its backward
  fits: it shares nothing with the program's chunked WY form, has no
  chunk, no triangular solve and no ``exp(-Gamma)``.
- MLA (``linear_attn_config.full_attn_layers``) forms the explicit
  192-wide keys ``[k_nope | k_pe]`` (``k_pe`` repeated over the heads) and
  goes through blocks of queries, each checkpointed (the scores of 8192
  positions by 32 heads do not fit at once).
- The experts are a loop over the experts HELD (``held_experts``): the
  router scores all ``num_experts`` in float32 (sigmoid; top k by score +
  correction bias; weights the chosen scores, renormalised, times
  ``routed_scaling_factor``), each held SwiGLU expert is run densely over
  all positions and weighted by what the router gave it there.  Pairs on
  absent experts are left out; the shared expert is added for everyone.

Departures and assumptions, each also in the configuration's ``assumed``
(the config.json states sizes, not these): the low-rank widths of KDA's
decay and output gates equal ``head_dim``; ``A_log`` a head and
``dt_bias`` a channel, ``g = -exp(A_log) softplus(. + dt_bias)``; q and k
l2-normalised over a head's channels (``x rsqrt(sum x^2 + 1e-6)``), q then
scaled by ``head_dim ** -0.5``; a sigmoid output gate on a per-head
RMSNorm; convolutions without bias; ``mla_use_nope``: no rotary
embedding anywhere; ``num_expert_group`` = ``topk_group`` = 1, so the
grouped top-k is the plain one; linear weights stored ``[in, out]``; Adam
in the form of Paddle's adam_op (as ``references/gpt.py``).
``precision="fp8"`` is the correctness check's control: the operands of
every projection and expert product are rounded to 8 bits
(``references/gpt.py`` has the recipe); the router, the recurrence, the
convolutions and attention's two products stay float32.
"""
from __future__ import annotations

import functools

import numpy as np

from .gpt import _matmul

PUBLISHED_FULL = (4, 8, 12, 16, 20, 24, 27)
DEFAULTS = dict(
    hidden_size=2304, num_hidden_layers=27, rms_norm_eps=1e-5,
    linear_attn_config={
        "full_attn_layers": list(PUBLISHED_FULL), "head_dim": 128,
        "kda_layers": [i for i in range(1, 28) if i not in PUBLISHED_FULL],
        "num_heads": 32, "short_conv_kernel_size": 4},
    num_attention_heads=32, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, kv_lora_rank=512, intermediate_size=9216,
    first_k_dense_replace=1, num_experts=256, num_experts_per_token=8,
    moe_intermediate_size=1024, num_shared_experts=1,
    routed_scaling_factor=2.446, moe_renormalize=True, held_experts=None)
SCAN_SEGMENT = 128          # positions a checkpointed segment of the scan
QUERY_BLOCK = 512           # queries a checkpointed block of attention
L2_EPS = 1e-6


def cfg(m: dict) -> dict:
    c = {**DEFAULTS, **m}
    lo, hi = c["held_experts"] or (0, c["num_experts"])
    c["held"] = (int(lo), int(hi))
    la = c["linear_attn_config"]
    c["kinds"] = []
    for i in range(1, c["num_hidden_layers"] + 1):
        mixer = "kda" if i in la["kda_layers"] else "mla"
        assert (i in la["kda_layers"]) != (i in la["full_attn_layers"]), i
        c["kinds"].append(
            (mixer, "dense" if i <= c["first_k_dense_replace"] else "moe"))
    c["kda_inner"] = la["num_heads"] * la["head_dim"]
    return c


def layer_spec(c: dict, mixer: str, ffn: str) -> dict:
    d = c["hidden_size"]
    if mixer == "kda":
        la = c["linear_attn_config"]
        inner, hd, taps = c["kda_inner"], la["head_dim"], \
            la["short_conv_kernel_size"]
        leaves = {"q_proj.weight": (d, inner), "k_proj.weight": (d, inner),
                  "v_proj.weight": (d, inner),
                  "q_conv1d.weight": (inner, taps),
                  "k_conv1d.weight": (inner, taps),
                  "v_conv1d.weight": (inner, taps),
                  "f_a_proj.weight": (d, hd), "f_b_proj.weight": (hd, inner),
                  "A_log": (la["num_heads"],), "dt_bias": (inner,),
                  "b_proj.weight": (d, la["num_heads"]),
                  "o_norm.weight": (hd,),
                  "g_a_proj.weight": (d, hd), "g_b_proj.weight": (hd, inner),
                  "o_proj.weight": (inner, d)}
    else:
        h, rank = c["num_attention_heads"], c["kv_lora_rank"]
        qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
        leaves = {"q_proj.weight": (d, h * qk),
                  "kv_a_proj_with_mqa.weight":
                      (d, rank + c["qk_rope_head_dim"]),
                  "kv_a_layernorm.weight": (rank,),
                  "kv_b_proj.weight":
                      (rank, h * (c["qk_nope_head_dim"] + c["v_head_dim"])),
                  "o_proj.weight": (h * c["v_head_dim"], d)}
    spec = {"input_layernorm.weight": (d,),
            "post_attention_layernorm.weight": (d,)}
    spec.update({"self_attn." + k: v for k, v in leaves.items()})
    if ffn == "dense":
        f = c["intermediate_size"]
        spec.update({"mlp.gate_proj.weight": (d, f),
                     "mlp.up_proj.weight": (d, f),
                     "mlp.down_proj.weight": (f, d)})
    else:
        held = c["held"][1] - c["held"][0]
        f = c["moe_intermediate_size"]
        fs = c["num_shared_experts"] * f
        spec.update({
            "mlp.routed.gate": (d, c["num_experts"]),
            "mlp.routed.e_score_correction_bias": (c["num_experts"],),
            "mlp.routed.experts.w_gate": (held, d, f),
            "mlp.routed.experts.w_up": (held, d, f),
            "mlp.routed.experts.w_down": (held, f, d),
            "mlp.shared_experts.gate_proj.weight": (d, fs),
            "mlp.shared_experts.up_proj.weight": (d, fs),
            "mlp.shared_experts.down_proj.weight": (fs, d)})
    return spec


def param_spec(m: dict) -> dict:
    """name -> shape, in the program's naming."""
    c = cfg(m)
    d = c["hidden_size"]
    spec = {"model.embed_tokens.weight": (m["vocab_size"], d),
            "model.norm.weight": (d,),
            "lm_head.weight": (m["vocab_size"], d)}
    for i, kinds in enumerate(c["kinds"]):
        for leaf, shape in layer_spec(c, *kinds).items():
            spec[f"model.layers.{i}.{leaf}"] = shape
    return spec


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """The model's operations a token, forward and backward, by formula
    whatever implements them: 6 x the weights a token meets outside the
    embedding (a routed expert at the EXPECTED pairs a token that fall on
    held experts, top_k x held / all), plus causal attention's two
    products (3 s per score and per value channel: half the square) and
    KDA's recurrence (7 operations a state element a position: the decay,
    S^T k, the rank-1 update, S^T q; times 3).  Recomputed operations are
    not counted."""
    c = cfg(m)
    d = c["hidden_size"]
    la = c["linear_attn_config"]
    held = c["held"][1] - c["held"][0]
    pairs = c["num_experts_per_token"] * held / c["num_experts"]
    total = 6.0 * d * m["vocab_size"]
    for mixer, ffn in c["kinds"]:
        shapes = layer_spec(c, mixer, ffn)
        met = sum(int(np.prod(s)) for n, s in shapes.items()
                  if n.startswith("self_attn.") and len(s) == 2)
        total += 6.0 * met
        if mixer == "kda":
            total += 21.0 * c["kda_inner"] * la["head_dim"]
        else:
            total += 3.0 * seq_len * c["num_attention_heads"] * (
                c["qk_nope_head_dim"] + c["qk_rope_head_dim"] +
                c["v_head_dim"])
        if ffn == "dense":
            total += 6.0 * 3 * d * c["intermediate_size"]
        else:
            f = c["moe_intermediate_size"]
            total += 6.0 * (d * c["num_experts"] +
                            3 * d * f * c["num_shared_experts"] +
                            pairs * 3 * d * f)
    return total


def stack(flat: dict, m: dict) -> dict:
    """The flat leaves as one tree: the stack is heterogeneous, so a list
    of per-layer dicts and not a leading layer axis."""
    c = cfg(m)
    layers = []
    for i, kinds in enumerate(c["kinds"]):
        pre = f"model.layers.{i}."
        layers.append({leaf: flat[pre + leaf]
                       for leaf in layer_spec(c, *kinds)})
    return {"embed": flat["model.embed_tokens.weight"],
            "norm": flat["model.norm.weight"],
            "head": flat["lm_head.weight"], "layers": layers}


def unstack_names(tree: dict) -> dict:
    out = {"model.embed_tokens.weight": tree["embed"],
           "model.norm.weight": tree["norm"],
           "lm_head.weight": tree["head"]}
    for i, layer in enumerate(tree["layers"]):
        for leaf, v in layer.items():
            out[f"model.layers.{i}.{leaf}"] = v
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
    _, idx = jax.lax.top_k(score + bias, c["num_experts_per_token"])
    w = jnp.take_along_axis(score, idx, axis=-1)
    if c["moe_renormalize"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * c["routed_scaling_factor"]


def swiglu(x, w_gate, w_up, w_down, mm):
    import jax
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def moe_routed(c: dict, x, p: dict, mm, held=None):
    """The part of the routed result that the experts `held` give (the
    configuration's own share unless told otherwise)."""
    import jax
    import jax.numpy as jnp
    lo, hi = held or c["held"]
    idx, w = route(c, x, p["mlp.routed.gate"],
                   p["mlp.routed.e_score_correction_bias"])

    def add_expert(y, expert):
        e, w_gate, w_up, w_down = expert
        coef = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        return y + coef[:, None] * swiglu(x, w_gate, w_up, w_down, mm), None

    # expert after expert, densely over all positions (a scan and not an
    # unrolled loop: the program is compiled for one expert; each step
    # checkpointed, so the backward holds one expert's activations)
    y, _ = jax.lax.scan(
        jax.checkpoint(add_expert), jnp.zeros_like(x),
        (jnp.arange(lo, hi), p["mlp.routed.experts.w_gate"],
         p["mlp.routed.experts.w_up"], p["mlp.routed.experts.w_down"]))
    return y


def shared_expert(x, p: dict, mm):
    return swiglu(x, p["mlp.shared_experts.gate_proj.weight"],
                  p["mlp.shared_experts.up_proj.weight"],
                  p["mlp.shared_experts.down_proj.weight"], mm)


def delta_recurrence(q, k, v, g, beta):
    """The gated delta rule, position by position: ``q``/``k``/``g [S, H,
    K]``, ``v [S, H, V]``, ``beta [S, H]`` -> ``o [S, H, V]``."""
    import jax
    import jax.numpy as jnp
    s, h, kdim = q.shape
    vdim = v.shape[-1]
    pad = (-s) % SCAN_SEGMENT
    widen = lambda t: jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
    seg = lambda t: widen(t).reshape((-1, SCAN_SEGMENT) + t.shape[1:])

    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        state = jnp.exp(g_t)[:, :, None] * state
        u = b_t[:, None] * (v_t - jnp.sum(k_t[:, :, None] * state, axis=1))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.sum(q_t[:, :, None] * state, axis=1)

    @jax.checkpoint
    def segment(state, inp):
        return jax.lax.scan(step, state, inp)

    _, o = jax.lax.scan(segment, jnp.zeros((h, kdim, vdim), jnp.float32),
                        tuple(map(seg, (q, k, v, g, beta))))
    return o.reshape(-1, h, vdim)[:s]


def _layer_fns(c: dict, precision: str):
    import jax
    import jax.numpy as jnp
    mm = _matmul(precision)
    hi = jax.lax.Precision.HIGHEST
    eps = c["rms_norm_eps"]

    def rms(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * w

    def kda(x, p):
        s = x.shape[0]
        la = c["linear_attn_config"]
        h, hd, taps = la["num_heads"], la["head_dim"], \
            la["short_conv_kernel_size"]

        def branch(name):
            t = jnp.pad(mm(x, p[f"self_attn.{name}_proj.weight"]),
                        [(taps - 1, 0), (0, 0)])
            w = p[f"self_attn.{name}_conv1d.weight"]
            conv = sum(t[j:j + s] * w[:, j] for j in range(taps))
            return jax.nn.silu(conv).reshape(s, h, hd)

        unit = lambda t: t * jax.lax.rsqrt(
            jnp.sum(t * t, -1, keepdims=True) + L2_EPS)
        q, k, v = unit(branch("q")) * hd ** -0.5, unit(branch("k")), \
            branch("v")
        low = lambda a, b: mm(mm(x, p[f"self_attn.{a}.weight"]),
                              p[f"self_attn.{b}.weight"])
        g = -jnp.exp(p["self_attn.A_log"])[:, None] * jax.nn.softplus(
            low("f_a_proj", "f_b_proj") + p["self_attn.dt_bias"]
        ).reshape(s, h, hd)
        beta = jax.nn.sigmoid(mm(x, p["self_attn.b_proj.weight"]))
        o = delta_recurrence(q, k, v, g, beta)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * \
            p["self_attn.o_norm.weight"]
        gate = jax.nn.sigmoid(low("g_a_proj", "g_b_proj")).reshape(s, h, hd)
        return mm((o * gate).reshape(s, h * hd),
                  p["self_attn.o_proj.weight"])

    def mla(x, p):
        s = x.shape[0]
        nh, nope, rope, dv, rank = (
            c["num_attention_heads"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"])
        q = mm(x, p["self_attn.q_proj.weight"]).reshape(s, nh, nope + rope)
        kva = mm(x, p["self_attn.kv_a_proj_with_mqa.weight"])
        latent = rms(kva[:, :rank], p["self_attn.kv_a_layernorm.weight"])
        kv = mm(latent, p["self_attn.kv_b_proj.weight"]).reshape(
            s, nh, nope + dv)
        # the 192-wide keys, explicitly: every head's own 128 channels
        # and the 64 channels all heads share; no position is applied
        k = jnp.concatenate(
            [kv[:, :, :nope], jnp.repeat(kva[:, None, rank:], nh, axis=1)],
            axis=-1).transpose(1, 0, 2)                      # [nh, S, 192]
        v = kv[:, :, nope:].transpose(1, 0, 2)               # [nh, S, 128]
        qb = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

        @jax.checkpoint
        def block(args):
            q_blk, first = args                          # [qb, nh, 192]
            sc = jnp.einsum("qhd,hkd->hqk", q_blk, k,
                            precision=hi) * (nope + rope) ** -0.5
            seen = (first + jnp.arange(qb))[:, None] >= jnp.arange(s)[None]
            sc = jnp.where(seen[None], sc, -1e30)
            return jnp.einsum("hqk,hkd->qhd", jax.nn.softmax(sc, -1), v,
                              precision=hi)

        o = jax.lax.map(block, (q.reshape(s // qb, qb, nh, nope + rope),
                                jnp.arange(0, s, qb)))
        return mm(o.reshape(s, nh * dv), p["self_attn.o_proj.weight"])

    def dense(x, p):
        return swiglu(x, p["mlp.gate_proj.weight"], p["mlp.up_proj.weight"],
                      p["mlp.down_proj.weight"], mm)

    def moe(x, p):
        return moe_routed(c, x, p, mm) + shared_expert(x, p, mm)

    mixers = {"kda": kda, "mla": mla}
    ffns = {"dense": dense, "moe": moe}

    def layer(mixer, ffn):
        def run(x, p):
            p = {k: v.astype(jnp.float32) for k, v in p.items()}
            x = x + mixers[mixer](rms(x, p["input_layernorm.weight"]), p)
            return x + ffns[ffn](
                rms(x, p["post_attention_layernorm.weight"]), p)
        return jax.checkpoint(run)

    return rms, layer


def _forward(m: dict, precision: str):
    """(tree of params, ids [S]) -> hidden states [S, d] after the final
    norm."""
    import jax.numpy as jnp
    c = cfg(m)
    rms, layer = _layer_fns(c, precision)
    fns = [layer(*kinds) for kinds in c["kinds"]]

    def forward(params, ids):
        x = params["embed"].astype(jnp.float32)[ids]
        for fn, p in zip(fns, params["layers"]):
            x = fn(x, p)
        return rms(x, params["norm"].astype(jnp.float32))

    return forward


def _freeze(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    return tuple(_freeze(x) for x in v) if isinstance(v, (list, tuple)) \
        else v


def _thaw(items: tuple) -> dict:
    m = dict(items)
    if "linear_attn_config" in m:
        m["linear_attn_config"] = {
            k: list(v) if isinstance(v, tuple) else v
            for k, v in m["linear_attn_config"]}
    return m


def _key(m: dict) -> tuple:
    return _freeze(m)


@functools.lru_cache(maxsize=None)
def _logits_fn(model_items: tuple, precision: str):
    import jax
    import jax.numpy as jnp
    forward = _forward(_thaw(model_items), precision)

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
    moments between updates, are kept on the host.  Returns the losses,
    the per-leaf norm of the first step's gradient and of the parameters'
    change over all the steps, under the flat names."""
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
    # computed, Adam's two moments: 602 M parameters x (weights, both
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
