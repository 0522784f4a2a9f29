"""Plain reference of the GPT-3 block (Brown et al. 2020; GPT-2's
pre-LayerNorm decoder): forward, next-token loss, gradients and Adam in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul
precision.  No kernels, no cache, no batching, none of the program's code
and none of its arrays: the weights it is given come from
``benchmark/weights.py``.

Departures from the paper, each also in the configurations' ``assumed``:
learned absolute positions and a head tied to the embedding (GPT-2's, the
paper does not restate them); tanh-approximated GELU (GPT-2's); dense
attention in every layer (the paper alternates dense and locally banded
sparse layers and publishes no band width); Adam in the form of Paddle's
adam_op, ``lr*sqrt(1-b2^t)/(1-b1^t) * m/(sqrt(v)+eps)``.

``precision="fp8"`` and ``"int8"`` are controls of the correctness
check: every matmul operand is rounded to 8 bits before a float32 product:
fp8 with one scale per tensor, e4m3 forward and e5m2 for the gradients in
the backward products (the usual fp8 recipe); int8 symmetric with one
scale per row of activations and per output column of weights and a
straight-through gradient.  They stand for the
precision step below bf16 that a later PR would be tempted by.
"""
from __future__ import annotations

import functools

import numpy as np

LAYER_LEAVES = (
    "ln_1.weight", "ln_1.bias", "attn.qkv_proj.weight", "attn.qkv_proj.bias",
    "attn.out_proj.weight", "attn.out_proj.bias", "ln_2.weight", "ln_2.bias",
    "mlp.up_proj.weight", "mlp.up_proj.bias", "mlp.down_proj.weight",
    "mlp.down_proj.bias")


def dims(m: dict):
    h, nh = m["hidden_size"], m["num_heads"]
    return h, nh, h // nh, m.get("ffn_hidden_size") or 4 * h


def param_spec(m: dict) -> dict:
    """name -> shape, in the checkpoint's naming."""
    h, _, _, f = dims(m)
    per_layer = {
        "ln_1.weight": (h,), "ln_1.bias": (h,),
        "attn.qkv_proj.weight": (h, 3 * h), "attn.qkv_proj.bias": (3 * h,),
        "attn.out_proj.weight": (h, h), "attn.out_proj.bias": (h,),
        "ln_2.weight": (h,), "ln_2.bias": (h,),
        "mlp.up_proj.weight": (h, f), "mlp.up_proj.bias": (f,),
        "mlp.down_proj.weight": (f, h), "mlp.down_proj.bias": (h,)}
    spec = {"gpt.wte.weight": (m["vocab_size"], h),
            "gpt.wpe.weight": (m["max_seq_len"], h),
            "gpt.ln_f.weight": (h,), "gpt.ln_f.bias": (h,)}
    for i in range(m["num_layers"]):
        for leaf, shape in per_layer.items():
            spec[f"gpt.blocks.{i}.{leaf}"] = shape
    return spec


def num_params_no_embeddings(m: dict) -> int:
    return sum(int(np.prod(s)) for n, s in param_spec(m).items()
               if n not in ("gpt.wte.weight", "gpt.wpe.weight"))


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """6N + 12 L h s: forward and backward of the matmuls (N without the
    embeddings) and of attention's two products; recomputed operations
    are not counted (the on-chip-measurement guide's MFU)."""
    return 6.0 * num_params_no_embeddings(m) + \
        12.0 * m["num_layers"] * m["hidden_size"] * seq_len


def stack(flat: dict, m: dict) -> dict:
    """The flat leaves as one tree whose layer leaves are stacked on a
    leading layer axis, for a scan."""
    import jax.numpy as jnp
    return {"wte": flat["gpt.wte.weight"], "wpe": flat["gpt.wpe.weight"],
            "ln_f.weight": flat["gpt.ln_f.weight"],
            "ln_f.bias": flat["gpt.ln_f.bias"],
            "blocks": {leaf: jnp.stack(
                [flat[f"gpt.blocks.{i}.{leaf}"]
                 for i in range(m["num_layers"])]) for leaf in LAYER_LEAVES}}


def unstack_names(tree: dict, m: dict) -> dict:
    """Per-leaf values of a stacked tree (each layer leaf a vector over
    layers) back under the flat names."""
    out = {"gpt.wte.weight": tree["wte"], "gpt.wpe.weight": tree["wpe"],
           "gpt.ln_f.weight": tree["ln_f.weight"],
           "gpt.ln_f.bias": tree["ln_f.bias"]}
    for leaf in LAYER_LEAVES:
        for i in range(m["num_layers"]):
            out[f"gpt.blocks.{i}.{leaf}"] = tree["blocks"][leaf][i]
    return out


def _matmul(precision: str):
    import jax
    import jax.numpy as jnp

    def plain(a, b):
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    if precision == "float32":
        return plain
    if precision == "fp8":
        def q(x, dtype, largest):
            scale = jnp.max(jnp.abs(x)) / largest
            scale = jnp.where(scale > 0, scale, 1.0)
            return (x / scale).astype(dtype).astype(jnp.float32) * scale

        @jax.custom_vjp
        def fp8(a, b):                   # a [s, k] @ b [k, n]
            return plain(q(a, jnp.float8_e4m3fn, 448.0),
                         q(b, jnp.float8_e4m3fn, 448.0))

        def fwd(a, b):
            qa = q(a, jnp.float8_e4m3fn, 448.0)
            qb = q(b, jnp.float8_e4m3fn, 448.0)
            return plain(qa, qb), (qa, qb)

        def bwd(saved, g):               # gradients travel as e5m2
            qa, qb = saved
            qg = q(g, jnp.float8_e5m2, 57344.0)
            return plain(qg, qb.T), plain(qa.T, qg)

        fp8.defvjp(fwd, bwd)
        return fp8
    if precision != "int8":
        raise ValueError(f"reference precision {precision!r}")

    def q8(x, axis):
        scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
        scale = jnp.where(scale > 0, scale, 1.0)
        q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
        return x + jax.lax.stop_gradient(q - x)      # straight-through

    def quantized(a, b):
        # a [..., k] by rows, b [k, n] by output columns
        return plain(q8(a, -1), q8(b, 0))
    return quantized


def _forward(m: dict, precision: str):
    """(stacked params, ids [S]) -> hidden states [S, H] after ln_f,
    with the block under jax.checkpoint so the backward holds one layer."""
    import jax
    import jax.numpy as jnp
    h, nh, d, _ = dims(m)
    eps = m.get("layer_norm_epsilon", 1e-5)
    mm = _matmul(precision)
    hi = jax.lax.Precision.HIGHEST

    def ln(x, w, b):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * w + b

    def block(x, p):
        s = x.shape[0]
        p = {k: v.astype(jnp.float32) for k, v in p.items()}
        a = ln(x, p["ln_1.weight"], p["ln_1.bias"])
        qkv = mm(a, p["attn.qkv_proj.weight"]) + p["attn.qkv_proj.bias"]
        q, k, v = (t.reshape(s, nh, d).transpose(1, 0, 2)
                   for t in jnp.split(qkv, 3, axis=-1))
        sc = jnp.einsum("hqd,hkd->hqk", q, k, precision=hi) / np.sqrt(d)
        causal = jnp.tril(jnp.ones((s, s), bool))
        sc = jnp.where(causal[None], sc, -1e30)
        o = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(sc, -1), v,
                       precision=hi)
        o = o.transpose(1, 0, 2).reshape(s, h)
        x = x + mm(o, p["attn.out_proj.weight"]) + p["attn.out_proj.bias"]
        f = ln(x, p["ln_2.weight"], p["ln_2.bias"])
        f = jax.nn.gelu(mm(f, p["mlp.up_proj.weight"]) +
                        p["mlp.up_proj.bias"], approximate=True)
        x = x + mm(f, p["mlp.down_proj.weight"]) + p["mlp.down_proj.bias"]
        return x, None

    def forward(params, ids):
        s = ids.shape[0]
        x = params["wte"].astype(jnp.float32)[ids] + \
            params["wpe"].astype(jnp.float32)[:s]
        x, _ = jax.lax.scan(jax.checkpoint(block), x, params["blocks"])
        return ln(x, params["ln_f.weight"].astype(jnp.float32),
                  params["ln_f.bias"].astype(jnp.float32))

    return forward


@functools.lru_cache(maxsize=None)
def _logits_fn(model_items: tuple, precision: str):
    import jax
    import jax.numpy as jnp
    m = dict(model_items)
    forward = _forward(m, precision)

    def logits(params, ids):
        x = forward(params, ids)
        return jnp.matmul(x, params["wte"].astype(jnp.float32).T,
                          precision=jax.lax.Precision.HIGHEST)
    return jax.jit(logits)


def logits(m: dict, params: dict, ids, precision: str = "float32"):
    """Next-token logits [S, V] after every position of ids [S] (stacked
    params; causal, so right padding is unseen)."""
    return _logits_fn(_key(m), precision)(params, ids)


def _key(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


def train_steps(m: dict, flat_params: dict, batches, opt: dict,
                precision: str = "float32") -> dict:
    """Follow the first len(batches) optimizer steps: batches is a list
    of (ids [B, S], labels [B, S]).  Rows go through one at a time and
    their gradients are summed, so the device holds one row's
    activations.  Returns the losses, the per-leaf norm of the first
    step's gradient and the per-leaf norm of the parameters' change over
    all the steps, under the flat names."""
    import jax
    import jax.numpy as jnp
    forward = _forward(m, precision)
    lr, b1, b2, eps = (opt["learning_rate"], opt.get("beta1", 0.9),
                       opt.get("beta2", 0.999), opt.get("epsilon", 1e-8))
    hi = jax.lax.Precision.HIGHEST

    def row_loss(params, ids, labels):
        x = forward(params, ids)
        lg = jnp.matmul(x, params["wte"].T, precision=hi)
        lse = jax.nn.logsumexp(lg, -1)
        return jnp.sum(lse - jnp.take_along_axis(
            lg, labels[:, None], -1)[:, 0])

    row_grad = jax.jit(jax.value_and_grad(row_loss))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def accumulate(acc, g):
        return jax.tree_util.tree_map(jnp.add, acc, g)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adam(params, m1, m2, grads, t):
        bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t
        step = lr * jnp.sqrt(bc2) / bc1

        def one(p, a, b, g):
            a = b1 * a + (1 - b1) * g
            b = b2 * b + (1 - b2) * g * g
            return p - step * a / (jnp.sqrt(b) + eps), a, b
        out = jax.tree_util.tree_map(one, params, m1, m2, grads)
        pick = lambda i: jax.tree_util.tree_map(
            lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
        return pick(0), pick(1), pick(2)

    @jax.jit
    def leaf_norms(tree):
        def norm(x):
            axes = tuple(range(1, x.ndim))
            return jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))
        top = {k: jnp.sqrt(jnp.sum(jnp.square(v)))
               for k, v in tree.items() if k != "blocks"}
        top["blocks"] = {k: norm(v) for k, v in tree["blocks"].items()}
        return top

    @jax.jit
    def diff(a, b):
        return jax.tree_util.tree_map(jnp.subtract, a, b)

    params = stack({k: v.astype(jnp.float32)
                    for k, v in flat_params.items()}, m)
    del flat_params      # the stacked copy is the one that is kept
    start = jax.tree_util.tree_map(jnp.copy, params)
    m1 = jax.tree_util.tree_map(jnp.zeros_like, params)
    m2 = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for t, (ids, labels) in enumerate(batches, start=1):
        ids, labels = np.asarray(ids), np.asarray(labels)
        total, acc = 0.0, None
        for r in range(ids.shape[0]):
            loss, g = row_grad(params, ids[r], labels[r])
            total += float(loss)
            acc = g if acc is None else accumulate(acc, g)
        n = float(ids.size)
        grads = jax.tree_util.tree_map(lambda g: g / n, acc)
        losses.append(total / n)
        if t == 1:
            grad_norms = jax.device_get(leaf_norms(grads))
        params, m1, m2 = adam(params, m1, m2, grads,
                              jnp.asarray(t, jnp.float32))
    delta_norms = jax.device_get(leaf_norms(diff(params, start)))
    return {"losses": losses,
            "grad_norms": {k: float(v) for k, v in
                           unstack_names(grad_norms, m).items()},
            "delta_norms": {k: float(v) for k, v in
                            unstack_names(delta_norms, m).items()}}
