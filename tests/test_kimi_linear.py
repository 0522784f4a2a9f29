"""The Kimi-Linear hybrid stack (KDA / MLA over dense and routed SwiGLU
feed-forwards) against its plain reference
(``benchmark/references/kimi_linear.py``) at a small size on the CPU: the
chunked KDA scan against the position-by-position recurrence, outputs and
every gradient, at decays where a whole chunk's ``exp(-Gamma)``
overflows; flash attention whose key and value widths differ, kernel
against composite; the gated experts against a loop; the shares of one
MoE layer adding up to the uncut layer; logits, loss and every gradient
of the model; three steps through ``SpmdTrainer``; the parameter count
of the benchmark's cut at the published widths."""
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights as W
from benchmark.references import kimi_linear as R
from paddle_tpu import ops
from paddle_tpu.distributed import moe
from paddle_tpu.func import functional_call
from paddle_tpu.models import (GPTPretrainingCriterion, KimiLinearConfig,
                               KimiLinearForCausalLM)
from paddle_tpu.nn import functional as F

kda = importlib.import_module("paddle_tpu.ops.kda_scan")

SMALL = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=5,
    linear_attn_config={"full_attn_layers": [4, 8], "head_dim": 16,
                        "kda_layers": [1, 2, 3, 5, 6, 7], "num_heads": 4,
                        "short_conv_kernel_size": 4},
    num_attention_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, kv_lora_rank=32, intermediate_size=128,
    first_k_dense_replace=1, num_experts=16, num_experts_per_token=4,
    moe_intermediate_size=32, held_experts=[0, 4])
INIT = [{"match": "norm\\.weight$", "kind": "ones"},
        {"match": "A_log$|correction_bias$", "kind": "zeros"},
        {"match": "dt_bias$", "kind": "normal", "std": 3.0},
        {"match": "conv1d\\.weight$", "kind": "normal", "std": 0.29},
        {"match": ".", "kind": "normal", "std": 0.05}]


def small(**over):
    """(reference kwargs, program kwargs) of a small stack."""
    ref = {**SMALL, **over}
    return ref, {**ref, "kda_chunk_size": 32}


def seeded(ref_kw, seed=5):
    """Seeded weights with the leaves the benchmark draws at 0 or 1
    (A_log, the norms, the router's bias) moved off them, so that a wrong
    use of one shows."""
    flat = W.make_weights(seed, R.param_spec(ref_kw), INIT, "float32")
    key = jax.random.PRNGKey(seed)
    for i, name in enumerate(sorted(flat)):
        if name.rsplit(".", 1)[-1] in ("A_log",
                                       "e_score_correction_bias") or \
                name.endswith("norm.weight"):
            flat[name] = flat[name] + 0.2 * jax.random.normal(
                jax.random.fold_in(key, i), flat[name].shape)
    return flat


def buffers_of(model):
    return {n: b.data for n, b in model.named_buffers() if b is not None}


def ids_of(rows, length, seed=0):
    ids = np.random.default_rng(seed).integers(
        0, SMALL["vocab_size"], (rows, length)).astype(np.int32)
    return ids, np.roll(ids, -1, axis=1)


# ---------------------------------------------------------------------------
# the chunked KDA scan
# ---------------------------------------------------------------------------
def scan_inputs(seed, rows, length, heads=3, kdim=16, vdim=8):
    """q and k as the mixer hands them over, and decays spread as the
    benchmark's dt_bias spreads them: a channel's g from -0.003 to -8 a
    step, so that 64 steps of the fast ones sum past -400 (exp(400)
    overflows float32) while slow ones carry across many chunks."""
    r = np.random.default_rng(seed)
    n = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(n(rows, length, heads, kdim)) * kdim ** -0.5
    k = unit(n(rows, length, heads, kdim))
    v = n(rows, length, heads, vdim)
    g = -jax.nn.softplus(0.3 * n(rows, length, heads, kdim) +
                         3.0 * n(1, 1, heads, kdim))
    beta = jax.nn.sigmoid(n(rows, length, heads))
    return q, k, v, g, beta


def recurrence(q, k, v, g, beta):
    return jnp.stack([R.delta_recurrence(*(t[r] for t in (q, k, v, g, beta)))
                      for r in range(q.shape[0])])


@pytest.mark.parametrize("length,chunk", [(200, 64), (64, 64), (37, 32),
                                          (7, 16), (130, 32)])
def test_chunked_kda_matches_the_recurrence(length, chunk):
    args = scan_inputs(3, 2, length)
    if length >= 64:
        assert float(jnp.cumsum(args[3], 1)[:, :64].min()) < -200
    got = ops.kda_scan(*args, chunk=chunk)
    np.testing.assert_allclose(got, recurrence(*args), atol=2e-6)


def test_chunked_kda_gradients_match_the_recurrence():
    """Every input's gradient over four chunks, under decays whose
    whole-chunk exp(-Gamma) overflows: nothing is NaN or inf, and the
    decay's own gradient agrees."""
    args = scan_inputs(4, 2, 200)
    assert float(jnp.exp(-jnp.cumsum(args[3], 1)[:, :64]).max()) == np.inf
    weigh = jnp.cos(jnp.arange(8.0))
    loss = lambda fn: lambda *a: jnp.sum(fn(*a) * weigh)
    got = jax.grad(loss(lambda *a: ops.kda_scan(*a, chunk=64)),
                   argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(loss(recurrence), argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a / scale, b / scale, atol=5e-6,
                                   err_msg=name)


def test_kda_with_the_carried_state_cut_differs():
    """Every chunk taken for a sequence of its own: the first chunk
    agrees with the recurrence, what follows does not."""
    args = scan_inputs(5, 1, 128)
    cut = lambda t: t.reshape((-1, 32) + t.shape[2:])
    broken = ops.kda_scan(*map(cut, args), chunk=32).reshape(1, 128, 3, 8)
    want = recurrence(*args)
    np.testing.assert_allclose(broken[:, :32], want[:, :32], atol=2e-6)
    assert float(jnp.max(jnp.abs(broken[:, 32:] - want[:, 32:]))) > 0.05


@pytest.mark.parametrize("widths,reason", [
    (dict(kdim=128, vdim=128), "backend is not tpu"),
    (dict(kdim=16, vdim=8), "shape not served by the kernel"),
    (dict(kdim=128, vdim=64), "shape not served by the kernel")])
def test_kda_scan_notes_its_path(widths, reason):
    """Off the chip the scan is XLA's form and says why: there is no tpu,
    or (heads narrower than a lane tile) the kernel would not serve the
    shape anywhere.  A chunk the pair terms cannot halve is refused
    before either path."""
    ops.kernel_paths.reset()
    args = scan_inputs(6, 1, 40, **widths)
    got = ops.kda_scan(*args, chunk=16)
    assert ops.kernel_paths.counts()["kda_scan"] == \
        {"kernel": 0, "composite": 1}
    assert ops.kernel_paths.last_reason("kda_scan") == reason
    np.testing.assert_allclose(got, recurrence(*args), atol=2e-6)
    with pytest.raises(ValueError, match="power of two"):
        ops.kda_scan(*args, chunk=48)
    assert ops.kernel_paths.counts()["kda_scan"]["composite"] == 1


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_conv_without_bias_is_the_plain_taps(dtype):
    r = np.random.default_rng(1)
    x = jnp.asarray(r.normal(size=(2, 9, 6)), dtype)
    w = jnp.asarray(r.normal(size=(6, 4)), dtype)
    xp = jnp.pad(x.astype(jnp.float32), [(0, 0), (3, 0), (0, 0)])
    want = sum(xp[:, j:j + 9] * w.astype(jnp.float32)[:, j]
               for j in range(4))
    np.testing.assert_allclose(
        ops.causal_conv1d(x, w).astype(jnp.float32), want,
        atol=1e-6 if dtype == "float32" else 0.1)


# ---------------------------------------------------------------------------
# flash attention at two widths
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("d,dv,hkv", [(192, 128, 2), (192, 128, 1),
                                      (128, 64, 2), (128, 128, 2),
                                      (64, 64, 1)])
def test_flash_kernel_takes_a_key_and_a_value_width(d, dv, hkv):
    """The interpreted kernels against the composite, forward and all
    three gradients; equal widths go the way they always went."""
    from paddle_tpu.core.tensor import Tensor
    r = np.random.default_rng(d + dv)
    n = lambda *shape: jnp.asarray(r.normal(size=shape), jnp.float32)
    q, k, v = n(2, 256, 2, d), n(2, 256, hkv, d), n(2, 256, hkv, dv)
    weigh = jnp.cos(jnp.arange(float(dv)))

    def run(q, k, v):
        out = F.flash_attention(Tensor(q), Tensor(k), Tensor(v), causal=True)
        return jnp.sum(out.data * weigh), out.data

    ops.kernel_paths.reset()
    ops.set_interpret_mode(True)
    try:
        (_, out), grads = jax.value_and_grad(
            run, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    finally:
        ops.set_interpret_mode(False)
    assert ops.kernel_paths.counts()["flash_attention"] == \
        {"kernel": 1, "composite": 0}
    (_, want), want_grads = jax.value_and_grad(
        run, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    assert out.shape == (2, 256, 2, dv)
    np.testing.assert_allclose(out, want, atol=1e-5)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(a, b, atol=2e-5)
    # and the scores' scale is the KEY width's
    plain = jax.nn.softmax(jnp.where(
        jnp.tril(jnp.ones((256, 256), bool)),
        jnp.einsum("qd,kd->qk", q[0, :, 0], k[0, :, 0]) * d ** -0.5,
        -1e30), -1) @ v[0, :, 0]
    np.testing.assert_allclose(out[0, :, 0], plain, atol=1e-5)


def test_flash_refuses_no_width_it_took_before():
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    assert all(fa._width_served(d) for d in (64, 128, 192, 256))
    assert not any(fa._width_served(d) for d in (32, 96, 160))
    # strips of 8192 x (128 + 128) in bf16 ask for nothing, 192 does
    assert fa._strip_room(8192, 128, 128, 2) == {}
    assert fa._strip_room(2048, 64, 64, 2) == {}
    assert "compiler_params" in fa._strip_room(8192, 192, 128, 2)


# ---------------------------------------------------------------------------
# the gated experts and the shares
# ---------------------------------------------------------------------------
def moe_share(ref_kw, flat, lo, hi, layer=1):
    """One share's MoELayer holding experts lo..hi of the full weights."""
    share = moe.MoELayer(
        ref_kw["hidden_size"], ref_kw["moe_intermediate_size"],
        num_experts=16, top_k=4, capacity_factor=None,
        routed_scaling=2.446, held_experts=(lo, hi), activation="swiglu")
    pre = f"model.layers.{layer}.mlp.routed."
    params = {"gate": flat[pre + "gate"],
              "e_score_correction_bias":
                  flat[pre + "e_score_correction_bias"]}
    for leaf in ("w_gate", "w_up", "w_down"):
        params["experts." + leaf] = flat[pre + "experts." + leaf][lo:hi]
    return share, params


def layer_leaves(flat, layer=1):
    mark = f".layers.{layer}."
    return {k.split(mark)[1]: v for k, v in flat.items() if mark in k}


def test_swiglu_experts_are_the_plain_loop():
    """silu(x W_gate) * (x W_up) then W_down, expert by expert, weighted
    by the router's own weights: outputs and the gradients of all three
    stacked weights."""
    ref_kw, _ = small(held_experts=[0, 16])
    flat = seeded(ref_kw)
    c = R.cfg(ref_kw)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 40, 64)),
                    jnp.float32)
    share, params = moe_share(ref_kw, flat, 0, 16)
    assert sorted(n for n, _ in share.named_parameters()) == [
        "e_score_correction_bias", "experts.w_down", "experts.w_gate",
        "experts.w_up", "gate"]
    p = layer_leaves(flat)
    mm = lambda a, b: jnp.matmul(a, b)

    def program(experts):
        y, _ = functional_call(share, {**params, **experts},
                               buffers_of(share), x)
        return jnp.sum(y * jnp.cos(jnp.arange(64.0))), y

    def plain(experts):
        q = {**p, **{"mlp.routed." + k: v for k, v in experts.items()}}
        y = R.moe_routed(c, x[0], q, mm, held=(0, 16))
        return jnp.sum(y * jnp.cos(jnp.arange(64.0))), y

    experts = {k: v for k, v in params.items() if k.startswith("experts.")}
    (_, got), grads = jax.value_and_grad(program, has_aux=True)(experts)
    (_, want), want_grads = jax.value_and_grad(plain, has_aux=True)(experts)
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    for name in experts:
        np.testing.assert_allclose(grads[name], want_grads[name], atol=2e-5,
                                   err_msg=name)


def test_a_gated_activation_is_refused_on_the_capacity_path():
    with pytest.raises(ValueError, match="dropless"):
        moe.MoELayer(16, 32, 4, capacity_factor=1.25, activation="swiglu")


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of 4 experts each, the shared expert counted once,
    against the reference layer holding all 16."""
    ref_kw, _ = small(held_experts=[0, 16])
    flat = seeded(ref_kw)
    c = R.cfg(ref_kw)
    x = jnp.asarray(np.random.default_rng(9).normal(size=(2, 24, 64)),
                    jnp.float32)
    total, pairs = 0.0, 0
    for lo in range(0, 16, 4):
        share, params = moe_share(ref_kw, flat, lo, lo + 4)
        y, bufs = functional_call(share, params, buffers_of(share), x)
        total = total + y
        stats = np.asarray(bufs["expert_stats"])
        assert stats[:4].sum() == stats[4]          # nothing dropped
        assert stats[5] == 48
        pairs += int(stats[4])
    assert pairs == 48 * 4                          # every pair on a share
    p = layer_leaves(flat)
    mm = lambda a, b: jnp.matmul(a, b)
    cut = lambda lo, hi: {
        **p, **{f"mlp.routed.experts.{leaf}":
                p[f"mlp.routed.experts.{leaf}"][lo:hi]
                for leaf in ("w_gate", "w_up", "w_down")}}
    for r in range(2):
        want = R.moe_routed(c, x[r], p, mm, held=(0, 16)) + \
            R.shared_expert(x[r], p, mm)
        got = total[r] + R.shared_expert(x[r], p, mm)
        np.testing.assert_allclose(got, want, atol=2e-5)
        # and one share alone is the reference's same share
        share, params = moe_share(ref_kw, flat, 4, 8)
        y, _ = functional_call(share, params, buffers_of(share), x)
        np.testing.assert_allclose(
            y[r], R.moe_routed(c, x[r], cut(4, 8), mm, held=(4, 8)),
            atol=2e-5)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("layers", [2, 5, 8])
def test_parameter_names_are_the_references(layers):
    ref_kw, kw = small(num_hidden_layers=layers)
    model = KimiLinearForCausalLM(KimiLinearConfig(**kw))
    spec = R.param_spec(ref_kw)
    params = dict(model.named_parameters())
    assert set(params) == set(spec)
    assert {n: tuple(p.shape) for n, p in params.items()} == \
        {n: tuple(s) for n, s in spec.items()}


def test_layer_kinds_follow_the_configs_lists():
    """Mixers from linear_attn_config's two lists (counted from 1), the
    feed-forward dense for the first first_k_dense_replace layers."""
    from paddle_tpu.models import kimi_linear as K
    _, kw = small(num_hidden_layers=8, first_k_dense_replace=2)
    model = KimiLinearForCausalLM(KimiLinearConfig(**kw))
    layers = list(model.model.layers)
    assert [type(l.self_attn) for l in layers] == [
        K.MLAttention if i in (4, 8) else K.KDAMixer for i in range(1, 9)]
    assert [type(l.mlp) for l in layers] == \
        [K.KimiMLP] * 2 + [K.KimiMoE] * 6
    assert layers[0].mlp.gate_proj.weight.shape == [64, 128]
    assert layers[2].mlp.routed.experts.w_gate.shape == [4, 64, 32]
    assert layers[2].mlp.routed.gate.shape == [64, 16]     # all 16 scored
    # the published lists: 20 KDA, 7 MLA, three to one
    pub = KimiLinearConfig(held_experts=(0, 8))
    kinds = [pub.mixer_kind(i) for i in range(1, 28)]
    assert kinds.count("kda") == 20 and kinds.count("mla") == 7
    assert [i for i in range(1, 28) if kinds[i - 1] == "mla"] == \
        [4, 8, 12, 16, 20, 24, 27]
    assert [pub.ffn_kind(i) for i in (1, 2, 27)] == ["dense", "moe", "moe"]
    with pytest.raises(ValueError, match="layer 3"):
        KimiLinearConfig(num_hidden_layers=3, linear_attn_config={
            **SMALL["linear_attn_config"], "kda_layers": [1, 2]})


def test_parameter_count_at_the_published_widths():
    """The benchmark's cut from shapes alone (nothing is allocated): the
    issue's table, layer by layer."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "kimi-linear-ep32-train.json")) as f:
        kw = json.load(f)["model"]["kwargs"]
    spec = R.param_spec(kw)
    size = lambda pre: sum(math.prod(s) for n, s in spec.items()
                           if n.startswith(pre))
    assert size("model.layers.0.self_attn.") == 39_514_272      # KDA
    assert size("model.layers.3.self_attn.") == 29_114_880      # MLA
    assert size("model.layers.0.") == 103_219_872       # KDA + dense
    assert size("model.layers.1.") == size("model.layers.2.") == \
        size("model.layers.4.") == 103_809_952          # KDA + 8 experts
    assert size("model.layers.3.") == 93_410_560        # MLA + 8 experts
    assert size("model.embed_tokens.") + size("lm_head.") == 94_371_840
    assert size("") == 602_434_432
    assert spec["model.layers.1.mlp.routed.gate"] == (2304, 256)
    # the program's configuration names the same layers
    cfg = KimiLinearConfig(**kw)
    assert [cfg.mixer_kind(i) for i in range(1, 6)] == \
        ["kda", "kda", "kda", "mla", "kda"]
    assert [cfg.ffn_kind(i) for i in range(1, 6)] == ["dense"] + ["moe"] * 4


@pytest.mark.parametrize("length", [70, 32])
def test_logits_match_reference(length):
    ref_kw, kw = small()
    flat = seeded(ref_kw)
    model = KimiLinearForCausalLM(KimiLinearConfig(**kw))
    ids, _ = ids_of(2, length)
    out, _ = functional_call(model, flat, buffers_of(model),
                             jnp.asarray(ids), training=False)
    tree = R.stack(flat, ref_kw)
    for r in range(2):
        want = R.logits(ref_kw, tree, jnp.asarray(ids[r]))
        np.testing.assert_allclose(out[r], want, atol=3e-5)


def test_mla_through_the_flash_kernel_matches_reference():
    """The latent attention layer through the Pallas kernels
    (interpreted) at the published head widths: 192-wide scores, 128-wide
    values, 2 heads, 128 positions."""
    ref_kw, kw = small(
        num_hidden_layers=1, num_attention_heads=2, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        linear_attn_config={**SMALL["linear_attn_config"],
                            "full_attn_layers": [1], "kda_layers": [2]})
    flat = seeded(ref_kw)
    model = KimiLinearForCausalLM(KimiLinearConfig(**kw))
    ids, _ = ids_of(1, 128)
    ops.kernel_paths.reset()
    ops.set_interpret_mode(True)
    try:
        out, _ = functional_call(model, flat, buffers_of(model),
                                 jnp.asarray(ids), training=False)
    finally:
        ops.set_interpret_mode(False)
    assert ops.kernel_paths.counts()["flash_attention"] == \
        {"kernel": 1, "composite": 0}
    want = R.logits(ref_kw, R.stack(flat, ref_kw), jnp.asarray(ids[0]))
    np.testing.assert_allclose(out[0], want, atol=3e-5)


def reference_loss_fn(ref_kw, ids, labels):
    def loss(tree):
        total = 0.0
        for r in range(ids.shape[0]):
            lg = R._logits_fn(R._key(ref_kw), "float32")(
                tree, jnp.asarray(ids[r]))
            lse = jax.nn.logsumexp(lg, -1)
            total = total + jnp.sum(lse - jnp.take_along_axis(
                lg, jnp.asarray(labels[r])[:, None], -1)[:, 0])
        return total / ids.size
    return loss


def test_loss_and_every_gradient_match_reference():
    """Through the training forward (remat a layer, fused cross-entropy)
    and the criterion, against the reference's own loss."""
    ref_kw, kw = small()
    flat = seeded(ref_kw)
    model = KimiLinearForCausalLM(KimiLinearConfig(**kw, fused_ce=True))
    model.enable_recompute()
    crit = GPTPretrainingCriterion()
    ids, labels = ids_of(2, 70)
    from paddle_tpu.core.autograd import no_grad
    from paddle_tpu.core.tensor import Tensor

    def program_loss(params):
        with no_grad():
            out, _ = functional_call(model, params, buffers_of(model),
                                     jnp.asarray(ids), training=True)
            out = jax.tree_util.tree_map(Tensor, out)
            return crit(out, Tensor(jnp.asarray(labels))).data

    loss, grads = jax.value_and_grad(program_loss)(flat)
    want_loss, want = jax.value_and_grad(
        reference_loss_fn(ref_kw, ids, labels))(R.stack(flat, ref_kw))
    want = R.unstack_names(want)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    assert set(grads) == set(want)
    for name in sorted(want):
        scale = float(jnp.max(jnp.abs(want[name]))) + 1e-12
        np.testing.assert_allclose(
            grads[name] / scale, want[name] / scale, atol=3e-4,
            err_msg=name)
    # the correction bias only picks: no gradient reaches it
    assert float(jnp.max(jnp.abs(
        grads["model.layers.1.mlp.routed.e_score_correction_bias"]))) == 0.0


def test_three_steps_through_spmd_trainer_follow_the_reference():
    """SpmdTrainer's own step (remat a layer, fused CE, Adam) in float32
    against the reference's train_steps on the same batches: the three
    losses, every leaf's first gradient (Adam's first moment over 0.1)
    and every leaf's change; and nothing recompiles."""
    from paddle_tpu.distributed import SpmdTrainer, create_mesh
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.optimizer import Adam
    from paddle_tpu.utils import compile_counter
    ref_kw, kw = small()
    flat = seeded(ref_kw)
    model = KimiLinearForCausalLM(KimiLinearConfig(**kw, fused_ce=True))
    # the trainer and the reference's Adam both donate what they are given
    fresh = lambda: {n: jnp.array(v) for n, v in flat.items()}
    for name, p in dict(model.named_parameters()).items():
        p.data = fresh()[name]
    crit = GPTPretrainingCriterion()
    st = DistributedStrategy()
    st.recompute = True
    st.recompute_configs = {"policy": "full"}
    trainer = SpmdTrainer(
        model, Adam(parameters=model.parameters(), learning_rate=1e-3),
        lambda o, l: crit(o, l), mesh=create_mesh(
            {"dp": 1}, devices=jax.devices()[:1]), strategy=st)
    moe.reset_expert_totals()
    batches = [ids_of(2, 70, seed=s) for s in range(3)]
    want = R.train_steps(ref_kw, fresh(), batches, {"learning_rate": 1e-3})
    losses = [float(trainer.train_step(*batches[0]))]
    norm = lambda a: float(jnp.sqrt(jnp.sum(jnp.square(a))))
    first_grad = {n: norm(s["moment1"]) / 0.1
                  for n, s in trainer.opt_state.items()}
    snap = compile_counter.snapshot()
    losses += [float(trainer.train_step(*b)) for b in batches[1:]]
    assert snap.new_compiles == 0 and snap.new_traces == 0
    np.testing.assert_allclose(losses, want["losses"], rtol=2e-6)
    floor = float(np.median(list(want["grad_norms"].values())))
    for name, ref in want["grad_norms"].items():
        assert abs(first_grad[name] - ref) <= 2e-3 * max(ref, floor), name
    for name, ref in want["delta_norms"].items():
        got = norm(trainer.params[name] - flat[name])
        assert abs(got - ref) <= 0.02 * ref + 1e-7, name
    totals = trainer.stats["expert_stats"]
    assert len(totals["layers"]) == 4 and totals["pairs_dropped"] == 0
    assert all(rec["tokens"] == 3 * 140 for rec in totals["layers"].values())
    # 4 of 16 experts a token, 4 held: 1 local pair a token expected
    assert 0.6 < totals["local_pairs_per_token"] < 1.4
