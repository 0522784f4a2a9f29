"""The Nemotron-H hybrid stack (Mamba-2 / MoE / attention) against its
plain reference (``benchmark/references/nemotron_h.py``) at a small size
on the CPU: logits, loss and every gradient on seeded weights; the
chunked scan against the sequential recurrence; the router; the shares
of one MoE layer adding up to the uncut layer, over the short dropless
buffer and over the worst case; the grouped matmul kernel against its
composite; a step through ``SpmdTrainer``."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights as W
from benchmark.references import nemotron_h as R
from paddle_tpu import ops
from paddle_tpu.distributed import moe
from paddle_tpu.func import functional_call
from paddle_tpu.models import (GPTPretrainingCriterion, NemotronHConfig,
                               NemotronHForCausalLM)

gm = importlib.import_module("paddle_tpu.ops.grouped_matmul")
ssd = importlib.import_module("paddle_tpu.ops.ssd_scan")

SMALL = dict(
    vocab_size=256, hidden_size=128, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, mamba_num_heads=8,
    mamba_head_dim=16, n_groups=2, ssm_state_size=16, conv_kernel=4,
    n_routed_experts=16, num_experts_per_tok=6, moe_intermediate_size=64,
    moe_shared_expert_intermediate_size=128, held_experts=[0, 4])
INIT = [{"match": "norm\\.weight$|norm_f\\.weight$|mixer\\.D$",
         "kind": "ones"},
        {"match": "A_log$|dt_bias$|conv1d\\.bias$|correction_bias$",
         "kind": "zeros"},
        {"match": ".", "kind": "normal", "std": 0.02}]


def small(pattern, **over):
    """(reference kwargs, program kwargs) of a small stack."""
    ref = {**SMALL, "hybrid_override_pattern": pattern, **over}
    return ref, {**ref, "chunk_size": 16}


def seeded(ref_kw, seed=5):
    """Seeded weights with the leaves the benchmark draws at 0 or 1
    (A_log, dt_bias, D, the conv's and the router's bias) moved off
    them, so that a wrong use of one shows."""
    flat = W.make_weights(seed, R.param_spec(ref_kw), INIT, "float32")
    key = jax.random.PRNGKey(seed)
    for i, name in enumerate(sorted(flat)):
        if name.rsplit(".", 1)[-1] in ("A_log", "dt_bias", "D", "bias",
                                       "e_score_correction_bias"):
            flat[name] = flat[name] + 0.2 * jax.random.normal(
                jax.random.fold_in(key, i), flat[name].shape)
    return flat


def buffers_of(model):
    return {n: b.data for n, b in model.named_buffers() if b is not None}


def ids_of(rows, length, seed=0):
    ids = np.random.default_rng(seed).integers(
        0, SMALL["vocab_size"], (rows, length)).astype(np.int32)
    return ids, np.roll(ids, -1, axis=1)


@pytest.mark.parametrize("pattern", ["ME*", "MEMEM*EME"])
def test_parameter_names_are_the_references(pattern):
    ref_kw, kw = small(pattern)
    model = NemotronHForCausalLM(NemotronHConfig(**kw))
    spec = R.param_spec(ref_kw)
    params = dict(model.named_parameters())
    assert set(params) == set(spec)
    assert {n: tuple(p.shape) for n, p in params.items()} == \
        {n: tuple(s) for n, s in spec.items()}


@pytest.mark.parametrize("pattern,length", [("ME*", 40), ("M*E", 16),
                                            ("EM", 33)])
def test_logits_match_reference(pattern, length):
    ref_kw, kw = small(pattern)
    flat = seeded(ref_kw)
    model = NemotronHForCausalLM(NemotronHConfig(**kw))
    ids, _ = ids_of(2, length)
    out, _ = functional_call(model, flat, buffers_of(model),
                             jnp.asarray(ids), training=False)
    tree = R.stack(flat, ref_kw)
    for r in range(2):
        want = R.logits(ref_kw, tree, jnp.asarray(ids[r]))
        np.testing.assert_allclose(out[r], want, atol=2e-5)


def test_flash_kernel_path_matches_reference():
    """The attention layer through the Pallas kernel (interpreted): 4
    query heads on 2 KV heads of 64, 128 positions."""
    ref_kw, kw = small("M*", head_dim=64)
    flat = seeded(ref_kw)
    model = NemotronHForCausalLM(NemotronHConfig(**kw))
    ids, _ = ids_of(1, 128)
    ops.kernel_paths.reset()
    ops.set_interpret_mode(True)
    try:
        out, _ = functional_call(model, flat, buffers_of(model),
                                 jnp.asarray(ids), training=False)
    finally:
        ops.set_interpret_mode(False)
    assert ops.kernel_paths.counts()["flash_attention"]["kernel"] == 1
    want = R.logits(ref_kw, R.stack(flat, ref_kw), jnp.asarray(ids[0]))
    np.testing.assert_allclose(out[0], want, atol=2e-5)


def test_loss_and_every_gradient_match_reference():
    """Through the training forward (remat a layer, fused cross-entropy)
    and the criterion, against the reference's own loss."""
    ref_kw, kw = small("MEMEM*EME")
    flat = seeded(ref_kw)
    model = NemotronHForCausalLM(NemotronHConfig(**kw, fused_ce=True))
    model.enable_recompute()
    crit = GPTPretrainingCriterion()
    ids, labels = ids_of(2, 24)
    from paddle_tpu.core.autograd import no_grad
    from paddle_tpu.core.tensor import Tensor

    def program_loss(params):
        with no_grad():
            out, _ = functional_call(model, params, buffers_of(model),
                                     jnp.asarray(ids), training=True)
            out = jax.tree_util.tree_map(Tensor, out)
            return crit(out, Tensor(jnp.asarray(labels))).data

    def reference_loss(tree):
        total = 0.0
        for r in range(2):
            lg = R._logits_fn(R._key(ref_kw), "float32")(
                tree, jnp.asarray(ids[r]))
            lse = jax.nn.logsumexp(lg, -1)
            total = total + jnp.sum(lse - jnp.take_along_axis(
                lg, jnp.asarray(labels[r])[:, None], -1)[:, 0])
        return total / ids.size

    loss, grads = jax.value_and_grad(program_loss)(flat)
    want_loss, want = jax.value_and_grad(reference_loss)(
        R.stack(flat, ref_kw))
    want = R.unstack_names(want)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    assert set(grads) == set(want)
    for name in sorted(want):
        scale = float(jnp.max(jnp.abs(want[name]))) + 1e-12
        np.testing.assert_allclose(
            grads[name] / scale, want[name] / scale, atol=2e-4,
            err_msg=name)
    # the correction bias only picks: no gradient reaches it
    assert float(jnp.max(jnp.abs(
        grads["backbone.layers.1.mixer.routed.e_score_correction_bias"]
    ))) == 0.0


def scan_inputs(seed, rows, length, heads=8, p=16, groups=2, n=16):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return (f(rows, length, heads, p),
            jax.nn.softplus(f(rows, length, heads)),
            -jnp.exp(0.3 * f(heads)), f(rows, length, groups, n),
            f(rows, length, groups, n))


@pytest.mark.parametrize("length,chunk", [(50, 16), (64, 16), (7, 16),
                                          (130, 128)])
def test_chunked_scan_matches_the_sequential_recurrence(length, chunk):
    x, dt, a, b, c = scan_inputs(1, 2, length)
    got = ssd.ssd_scan(x, dt, a, b, c, chunk=chunk)
    for r in range(2):
        want = R.recurrence(x[r], dt[r], a, b[r], c[r])
        np.testing.assert_allclose(got[r], want, atol=3e-5, rtol=1e-5)


def test_chunked_scan_gradient_matches_the_recurrence():
    """At a length that is no multiple of the chunk."""
    x, dt, a, b, c = scan_inputs(2, 1, 41)
    probe = jnp.asarray(np.random.default_rng(3).normal(size=x.shape[1:]),
                        jnp.float32)
    chunked = lambda *t: jnp.sum(ssd.ssd_scan(
        t[0][None], t[1][None], t[2], t[3][None], t[4][None],
        chunk=16)[0] * probe)
    plain = lambda *t: jnp.sum(R.recurrence(*t) * probe)
    args = (x[0], dt[0], a, b[0], c[0])
    got = jax.grad(chunked, argnums=range(5))(*args)
    want = jax.grad(plain, argnums=range(5))(*args)
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(g, w_, atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_conv_backward_matches_the_plain_taps(dtype):
    """The convolution's hand-written backward (dx in the input's dtype,
    the weight's and the bias's sums over every position in float32)
    against the taps differentiated as written, in float32."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 37, 24)), dtype)
    w = jnp.asarray(rng.normal(size=(24, 4)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(24,)), jnp.float32)
    probe = jnp.asarray(rng.normal(size=(2, 37, 24)), jnp.float32)

    def plain(x, w, b):
        xp = jnp.pad(x.astype(jnp.float32), [(0, 0), (3, 0), (0, 0)])
        return b + sum(xp[:, j:j + 37] * w[:, j] for j in range(4))

    via = lambda fn: lambda *a: jnp.sum(
        jnp.tanh(fn(*a).astype(jnp.float32)) * probe)
    tol = 1e-5 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(
        ssd.causal_conv1d(x, w, b).astype(jnp.float32), plain(x, w, b),
        atol=tol, rtol=tol)
    got = jax.grad(via(ssd.causal_conv1d), (0, 1, 2))(x, w, b)
    want = jax.grad(via(plain), (0, 1, 2))(x, w, b)
    assert got[0].dtype == x.dtype and got[1].dtype == got[2].dtype == \
        jnp.float32
    for g, w_ in zip(got, want):
        np.testing.assert_allclose(g.astype(jnp.float32),
                                   w_.astype(jnp.float32),
                                   atol=tol * 10, rtol=tol)


def test_scan_has_one_path_and_notes_no_fallback():
    """A count in kernel_paths means a kernel was passed over; the scan
    has none, so a run's fallbacks read 0 unless a real kernel fell."""
    ops.kernel_paths.reset()
    ssd.ssd_scan(*scan_inputs(4, 1, 16), chunk=16)
    assert "ssd_scan" not in ops.kernel_paths.counts()


def test_router_choice_weights_and_scaling():
    ref_kw, _ = small("E")
    c = R.cfg(ref_kw)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(64, 128)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(128, 16)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(16,)), jnp.float32)
    idx, w = moe.route_top_k(jnp.dot(x, gate), bias, 6, True, 2.5)
    want_idx, want_w = R.route(c, x, gate, bias)
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(w, want_w, rtol=1e-6)
    # the bias picks and does not weigh; the weights sum to the scaling
    score = jax.nn.sigmoid(jnp.dot(x, gate))
    np.testing.assert_array_equal(
        jnp.sort(idx, -1), jnp.sort(jax.lax.top_k(score + bias, 6)[1], -1))
    np.testing.assert_allclose(jnp.sum(w, -1), 2.5, rtol=1e-5)
    chosen = jnp.take_along_axis(score, idx, -1)
    np.testing.assert_allclose(
        w, 2.5 * chosen / jnp.sum(chosen, -1, keepdims=True), rtol=1e-5)


def moe_share(ref_kw, flat, lo, hi):
    """One share's MoELayer holding experts lo..hi of the full weights."""
    layer = moe.MoELayer(
        128, ref_kw["moe_intermediate_size"], num_experts=16, top_k=6,
        capacity_factor=None, routed_scaling=2.5, held_experts=(lo, hi),
        activation="relu2")
    pre = "backbone.layers.0.mixer.routed."
    params = {"gate": flat[pre + "gate"],
              "e_score_correction_bias":
                  flat[pre + "e_score_correction_bias"],
              "experts.w_up": flat[pre + "experts.w_up"][lo:hi],
              "experts.w_down": flat[pre + "experts.w_down"][lo:hi]}
    return layer, params


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of 4 experts each, the shared expert counted once,
    against the reference layer holding all 16."""
    ref_kw, _ = small("E", held_experts=[0, 16])
    flat = seeded(ref_kw)
    c = R.cfg(ref_kw)
    x = jnp.asarray(np.random.default_rng(9).normal(size=(2, 24, 128)),
                    jnp.float32)
    total, pairs = 0.0, 0
    for lo in range(0, 16, 4):
        layer, params = moe_share(ref_kw, flat, lo, lo + 4)
        y, bufs = functional_call(layer, params, buffers_of(layer), x)
        total = total + y
        stats = np.asarray(bufs["expert_stats"])
        assert stats[:4].sum() == stats[4]          # nothing dropped
        assert stats[5] == 48
        pairs += int(stats[4])
    assert pairs == 48 * 6                          # every pair on a share
    p = {k.split("layers.0.")[1]: v for k, v in flat.items()
         if ".layers.0." in k}
    mm = lambda a, b: jnp.matmul(a, b)
    for r in range(2):
        want = R.moe_routed(c, x[r], p, mm, held=(0, 16)) + \
            R.shared_expert(x[r], p, mm)
        got = total[r] + R.shared_expert(x[r], p, mm)
        np.testing.assert_allclose(got, want, atol=2e-5)
        # and one share alone is the reference's same share
        layer, params = moe_share(ref_kw, flat, 4, 8)
        y, _ = functional_call(layer, params, buffers_of(layer), x)
        np.testing.assert_allclose(
            y[r], R.moe_routed(c, x[r], p_share(p, 4, 8), mm, held=(4, 8)),
            atol=2e-5)


def p_share(p, lo, hi):
    return {**p, "mixer.routed.experts.w_up":
            p["mixer.routed.experts.w_up"][lo:hi],
            "mixer.routed.experts.w_down":
            p["mixer.routed.experts.w_down"][lo:hi]}


@pytest.mark.parametrize("case", ["even", "all_on_one", "none_held"])
def test_dropless_layout_keeps_every_pair(case):
    """Under any imbalance: every pair on a held expert has a row of its
    own in a tile of its expert, and the buffer's counters agree."""
    t, k, lo, held, tile = 40, 3, 2, 4, 16
    rng = np.random.default_rng(11)
    if case == "even":
        idx = np.stack([rng.permutation(12)[:k] for _ in range(t)])
    elif case == "all_on_one":
        idx = np.tile(np.array([3, 0, 1]), (t, 1))
    else:
        idx = np.tile(np.array([0, 1, 7]), (t, 1))
    lay = jax.tree_util.tree_map(np.asarray, moe.dropless_layout(
        jnp.asarray(idx, jnp.int32), lo, held, tile))
    m = lay["src_token"].shape[0]
    on_held = (idx >= lo) & (idx < lo + held)
    assert lay["assigned"] == on_held.sum() == lay["load"].sum()
    assert (lay["dest_row"][~on_held] == m).all()
    rows = lay["dest_row"][on_held]
    assert len(set(rows.tolist())) == rows.size and (rows < m).all()
    assert (rows // tile < lay["tiles_used"][0]).all()
    np.testing.assert_array_equal(
        lay["tile_group"][rows // tile], idx[on_held] - lo)
    tok, kk = np.nonzero(on_held)
    np.testing.assert_array_equal(lay["src_token"][rows], tok)
    np.testing.assert_array_equal(lay["src_pair"][rows], tok * k + kk)
    assert (lay["src_token"] < t).sum() == on_held.sum()
    used = lay["tile_group"][:lay["tiles_used"][0]]
    assert (np.diff(used) >= 0).all() and set(used) == set(range(held))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_grouped_matmul_kernel_matches_its_composite(dtype):
    """Interpreted: the product, dx and the per-expert dw."""
    rng = np.random.default_rng(13)
    t, k, tile = 300, 6, 128
    idx = jnp.asarray(np.stack([rng.permutation(16)[:k]
                                for _ in range(t)]), jnp.int32)
    lay = moe.dropless_layout(idx, 4, 4, tile)
    tiles = (lay["tile_group"], lay["tiles_used"])
    m = lay["src_token"].shape[0]
    x = jnp.where((lay["src_token"] < t)[:, None],
                  jnp.asarray(rng.normal(size=(m, 128)), jnp.float32), 0)
    w = jnp.asarray(rng.normal(size=(4, 128, 64)), jnp.float32)
    x, w = x.astype(dtype), w.astype(dtype)
    f32 = jnp.float32
    via = lambda fn: lambda a, b: jnp.sum(jnp.sin(fn(a, b).astype(f32)))
    kernel = lambda a, b: gm.grouped_matmul(a, b, *tiles, tile_m=tile)
    plain = lambda a, b: gm._composite(a, b, *tiles, tile)
    ops.kernel_paths.reset()
    ops.set_interpret_mode(True)
    try:
        got = kernel(x, w)
        g_got = jax.grad(via(kernel), (0, 1))(x, w)
    finally:
        ops.set_interpret_mode(False)
    assert ops.kernel_paths.counts()["grouped_matmul"] == \
        {"kernel": 2, "composite": 0}
    tol = 1e-4 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got.astype(f32), plain(x, w).astype(f32),
                               atol=tol)
    for g, w_ in zip(g_got, jax.grad(via(plain), (0, 1))(x, w)):
        np.testing.assert_allclose(g.astype(f32), w_.astype(f32),
                                   atol=tol * 10, rtol=tol)
    # off the chip and uninterpreted, the entry point takes the composite
    kernel(x, w)
    assert ops.kernel_paths.counts()["grouped_matmul"]["composite"] == 1


# ---------------------------------------------------------------------------
# the short dropless buffer and the worst case behind one `cond`
# ---------------------------------------------------------------------------
# 64 tokens x 3 choices, experts 4..8 of 16 held, tiles of 16 rows: the
# worst case is 12 + 4 = 16 tiles, the load to expect 48 pairs, the short
# buffer ceil(2 x 48 / 16) + 4 = 10 tiles.  Pairs on each held expert:
LOADS = {"short": (12, 12, 12, 12),             # 4 tiles
         "all_on_one": (48, 48, 48, 48),        # every pair held: 12 tiles
         "at_the_boundary": (48, 48, 33, 16),   # 3 + 3 + 3 + 1 = 10 tiles
         "one_past_it": (48, 48, 33, 17)}       # 3 + 3 + 3 + 2 = 11 tiles
SHORT_TILES, BRANCH_TOKENS = 10, 64


def routed_to(counts, seed=17):
    """``x [1, 64, 128]`` and a router ``gate [128, 16]`` that reads the
    first 16 features: token by token the top 3 are the experts this
    builds, ``counts[e]`` tokens on held expert ``4 + e`` and the other
    choices on absent ones."""
    rng = np.random.default_rng(seed)
    picks = [[] for _ in range(BRANCH_TOKENS)]
    for e, n in enumerate(counts):
        free = sorted(range(BRANCH_TOKENS),
                      key=lambda t: (len(picks[t]), (t + 16 * e) % 64))
        for t in free[:n]:
            picks[t].append(4 + e)
    absent = [e for e in range(16) if not 4 <= e < 8]
    x = rng.normal(size=(1, BRANCH_TOKENS, 128))
    for t, chosen in enumerate(picks):
        assert len(chosen) <= 3
        chosen = chosen + [absent[(t + j) % 12]
                           for j in range(3 - len(chosen))]
        x[0, t, :16] = rng.uniform(-0.3, 0.3, 16) - 1.5
        x[0, t, chosen] += 3.0
    gate = 0.01 * rng.normal(size=(128, 16))
    gate[:16] += np.eye(16)
    return jnp.asarray(x, jnp.float32), jnp.asarray(gate, jnp.float32)


def branch_layer(held=(4, 8), seed=19):
    """A layer of 16 experts, top 3, and seeded weights for its share."""
    layer = moe.MoELayer(128, 64, num_experts=16, top_k=3,
                         capacity_factor=None, routed_scaling=2.5,
                         held_experts=held, activation="relu2")
    rng = np.random.default_rng(seed)
    n = layer.held[1] - layer.held[0]
    params = {
        "e_score_correction_bias": jnp.zeros((16,), jnp.float32),
        "experts.w_up": jnp.asarray(
            0.1 * rng.normal(size=(n, 128, 64)), jnp.float32),
        "experts.w_down": jnp.asarray(
            0.1 * rng.normal(size=(n, 64, 128)), jnp.float32)}
    return layer, params


def through(layer, params, bufs, x):
    from paddle_tpu.core.autograd import no_grad
    with no_grad():
        return functional_call(layer, params, bufs, x)


@pytest.fixture
def tiles_of_16(monkeypatch):
    monkeypatch.setattr(moe, "DROPLESS_TILE", 16)
    assert moe.dropless_short_tiles(BRANCH_TOKENS, 3, 4, 16, 16) == \
        SHORT_TILES < BRANCH_TOKENS * 3 // 16 + 4


@pytest.mark.parametrize("load", sorted(LOADS))
def test_either_branch_is_the_plain_loop(tiles_of_16, load):
    """The layer's output and every gradient (x, gate, w_up, w_down)
    against the reference's loop over the held experts, under a load
    that takes the short buffer, one that takes the worst case, and the
    two on either side of the boundary; the buffer's last slots say
    which branch ran."""
    counts = LOADS[load]
    x, gate = routed_to(counts)
    layer, params = branch_layer()
    params["gate"] = gate
    probe = jnp.asarray(np.random.default_rng(23).normal(size=x.shape),
                        jnp.float32)
    ref_kw, _ = small("E", held_experts=[4, 8], num_experts_per_tok=3)
    c = R.cfg(ref_kw)

    def program(params, x):
        y, bufs = through(layer, params, buffers_of(layer), x)
        return jnp.sum(y * probe), (y, bufs["expert_stats"])

    def reference(params, x):
        p = {"mixer.routed." + k: v for k, v in params.items()}
        y = R.moe_routed(c, x[0], p, jnp.matmul, held=(4, 8))
        return jnp.sum(y * probe[0]), y

    (_, (y, stats)), got = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True))(params, x)
    (_, want_y), want = jax.value_and_grad(
        reference, argnums=(0, 1), has_aux=True)(params, x)
    stats = np.asarray(stats)
    np.testing.assert_array_equal(stats[:4], counts)    # nothing dropped
    assert stats[4] == sum(counts) and stats[5] == BRANCH_TOKENS
    tiles = sum(-(-n // 16) for n in counts)
    assert stats[6] == BRANCH_TOKENS                # it had a branch
    assert stats[7] == (BRANCH_TOKENS if tiles <= SHORT_TILES else 0)
    np.testing.assert_allclose(y[0], want_y, atol=2e-5)
    for name in ("gate", "experts.w_up", "experts.w_down"):
        np.testing.assert_allclose(got[0][name], want[0][name], atol=2e-5,
                                   err_msg=name)
    np.testing.assert_allclose(got[1], want[1], atol=2e-5)
    assert float(jnp.max(jnp.abs(got[0]["gate"]))) > 1e-3


@pytest.mark.parametrize("steps,share", [
    (("short",), 1.0), (("all_on_one",), 0.0),
    (("short", "one_past_it", "at_the_boundary"), 2 / 3)])
def test_short_buffer_share_counts_the_branch_taken(tiles_of_16, steps,
                                                    share):
    """Over a sequence of steps, through ``publish_expert_totals``'
    take-and-zero: the buffer starts again from zero, a second reading
    of nothing leaves the share, and a further step moves it."""
    layer, params = branch_layer()
    bufs = buffers_of(layer)
    step = jax.jit(lambda params, bufs, x: through(layer, params, bufs, x))
    moe.reset_expert_totals()
    for load in steps:
        x, params["gate"] = routed_to(LOADS[load])
        _, bufs = step(params, bufs, x)
    held = {"layer.expert_stats": bufs["expert_stats"]}
    totals = moe.publish_expert_totals(held)
    assert totals["short_buffer_share"] == pytest.approx(share)
    assert totals["pairs_dropped"] == 0
    rec = totals["layers"]["layer.expert_stats"]
    assert rec["tokens"] == rec["branch_tokens"] == \
        BRANCH_TOKENS * len(steps)
    assert rec["short_tokens"] == round(share * rec["tokens"])
    assert int(np.asarray(held["layer.expert_stats"]).sum()) == 0
    assert moe.publish_expert_totals(held)["short_buffer_share"] == \
        pytest.approx(share)
    x, params["gate"] = routed_to(LOADS["short"])
    _, bufs = step(params, {"expert_stats": held["layer.expert_stats"]}, x)
    again = moe.publish_expert_totals(
        {"layer.expert_stats": bufs["expert_stats"]})
    n = len(steps)
    assert again["short_buffer_share"] == pytest.approx(
        (share * n + 1) / (n + 1))
    moe.reset_expert_totals()
    assert moe.expert_totals()["short_buffer_share"] is None


@pytest.mark.parametrize("held,branches", [(None, 0), ((4, 8), 1)])
def test_a_layer_holding_every_expert_has_no_branch(tiles_of_16, held,
                                                    branches):
    """Its worst case is the load to expect, so its program carries no
    ``cond``, its buffer's last two slots stay at zero and the share
    reads None; a share of the experts carries one."""
    x, gate = routed_to(LOADS["short"])
    layer, params = branch_layer(held)
    params["gate"] = gate
    run = lambda params, x: through(layer, params, buffers_of(layer), x)
    text = str(jax.make_jaxpr(run)(params, x))
    assert text.count(" cond[") == branches, text[:2000]
    _, bufs = run(params, x)
    np.testing.assert_array_equal(bufs["expert_stats"][-2:],
                                  [branches * BRANCH_TOKENS] * 2)
    moe.reset_expert_totals()
    totals = moe.publish_expert_totals({"layer.expert_stats":
                                        bufs["expert_stats"]})
    assert totals["short_buffer_share"] == (1.0 if branches else None)
    # the three existing fields as before: every pair, or the 48 held
    assert totals["local_pairs_per_token"] == (3.0 if held is None
                                               else 48 / BRANCH_TOKENS)
    assert totals["pairs_dropped"] == 0
    moe.reset_expert_totals()


def test_capacity_arguments_are_refused_on_the_wrong_path():
    with pytest.raises(ValueError, match="dropless"):
        moe.MoELayer(16, 32, 4, capacity_factor=1.25, held_experts=(0, 2))
    with pytest.raises(ValueError, match="no range"):
        moe.MoELayer(16, 32, 4, capacity_factor=None, held_experts=(2, 6))


@pytest.mark.parametrize("flips", [False, True],
                         ids=["one_buffer", "branch_flips"])
def test_train_step_through_spmd_trainer_never_recompiles(monkeypatch,
                                                          flips):
    """With tiles of 512 the 64 tokens' worst case is as short as the
    short buffer and the step has no branch; with tiles of 8 it has one
    (52 tiles against 28), and a router pushed onto the held experts for
    one step (4 x 64 pairs: 32 tiles) takes the worst case and comes
    back, in the same executable."""
    from paddle_tpu.distributed import SpmdTrainer, create_mesh
    if flips:
        monkeypatch.setattr(moe, "DROPLESS_TILE", 8)
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.optimizer import Adam
    from paddle_tpu.utils import compile_counter
    ref_kw, kw = small("MEM*E")
    model = NemotronHForCausalLM(NemotronHConfig(**kw, fused_ce=True))
    flat = seeded(ref_kw)
    bias = {n: np.asarray(v) for n, v in flat.items()
            if n.endswith("e_score_correction_bias")}
    for name, p in dict(model.named_parameters()).items():
        p.data = flat[name]
    crit = GPTPretrainingCriterion()
    st = DistributedStrategy()
    st.amp = True
    st.recompute = True
    st.recompute_configs = {"policy": "full"}
    trainer = SpmdTrainer(
        model, Adam(parameters=model.parameters(), learning_rate=1e-3),
        lambda o, l: crit(o, l), mesh=create_mesh(
            {"dp": 1}, devices=jax.devices()[:1]), strategy=st)
    moe.reset_expert_totals()
    batches = [ids_of(2, 32, seed=s) for s in range(3)]
    first = float(trainer.train_step(*batches[0]))
    snap = compile_counter.snapshot()
    losses = []
    for push, batch in zip((10.0 * flips, 0.0), batches[1:]):
        for n, value in bias.items():
            trainer.params[n] = jax.device_put(
                value + push * (np.arange(16) < 4),
                trainer.params[n].sharding)
        losses.append(float(trainer.train_step(*batch)))
    assert snap.new_compiles == 0 and snap.new_traces == 0
    assert np.isfinite([first] + losses).all()
    assert abs(first - np.log(256)) < 0.1
    totals = trainer.stats["expert_stats"]
    assert totals == moe.expert_totals()
    assert len(totals["layers"]) == 2
    assert totals["pairs_dropped"] == 0
    assert all(rec["tokens"] == 3 * 64 for rec in totals["layers"].values())
    # 6 of 16 experts a token, 4 held: 1.5 local pairs a token expected
    # (the seeded correction bias moves it on 192 tokens), 4 in the step
    # whose router was pushed onto the held experts
    assert 0.5 < totals["local_pairs_per_token"] - flips * 2.5 / 3 < 2.5
    assert totals["short_buffer_share"] == (2 / 3 if flips else None)
    assert totals["load_max_over_mean"] >= 1.0
    # a reading TAKES the counts: the buffers start again from zero (an
    # int32 holds the tokens between two readings, the totals are Python
    # integers), the next step runs the same executable, and a second
    # reading adds its step to the totals
    assert all(int(np.asarray(b).sum()) == 0
               for n, b in trainer.buffers.items()
               if n.endswith("expert_stats"))
    snap = compile_counter.snapshot()
    trainer.train_step(*batches[0])
    assert snap.new_compiles == 0 and snap.new_traces == 0
    again = trainer.stats["expert_stats"]
    assert all(rec["tokens"] == 4 * 64 for rec in again["layers"].values())
    assert again["pairs_dropped"] == 0
