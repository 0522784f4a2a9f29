"""Pallas flash attention kernel tests (interpret mode on CPU).

Ground truth is the module's own XLA composite (`_composite`), itself
verified against `_sdpa_reference` elsewhere. Covers fwd, the fused
Pallas backward (dq/dk/dv from saved logsumexp), native GQA, and the
key-padding mask.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import importlib

# ops/__init__ re-exports the flash_attention FUNCTION under the same
# name as the module; fetch the module itself
fa = importlib.import_module("paddle_tpu.ops.flash_attention")


@pytest.fixture(autouse=True)
def _interpret():
    fa.set_interpret_mode(True)
    yield
    fa.set_interpret_mode(False)


def make_qkv(b=2, s=256, h=4, hkv=None, d=64, seed=0):
    rng = np.random.RandomState(seed)
    hkv = hkv or h
    q = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(b, s, hkv, d).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(b, s, hkv, d).astype(np.float32) * 0.3)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_composite(causal):
    q, k, v = make_qkv()
    out = fa.flash_attention(q, k, v, causal=causal)
    ref = fa._composite(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_forward_multi_block():
    """S=512 with block 256 exercises the online-softmax block loop."""
    q, k, v = make_qkv(b=1, s=512, h=2)
    out = fa.flash_attention(q, k, v, causal=True)
    ref = fa._composite(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_backward_matches_composite(causal):
    q, k, v = make_qkv(b=1, s=256, h=2)

    def loss_flash(q_, k_, v_):
        return (fa.flash_attention(q_, k_, v_, causal=causal)
                .astype(jnp.float32) ** 2).sum()

    def loss_ref(q_, k_, v_):
        return (fa._composite(q_, k_, v_, causal)
                .astype(jnp.float32) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_backward_multi_block_causal():
    q, k, v = make_qkv(b=1, s=512, h=2, seed=3)

    def loss(fn):
        return lambda q_, k_, v_: (fn(q_, k_, v_).astype(jnp.float32)
                                   * jnp.cos(q_)).sum()

    gf = jax.grad(loss(lambda a, b, c: fa.flash_attention(
        a, b, c, causal=True)), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda a, b, c: fa._composite(a, b, c, True)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_gqa_forward_and_backward():
    """k/v with Hkv=2 < H=8 heads, never expanded: parity with the
    composite (which expands internally)."""
    q, k, v = make_qkv(b=2, s=256, h=8, hkv=2, seed=5)
    out = fa.flash_attention(q, k, v, causal=True)
    ref = fa._composite(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss_flash(q_, k_, v_):
        return (fa.flash_attention(q_, k_, v_, causal=True)
                .astype(jnp.float32) ** 2).sum()

    def loss_ref(q_, k_, v_):
        return (fa._composite(q_, k_, v_, True)
                .astype(jnp.float32) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert a.shape == b.shape  # dk/dv stay at Hkv heads
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_kv_mask_forward_and_backward():
    """Key-padding mask: last quarter of keys masked out."""
    q, k, v = make_qkv(b=2, s=256, h=2, seed=7)
    mask = np.ones((2, 256), np.float32)
    mask[:, 192:] = 0.0
    mask = jnp.asarray(mask)

    out = fa.flash_attention(q, k, v, causal=False, kv_mask=mask)
    ref = fa._composite(q, k, v, False, kv_mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    gf = jax.grad(lambda a, b, c: (fa.flash_attention(
        a, b, c, causal=True, kv_mask=mask) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: (fa._composite(
        a, b, c, True, kv_mask=mask) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    # masked keys receive zero dk/dv
    assert np.allclose(np.asarray(gf[1])[:, 192:], 0.0)
    assert np.allclose(np.asarray(gf[2])[:, 192:], 0.0)


def _grads(fn, q, k, v):
    """Value and all three gradients of a weighted sum of fn's output
    (the weights keep every row's cotangent distinct)."""
    def loss(q_, k_, v_):
        out = fn(q_, k_, v_).astype(jnp.float32)
        return (out * jnp.cos(jnp.arange(out.size, dtype=jnp.float32)
                              .reshape(out.shape))).sum(), out
    (_, out), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return out, grads


def _masked_head(b, s, n):
    """[B, S] key mask with the first n keys padded out: under `causal`
    the first n rows then attend nothing at all."""
    mask = np.ones((b, s), np.float32)
    mask[:, :n] = 0.0
    return jnp.asarray(mask)


# what the split loops can get wrong: (block_q, block_k), the shape, and
# whether the call is causal and carries a key mask
SPLIT_CASES = {
    "tall_tiles_diagonal_crosses_four": dict(blocks=(512, 128), s=1024),
    "wide_tiles_diagonal_crosses_each_once": dict(blocks=(128, 512), s=1024),
    "tall_by_two": dict(blocks=(256, 128), s=512),
    "wide_by_two": dict(blocks=(128, 256), s=512),
    "one_tile": dict(blocks=(256, 256), s=256),
    "one_tile_blocks_clamped": dict(blocks=(512, 1024), s=128),
    "gqa_two_widths": dict(blocks=(256, 128), s=512, h=8, hkv=2, d=192,
                           dv=128),
    "gqa_two_widths_wide": dict(blocks=(128, 256), s=512, h=4, hkv=2,
                                d=192, dv=128),
    "not_causal_no_mask": dict(blocks=(128, 256), s=512, causal=False),
    "not_causal_tall": dict(blocks=(256, 128), s=512, causal=False,
                            h=4, hkv=2),
    "key_mask_and_causal_tall": dict(blocks=(256, 128), s=512, masked=96),
    "key_mask_and_causal_wide": dict(blocks=(128, 256), s=512, masked=160,
                                     h=4, hkv=2),
}


@pytest.mark.parametrize("case", SPLIT_CASES)
def test_split_loops_match_composite(case, monkeypatch):
    """Forward and all three gradients against the composite, with the
    tiles pinned so that the diagonal crosses what the case names."""
    c = {**dict(h=2, hkv=None, d=64, dv=None, causal=True, masked=0),
         **SPLIT_CASES[case]}
    s, causal = c["s"], c["causal"]
    monkeypatch.setattr(fa, "get_block_sizes", lambda *a, **k: c["blocks"])
    q, k, v = make_qkv(b=1, s=s, h=c["h"], hkv=c["hkv"], d=c["d"], seed=11)
    if c["dv"]:
        v = v[..., :c["dv"]]
    mask = _masked_head(1, s, c["masked"]) if c["masked"] else None

    out, grads = _grads(lambda *a: fa.flash_attention(
        *a, causal=causal, kv_mask=mask), q, k, v)
    ref, ref_grads = _grads(lambda *a: fa._composite(
        *a, causal, kv_mask=mask), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    for a, b in zip(grads, ref_grads):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    if mask is not None:
        # rows that attend nothing read exact zeros and pass none back;
        # padded keys receive nothing
        n = c["masked"]
        assert not np.asarray(out)[:, :n].any()
        assert not np.asarray(grads[0])[:, :n].any()
        assert not np.asarray(grads[1])[:, :n].any()
        assert not np.asarray(grads[2])[:, :n].any()


def _pallas_calls(jaxpr):
    from jax._src import core
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("key_mask", [False, True])
def test_key_mask_is_an_operand_only_when_passed(key_mask):
    """The kernels are built without the mask operand when the caller
    passed none, and kernel_paths tells the two bodies apart."""
    from paddle_tpu.ops import kernel_paths
    q, k, v = make_qkv(b=2, s=256, h=4, hkv=2)
    mask = _masked_head(2, 256, 32) if key_mask else None
    kernel_paths.reset()
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: fa.flash_attention(*a, causal=True, kv_mask=mask).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    operands = sorted(len(e.invars) for e in _pallas_calls(jaxpr.jaxpr))
    # forward q k v; backward k v q do lse delta; the mask on top
    assert operands == [3 + key_mask, 6 + key_mask]
    built, other = ("key_mask", "no_key_mask") if key_mask \
        else ("no_key_mask", "key_mask")
    counts = kernel_paths.counts()
    assert counts["flash_attention"] == {"kernel": 1, "composite": 0}
    assert counts["flash_attention." + built] == \
        {"kernel": 1, "composite": 0}
    assert "flash_attention." + other not in counts


_NAMED_CASES = {
    # (b, s, h, hkv, d, dv, key mask)
    "mha": (2, 256, 4, 4, 64, 64, False),
    "gqa": (2, 256, 8, 2, 64, 64, False),
    "two_widths": (1, 128, 2, 2, 192, 128, False),
    "gqa_key_mask": (2, 256, 4, 2, 64, 64, True),
}


def _named_case(case):
    b, s, h, hkv, d, dv, key_mask = _NAMED_CASES[case]
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(b, s, hkv, d).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(b, s, hkv, dv).astype(np.float32) * 0.3)
    mask = _masked_head(b, s, 32) if key_mask else None
    loss = lambda *a: (fa.flash_attention(
        *a, causal=True, kv_mask=mask) ** 2).sum()
    return (q, k, v), loss


@pytest.mark.parametrize("case", sorted(_NAMED_CASES))
def test_forward_rule_names_what_the_backward_needs(case):
    """The differentiated call names the output as the caller gets it,
    [B, S, H, Dv], and the log-sum-exp as the kernel writes it; the
    call itself names nothing."""
    b, s, h, hkv, d, dv, _ = _NAMED_CASES[case]
    qkv, loss = _named_case(case)

    def named(jaxpr):
        return {e.params["name"]: e.outvars[0].aval.shape
                for e in jaxpr.eqns if e.primitive.name == "name"}

    assert named(jax.make_jaxpr(jax.grad(loss))(*qkv).jaxpr) == {
        "flash_out": (b, s, h, dv),
        "flash_lse": (b * hkv, h // hkv, 1, s)}
    assert named(jax.make_jaxpr(loss)(*qkv).jaxpr) == {}


@pytest.mark.parametrize("case", sorted(_NAMED_CASES))
def test_backward_from_kept_names_is_the_backward(case):
    """Under a remat that keeps the two names the backward kernel reads
    the kept output (turned back into the kernel's layout) and lse: the
    gradients are those of the plain call bit for bit, and no second
    forward kernel is traced; a remat that keeps nothing traces two."""
    qkv, loss = _named_case(case)
    plain = jax.grad(loss, argnums=(0, 1, 2))(*qkv)
    keep = jax.checkpoint_policies.save_only_these_names(
        *fa.RESIDUAL_NAMES)
    for policy, forwards in ((keep, 1), (None, 2)):
        grad = jax.grad(jax.checkpoint(loss, policy=policy),
                        argnums=(0, 1, 2))
        calls = list(_pallas_calls(jax.make_jaxpr(grad)(*qkv).jaxpr))
        assert len(calls) == forwards + 1
        for a, b in zip(grad(*qkv), plain):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_a_fallback_names_no_body():
    """A shape the kernels do not serve counts as the composite, as it
    did, and as neither body."""
    from paddle_tpu.ops import kernel_paths
    q, k, v = make_qkv(b=1, s=100, h=2)
    kernel_paths.reset()
    fa.flash_attention(q, k, v, causal=True)
    assert kernel_paths.counts() == \
        {"flash_attention": {"kernel": 0, "composite": 1}}
    assert kernel_paths.last_reason("flash_attention") == \
        "shape not served by the kernel"


def test_bf16_inputs():
    q, k, v = make_qkv(b=1, s=256, h=2)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = fa.flash_attention(qb, kb, vb, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = fa._composite(qb, kb, vb, True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2)


def test_unsupported_shapes_fall_back():
    # s % 128 != 0 -> composite (still correct)
    q, k, v = make_qkv(b=1, s=100, h=2)
    out = fa.flash_attention(q, k, v, causal=True)
    ref = fa._composite(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6)


@pytest.mark.parametrize("outer", ["none", "full", "dp_only"])
def test_mesh_partition_inside_a_manual_region(outer):
    """Under a compile mesh the kernel is shard_mapped (GSPMD cannot
    partition a Mosaic call).  A caller already inside a shard_map body
    holds per-shard operands: axes that body made Manual are left alone
    (every axis Manual: the bare kernel; dp Manual, tp still automatic:
    only tp is mapped, through the context mesh)."""
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed.mesh import (compile_mesh_guard,
                                             create_mesh, shard_map)
    mesh = create_mesh({"dp": 2, "tp": 2}, devices=jax.devices()[:4])
    q, k, v = make_qkv(b=4, s=128, h=4)
    seen = []

    def attn(q, k, v):
        seen.append(fa._mesh_partition(q.shape[0], q.shape[2],
                                       k.shape[2]))
        return fa.flash_attention(q, k, v, causal=True)

    fn = attn
    if outer != "none":
        where = {} if outer == "full" else {"axis_names": {"dp"}}
        fn = shard_map(attn, mesh=mesh, in_specs=P("dp"),
                       out_specs=P("dp"), check_vma=False, **where)
    with compile_mesh_guard(mesh):
        out = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(fa._composite(q, k, v, True)),
        rtol=2e-5, atol=2e-5)
    part = seen[0]
    if outer == "full":
        assert part is None
    elif outer == "none":
        assert part[0] == {"mesh": mesh}
        assert part[1] == P(("dp",), None, "tp", None)
    else:
        assert part[0] == {"axis_names": frozenset({"tp"})}
        assert part[1] == P(None, None, "tp", None)
