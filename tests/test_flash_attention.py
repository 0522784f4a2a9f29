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
