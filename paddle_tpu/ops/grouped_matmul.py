"""Grouped matrix product over the experts a chip holds.

``grouped_matmul(x, w, tile_group, tiles_used, tile_m=...)``: the rows of
``x [M, K]`` lie in tiles of ``tile_m``; tile ``t`` belongs to ONE group
(expert) ``tile_group[t]`` and is multiplied by ``w[tile_group[t]]``
(``w [E, K, N]``).  Only the first ``tiles_used`` tiles hold rows; the
rest of the buffer is its worst case, costs no product, and reads zero.
``tile_group`` is non-decreasing over the used tiles and names every
group at least once (``moe.dropless_layout`` builds it so), which is what
lets the weight gradient accumulate one group's tiles in place.

The Pallas kernels read the tile's group from scalar memory and pick the
weight block in the ``index_map``, so the device multiplies the rows that
exist (dropless routing has a static worst case of ``tokens x top_k``
rows, sixteen times the expected load on a 16-way expert share) and
skips the empty tail.  The XLA composite multiplies every row by every
group under a mask: E times the operations over the whole buffer, which
is why the chip gets the kernel.  Both record their choice in
``ops.kernel_paths`` under ``grouped_matmul``.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import kernel_paths

# the module, not the function of the same name the package exports
_fa = importlib.import_module(__package__ + ".flash_attention")

__all__ = ["grouped_matmul", "grouped_matmul_available"]

_VMEM_LIMIT = 64 * 1024 * 1024


def grouped_matmul_available() -> bool:
    return _fa.flash_attention_available()


def _block(dim: int, cap: int) -> int:
    """The widest block of `dim` that is a multiple of 128, divides it and
    is at most `cap`; the whole of `dim` where there is none (a block's
    last dimensions are multiples of the tiling or the array's own)."""
    best = 0
    for cand in range(128, min(dim, cap) + 1, 128):
        if dim % cand == 0:
            best = cand
    return best or dim


# ---------------------------------------------------------------------------
# y[tile] = x[tile] @ w[group[tile]]
# ---------------------------------------------------------------------------
def _gmm_kernel(group_ref, used_ref, x_ref, w_ref, o_ref, acc_ref):
    t, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t < used_ref[0])
    def _mul():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _gmm(x, w, tile_group, tiles_used, tile_m):
    m, kdim = x.shape
    n = w.shape[2]
    tk, tn = _block(kdim, 512), _block(n, 1024)
    n_tiles = m // tile_m

    def live(t, used):          # an empty tile re-reads the last used one
        return jnp.minimum(t, used[0] - 1)

    def x_map(t, j, k, group, used):
        return live(t, used), jnp.where(t < used[0], k, 0)

    def w_map(t, j, k, group, used):
        return group[live(t, used)], jnp.where(t < used[0], k, 0), j

    call = pl.pallas_call(
        _gmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles, n // tn, kdim // tk),
            in_specs=[pl.BlockSpec((tile_m, tk), x_map),
                      pl.BlockSpec((None, tk, tn), w_map)],
            out_specs=pl.BlockSpec((tile_m, tn),
                                   lambda t, j, k, group, used: (t, j)),
            scratch_shapes=[pltpu.VMEM((tile_m, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_fa._INTERPRET,
        name="grouped_matmul",
    )
    return _fa.run_kernel(x.dtype, call, tile_group, tiles_used, x, w)


# ---------------------------------------------------------------------------
# dw[g] = sum over g's tiles of x[tile]^T @ dy[tile]
# ---------------------------------------------------------------------------
def _gmm_dw_kernel(group_ref, used_ref, x_ref, dy_ref, o_ref):
    t = pl.program_id(2)
    used = used_ref[0]
    first = jnp.logical_or(
        t == 0, group_ref[t] != group_ref[jnp.maximum(t - 1, 0)])

    @pl.when(jnp.logical_and(first, t < used))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(t < used)
    def _acc():
        o_ref[...] += jax.lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _gmm_dw(x, dy, tile_group, tiles_used, tile_m, n_groups):
    m, kdim = x.shape
    n = dy.shape[1]
    tk, tn = _block(kdim, 512), _block(n, 512)
    n_tiles = m // tile_m

    def live(t, used):
        return jnp.minimum(t, used[0] - 1)

    call = pl.pallas_call(
        _gmm_dw_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(kdim // tk, n // tn, n_tiles),
            in_specs=[
                pl.BlockSpec((tile_m, tk),
                             lambda i, j, t, group, used: (live(t, used), i)),
                pl.BlockSpec((tile_m, tn),
                             lambda i, j, t, group, used: (live(t, used), j))],
            out_specs=pl.BlockSpec(
                (None, tk, tn),
                lambda i, j, t, group, used: (group[live(t, used)], i, j))),
        out_shape=jax.ShapeDtypeStruct((n_groups, kdim, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_fa._INTERPRET,
        name="grouped_matmul_dw",
    )
    return _fa.run_kernel(x.dtype, call, tile_group, tiles_used, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gmm_vjp(x, w, tile_group, tiles_used, tile_m):
    return _gmm(x, w, tile_group, tiles_used, tile_m)


def _gmm_fwd(x, w, tile_group, tiles_used, tile_m):
    return (_gmm(x, w, tile_group, tiles_used, tile_m),
            (x, w, tile_group, tiles_used))


def _gmm_bwd(tile_m, saved, dy):
    x, w, tile_group, tiles_used = saved
    dx = _gmm(dy, jnp.swapaxes(w, 1, 2), tile_group, tiles_used, tile_m)
    dw = _gmm_dw(x, dy, tile_group, tiles_used, tile_m, w.shape[0])
    return dx, dw.astype(w.dtype), None, None


_gmm_vjp.defvjp(_gmm_fwd, _gmm_bwd)


def _composite(x, w, tile_group, tiles_used, tile_m):
    """Every row by every group, under the mask of its tile's group."""
    n_tiles = x.shape[0] // tile_m
    live = jnp.arange(n_tiles) < tiles_used[0]
    row_group = jnp.repeat(jnp.where(live, tile_group, -1), tile_m)
    out = jnp.zeros((x.shape[0], w.shape[2]), jnp.float32)
    for g in range(w.shape[0]):
        xg = jnp.where((row_group == g)[:, None], x, jnp.zeros_like(x))
        out = out + jnp.dot(xg, w[g], preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


def grouped_matmul(x, w, tile_group, tiles_used, tile_m: int):
    """``x [M, K]`` (M a multiple of ``tile_m``), ``w [E, K, N]``,
    ``tile_group [M / tile_m]`` int32, ``tiles_used [1]`` int32 ->
    ``[M, N]`` in x's dtype; rows of unused tiles read zero."""
    m, kdim = x.shape
    supported = (m % tile_m == 0 and tile_m % 128 == 0 and kdim % 16 == 0
                 and w.shape[2] % 16 == 0 and x.dtype == w.dtype)
    if not supported or not grouped_matmul_available():
        kernel_paths.note_composite("grouped_matmul", supported)
        return _composite(x, w, tile_group, tiles_used, tile_m)
    kernel_paths.note("grouped_matmul", "kernel")
    return _gmm_vjp(x, w, tile_group.astype(jnp.int32),
                    tiles_used.astype(jnp.int32), tile_m)
