"""The decode step of power retention as one Pallas kernel: a grid step
takes one (slot, KV head)'s state ``[R, 128]`` into VMEM, decays it,
adds the token's ``phi(k) v^T``, writes it back over its own buffer
(``input_output_aliases``) and, while the rows are there, multiplies
them into the group's query heads.  Every state element crosses HBM
once in and once out a token; XLA's form reads it a third time for the
read-out product.

The rows of one ``j`` are contiguous (``power_retention``'s layout), so
for each ``j`` the kernel handles a slab ``[8 (J + 1), 128]``:

    new  = g * slab + kcol[:n] * coef * (k_j * v)          # the update
    V_h[j, :] = sum_i qcol_h[i] * coef[i] * new[i, :]      # the read, over i

with ``kcol`` / ``qcol_h`` the vectors down the sublanes and across all
lanes, and after the last ``j``: ``num_h = sum_j q_h[j] V_h[j, :]``.
All of it is float32 multiply-adds on the VPU: no product is rounded to
bf16, as the MXU would round it.  The normaliser ``z [128, 128]`` goes
through the same grid step.

Serves heads of 128 with values of 128 and at most 8 query heads a KV
head; everything else is ``power_retention.step_reference``.
"""
from __future__ import annotations

import functools
import importlib
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_fa = importlib.import_module(__package__ + ".flash_attention")
_pr = importlib.import_module(__package__ + ".power_retention")

__all__ = ["available", "serves", "step"]

_F32 = jnp.float32
_TILE = 8
_VMEM_LIMIT = 64 * 1024 * 1024


def serves(q, k, v, state) -> bool:
    d, dv = k.shape[-1], v.shape[-1]
    return d == 128 and dv == 128 and q.shape[1] // k.shape[1] <= _TILE \
        and state.s.dtype == _F32


def available(q, k, v, state) -> bool:
    return serves(q, k, v, state) and _fa.flash_attention_available()


def _kernel(kq_ref, v_ref, g_ref, s_ref, z_ref, s_out, z_out, num_ref,
            den_ref, kv_scr, vh_scr, *, groups: int):
    d = z_ref.shape[-1]
    nb = d // _TILE
    rows = kq_ref[0]                                   # [8, d]: k, q_0..
    cols = jnp.concatenate(
        [rows, jnp.zeros((d - _TILE, d), _F32)], axis=0).T
    down = lambda c: jnp.broadcast_to(cols[:, c:c + 1], (d, d))
    kcol = down(0)
    qcols = [down(1 + h) for h in range(groups)]
    g = g_ref[0]                                       # [1, d], one number
    kv_scr[...] = kcol * v_ref[0]                      # row j: k_j * v

    z = g * z_ref[0] + kcol * rows[0:1, :]
    z_out[0] = z
    dens = [jnp.sum(jnp.sum(qcols[h] * z, axis=0, keepdims=True) *
                    rows[1 + h:2 + h, :], axis=1, keepdims=True)
            for h in range(groups)]

    row_id = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
    for blk in range(nb):
        n = _TILE * (blk + 1)
        base = 32 * blk * (blk + 1)
        coef = jnp.where(row_id[:n] < _TILE * blk, math.sqrt(2.0), 1.0)
        kc = kcol[:n] * coef
        qc = [qcols[h][:n] * coef for h in range(groups)]

        def body(jj, carry, n=n, base=base, blk=blk, kc=kc, qc=qc):
            j = _TILE * blk + jj
            at = pl.ds(pl.multiple_of(base + jj * n, _TILE), n)
            new = g * s_ref[0, at, :] + kc * kv_scr[pl.ds(j, 1), :]
            s_out[0, at, :] = new
            for h in range(groups):
                vh_scr[h, pl.ds(j, 1), :] = jnp.sum(
                    qc[h] * new, axis=0, keepdims=True)
            return carry

        jax.lax.fori_loop(0, _TILE, body, 0)

    pad = jnp.zeros((_TILE - groups, d), _F32)
    num_ref[0] = jnp.concatenate(
        [jnp.sum(qcols[h] * vh_scr[h], axis=0, keepdims=True)
         for h in range(groups)] + [pad], axis=0)
    den_ref[0] = jnp.concatenate(
        [jnp.broadcast_to(dens[h], (1, d)) for h in range(groups)] + [pad],
        axis=0)


def step(q, k, v, log_g, state, eps: float):
    """``power_retention.power_retention_step``'s contract."""
    b, h, d = q.shape
    hkv = k.shape[1]
    groups = h // hkv
    bh = b * hkv
    rows = state.s.shape[2]
    kq = jnp.concatenate(
        [k.astype(_F32)[:, :, None], q.astype(_F32).reshape(b, hkv, groups, d),
         jnp.zeros((b, hkv, _TILE - 1 - groups, d), _F32)],
        axis=2).reshape(bh, _TILE, d)
    gate = jnp.broadcast_to(
        jnp.exp(log_g.astype(_F32)).reshape(bh, 1, 1), (bh, 1, d))
    one = lambda *shape: pl.BlockSpec((1,) + shape,
                                      lambda i: (i,) + (0,) * len(shape))
    s, z, num, den = pl.pallas_call(
        functools.partial(_kernel, groups=groups), grid=(bh,),
        in_specs=[one(_TILE, d), one(1, d), one(1, d), one(rows, d),
                  one(d, d)],
        out_specs=[one(rows, d), one(d, d), one(_TILE, d), one(_TILE, d)],
        out_shape=[jax.ShapeDtypeStruct((bh, rows, d), _F32),
                   jax.ShapeDtypeStruct((bh, d, d), _F32),
                   jax.ShapeDtypeStruct((bh, _TILE, d), _F32),
                   jax.ShapeDtypeStruct((bh, _TILE, d), _F32)],
        input_output_aliases={3: 0, 4: 1},
        scratch_shapes=[pltpu.VMEM((d, d), _F32),
                        pltpu.VMEM((groups, d, d), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_fa._INTERPRET, name="power_retention_step",
    )(kq, v.astype(_F32).reshape(bh, 1, d), gate,
      state.s.reshape(bh, rows, d), state.z.reshape(bh, d, d))
    num = num[:, :groups].reshape(b, h, d)
    den = den[:, :groups, 0].reshape(b, h, 1)
    y = _pr.normalise(num / d, den / d, eps)
    return y, type(state)(s.reshape(state.s.shape), z.reshape(state.z.shape))
