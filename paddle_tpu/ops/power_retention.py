"""Power retention of degree 2 (Manifest AI, *Scaling Context Requires
Rethinking Attention*, arXiv:2507.04239): attention whose weight is a
power of the score, so that it has an exact recurrent form over a state
of fixed size.

For a query head ``h`` in the group of KV head ``j`` and a log-gate
``gamma_t <= 0`` a KV head::

    a_ts = exp(sum_{r=s+1..t} gamma_r) * (q_t . k_s / sqrt(d)) ** 2   (s <= t)
    y_t  = sum_s a_ts v_s / (sum_s a_ts + eps)

With ``phi(u) . phi(w) == (u . w) ** 2`` the same numbers come from a
state a KV head, ``S_t = exp(gamma_t) S_{t-1} + phi(k_t) v_t^T``, and its
normaliser, the column of ones beside ``v``: ``y_t = (phi(q_t)^T S_t /
d) / (den_t / d + eps)``.  The normaliser's column ``sum_s decay
phi(k_s)`` read by ``phi(q)`` is ``q^T (sum_s decay k_s k_s^T) q``, so
it is kept as that ``d x d`` matrix ``Z_t = exp(gamma_t) Z_{t-1} + k_t
k_t^T`` and needs no ``phi``.

Two forms under one contract, both float32 whatever the inputs' dtype:

- ``power_retention_step``: one token a slot, the decode tick.  It is
  bound by memory (every state element is read and written once a token
  and multiplied into ``G`` query heads), so on the chip it is a Pallas
  kernel that updates the state where it lies
  (``power_retention_kernel.py``); XLA's form, below, is that kernel's
  oracle and the path off the chip.  ``kernel_paths`` notes the choice
  under ``power_retention``.
- ``power_retention_chunked``: a prompt, in chunks of ``chunk`` tokens:
  the attention form inside a chunk, the state between chunks.  XLA's
  form alone (it is bound by the MXU).  Positions at or past
  ``lengths[b]`` leave the state as it was: gate 1, no write.

How ``phi`` is laid out.  The symmetric expansion of a head of 128 has
128 * 129 / 2 = 8,256 distinct products.  Here the products are kept by
TILES of 8 x 8: for every pair of 8-wide blocks ``I <= J`` of the head
the whole tile ``u_i u_j`` (``i`` in ``I``, ``j`` in ``J``), times
``sqrt(2)`` off the diagonal; the 16 diagonal tiles keep both orders of
their 28 mixed products.  That is ``64 * 136 = 8,704`` rows, 5.4% more
than 8,256, and every row block is a whole number of (8, 128) float32
tiles with no mask and no gather.  Row of ``(j, i)``, ``J = j // 8``,
``i < 8 (J + 1)``: ``32 J (J + 1) + (j % 8) * 8 (J + 1) + i``: for one
``j`` the rows of all its ``i`` are contiguous.  The state is
``s [B, Hkv, R, Dv]`` (rows ``phi``, lanes the value channel) beside the
normaliser ``z [B, Hkv, d, d]`` (a 129th column would double the bytes
of every row on a 128-lane tile): ``(8704 + 128) * 128 * 4 = 4,521,984``
bytes a KV head of 128, where ``8256 * 129 * 4 = 4,260,096`` are the
mathematics' own.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from . import kernel_paths

__all__ = ["RetentionState", "TILE", "state_rows", "phi", "init_state",
           "normalise", "power_retention_step", "power_retention_chunked"]

TILE = 8
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


class RetentionState(NamedTuple):
    """``s [B, Hkv, R, Dv]`` and its normaliser ``z [B, Hkv, d, d]``,
    float32."""
    s: jax.Array
    z: jax.Array


def state_rows(head_dim: int) -> int:
    """Rows of ``phi`` for a head of ``head_dim`` (a multiple of 8)."""
    if head_dim % TILE:
        raise ValueError(f"head_dim {head_dim} is not a multiple of {TILE}")
    nb = head_dim // TILE
    return TILE * TILE * nb * (nb + 1) // 2


def init_state(batch: int, kv_heads: int, head_dim: int,
               v_dim: Optional[int] = None) -> RetentionState:
    rows = state_rows(head_dim)
    return RetentionState(
        jnp.zeros((batch, kv_heads, rows, v_dim or head_dim), _F32),
        jnp.zeros((batch, kv_heads, head_dim, head_dim), _F32))


def phi(u):
    """``[..., d] -> [..., R]`` float32 with ``phi(u) . phi(w) ==
    (u . w) ** 2`` (the layout is the module's docstring's)."""
    u = u.astype(_F32)
    d = u.shape[-1]
    nb = d // TILE
    state_rows(d)
    parts = []
    for blk in range(nb):
        n = TILE * (blk + 1)
        coef = np.where(np.arange(n) < TILE * blk, math.sqrt(2.0),
                        1.0).astype(np.float32)
        tile = u[..., TILE * blk:n, None] * (u[..., :n] * coef)[..., None, :]
        parts.append(tile.reshape(u.shape[:-1] + (TILE * n,)))
    return jnp.concatenate(parts, axis=-1)


def _grouped(q, kv_heads: int):
    """``[..., H, d] -> [..., Hkv, G, d]``: query head ``h`` reads KV
    head ``h // G``."""
    h = q.shape[-2]
    if h % kv_heads:
        raise ValueError(f"{h} query heads on {kv_heads} KV heads")
    return q.reshape(q.shape[:-2] + (kv_heads, h // kv_heads, q.shape[-1]))


def normalise(num, den, eps: float):
    """The weighted mean from its two sums (both already carry the
    score's scale): every form ends here."""
    return num / (den + eps)


def step_reference(q, k, v, log_g, state: RetentionState, eps: float):
    """XLA's form of one token a slot; see ``power_retention_step``."""
    b, h, d = q.shape
    hkv = k.shape[1]
    g = jnp.exp(log_g.astype(_F32))
    k = k.astype(_F32)
    qg = _grouped(q.astype(_F32), hkv)                 # [B, Hkv, G, d]
    s = g[..., None, None] * state.s + \
        phi(k)[..., None] * v.astype(_F32)[..., None, :]
    z = g[..., None, None] * state.z + k[..., :, None] * k[..., None, :]
    num = jnp.einsum("bjgr,bjrc->bjgc", phi(qg), s, precision=_HIGHEST)
    den = jnp.einsum("bjgi,bjik,bjgk->bjg", qg, z, qg, precision=_HIGHEST)
    y = normalise(num / d, den[..., None] / d, eps)
    return y.reshape(b, h, -1), RetentionState(s, z)


def power_retention_step(q, k, v, log_g, state: RetentionState, *,
                         eps: float = 1e-6):
    """One token a slot: ``q [B, H, d]``, ``k [B, Hkv, d]``,
    ``v [B, Hkv, Dv]``, ``log_g [B, Hkv]`` (``<= 0``), the state as it
    stands before the token.  Returns ``(y [B, H, Dv] float32, the state
    after it)``.  A slot with ``log_g == 0`` and ``k == 0`` keeps its
    state (gate 1, no write).  Handed in donated, the state is updated
    where it lies."""
    from . import power_retention_kernel as kernel
    if kernel.available(q, k, v, state):
        kernel_paths.note("power_retention", "kernel")
        return kernel.step(q, k, v, log_g, state, eps)
    kernel_paths.note_composite("power_retention",
                                kernel.serves(q, k, v, state))
    return step_reference(q, k, v, log_g, state, eps)


def _chunk(qc, kc, vc, lg, valid, state, eps: float):
    """One chunk: ``qc [B, C, Hkv, G, d]``, ``kc [B, C, Hkv, d]``,
    ``vc [B, C, Hkv, Dv]``, ``lg [B, C, Hkv]`` (already 0 where not
    valid), ``valid [B, C]``; ``state`` None for a zero state.  Returns
    ``(y [B, C, Hkv, G, Dv], state after the chunk)``."""
    d = qc.shape[-1]
    c = qc.shape[1]
    cum = jnp.cumsum(lg, axis=1)                       # L_t, [B, C, Hkv]
    # the attention form inside the chunk
    scores = jnp.einsum("btjgd,bsjd->bjgts", qc, kc, precision=_HIGHEST)
    decay = cum.transpose(0, 2, 1)[:, :, None, :, None] - \
        cum.transpose(0, 2, 1)[:, :, None, None, :]    # L_t - L_s
    seen = (jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]) & \
        valid[:, None, None, None, :]
    a = jnp.where(seen, jnp.exp(jnp.where(seen, decay, 0.0)) *
                  jnp.square(scores) / d, 0.0)         # [B, Hkv, G, t, s]
    num = jnp.einsum("bjgts,bsjc->btjgc", a, vc, precision=_HIGHEST)
    den = a.sum(-1).transpose(0, 3, 1, 2)              # [B, t, Hkv, G]
    pk = phi(kc) * valid[:, :, None, None]             # [B, C, Hkv, R]
    total = cum[:, -1]                                 # L_C, [B, Hkv]
    carry = jnp.exp(total[:, None] - cum)              # exp(L_C - L_s)
    ds = jnp.einsum("bsjr,bsjc->bjrc", pk * carry[..., None], vc,
                    precision=_HIGHEST)
    kz = kc * valid[:, :, None, None]
    dz = jnp.einsum("bsji,bsjk->bjik", kz * carry[..., None], kc,
                    precision=_HIGHEST)
    if state is not None:
        pq = phi(qc) * jnp.exp(cum)[:, :, :, None, None]
        num = num + jnp.einsum("btjgr,bjrc->btjgc", pq, state.s,
                               precision=_HIGHEST) / d
        den = den + jnp.einsum(
            "btjgi,bjik,btjgk->btjg", qc, state.z, qc,
            precision=_HIGHEST) * jnp.exp(cum)[..., None] / d
        keep = jnp.exp(total)
        ds = ds + keep[..., None, None] * state.s
        dz = dz + keep[..., None, None] * state.z
    return normalise(num, den[..., None], eps), RetentionState(ds, dz)


def power_retention_chunked(q, k, v, log_g, state0, lengths, *,
                            chunk: int = 256, eps: float = 1e-6):
    """A window of tokens a slot: ``q [B, S, H, d]``, ``k [B, S, Hkv,
    d]``, ``v [B, S, Hkv, Dv]``, ``log_g [B, S, Hkv]``; ``state0`` the
    state before the window, None for zero (the first chunk then reads
    no state); ``lengths [B]`` the real tokens of each row, None for all
    ``S``.  Returns ``(y [B, S, H, Dv] float32, the state after token
    lengths[b] - 1)``: positions at or past ``lengths[b]`` leave the
    state as it was, and their ``y`` is not meaningful."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    lens = jnp.full((b,), s, jnp.int32) if lengths is None \
        else jnp.asarray(lengths, jnp.int32)
    c = min(int(chunk), s)
    n = -(-s // c)
    pad = n * c - s
    valid = jnp.arange(n * c)[None, :] < lens[:, None]
    q, k, v = (jnp.pad(x.astype(_F32), ((0, 0), (0, pad), (0, 0), (0, 0)))
               for x in (q, k, v))
    lg = jnp.where(valid[..., None],
                   jnp.pad(log_g.astype(_F32), ((0, 0), (0, pad), (0, 0))),
                   0.0)
    q = _grouped(q, hkv)

    def cut(x):                                        # -> [n, B, C, ...]
        return jnp.moveaxis(x.reshape((b, n, c) + x.shape[2:]), 1, 0)

    qs, ks, vs, lgs, vals = (cut(x) for x in (q, k, v, lg, valid))
    y0, state = _chunk(qs[0], ks[0], vs[0], lgs[0], vals[0], state0, eps)
    ys = y0[None]
    if n > 1:
        def body(carry, xs):
            y, carry = _chunk(*xs, carry, eps)
            return carry, y
        state, rest = jax.lax.scan(
            body, state, (qs[1:], ks[1:], vs[1:], lgs[1:], vals[1:]))
        ys = jnp.concatenate([ys, rest], axis=0)
    y = jnp.moveaxis(ys, 0, 1).reshape(b, n * c, h, -1)[:, :s]
    return y, state
