"""Chunked Kimi Delta Attention scan (KDA: a gated delta rule whose
decay differs by key channel).

The recurrence, per head, with a matrix state ``S [K, V]``::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                         alpha_t = exp(g_t) in (0, 1]

is computed in chunks of ``chunk`` positions in its WY / UT form.  With
``Gamma_i`` the running sum of ``g`` inside a chunk (per channel),
``k+_i = k_i exp(Gamma_i)`` and ``q+_i = q_i exp(Gamma_i)``::

    A[i, j] = beta_i sum_c k_i[c] k_j[c] exp(Gamma_i[c] - Gamma_j[c])   (j < i)
    T = (I + A)^-1 Diag(beta)   (one triangular solve),   W = T K+,   U = T V
    S' = Diag(exp(Gamma_C)) S + Kend^T (U - W S)   Kend_j = k_j exp(Gamma_C - Gamma_j)
    O  = Q+ S + tril(M) (U - W S)                  M[i, j] as A without beta, with q_i

``S'`` is linear in ``S``, so ``Kend^T W`` and ``Kend^T U`` of every
chunk are batched products and only ``S' = decay o S + R - (Kend^T W) S``
runs in sequence over the chunks; the outputs follow from the states
entering the chunks, batched again.

The pair terms.  Decays differ by channel, so there is no ``[C, C]``
decay block a head, and ``exp(-Gamma)`` overflows float32 inside one
chunk for the fast channels (``g`` reaches -6 a step): ``k+ . k-`` over a
chunk is never formed.  A chunk is cut into sub-blocks of ``SUB`` rows.
Between sub-blocks the decays are taken relative to the LATER
block's first row ``r``: ``k_i exp(Gamma_i - Gamma_r)`` and
``k_j exp(Gamma_r - Gamma_j)``, both factors at most 1, a batched
product a level of halving.  Inside a sub-block the ``[SUB, SUB, K]`` differences
``exp(Gamma_i - Gamma_j)`` are formed directly (every exponent at most
0), in slices of the batch so that the block of differences stays small,
each slice rematerialised in the backward.  (The kernels halve on, down
to single rows, and form no block of differences.)

Precision: ``g``, ``Gamma``, every decay, the solve and the carried
state are float32 whatever the inputs; the matrix products take their
operands in the inputs' dtype (bf16 under AMP) and accumulate in
float32.

Two paths, one contract.  On the chip, for heads of whole lane tiles (K
and V multiples of 128), ``ops/kda_chunk_kernel.py`` keeps a chunk in
VMEM: Pallas kernels under one ``jax.custom_vjp``, forward and
hand-derived backward, the state carried over the chunks in a VMEM
scratch.  Everywhere else (no tpu, narrow heads) XLA's own operations
run the form below, `_chunked`, whose backward is that form
differentiated by JAX (the triangular solve brings its own rule); it is
also the oracle of the kernels' tests.  ``ops.kernel_paths`` records the
choice under ``kda_scan``.

Why the kernels (one head group, 2 x 8192 x 4 heads of 128, bf16, on a
v5e; scratch timings of PR 37, PERF.md section 6): XLA's form writes
every ``[C, K]`` intermediate of a chunk to HBM (11.7 GB forward and
backward for 0.1 GB of inputs and outputs) and takes 5.24 ms forward and
14.41 forward and backward; the kernels take 2.46 and 4.17.  Cut where
the kernels' stages are, XLA's pair terms, solve, ``W`` and ``U`` take
4.36 and 10.62 ms and its pass over the state with the outputs 2.10 and
5.45; of the kernels' 2.46 and 4.17 the pass over the state with the
outputs is 1.24 and 2.96.

Memory of XLA's form.  Differentiated whole, it keeps some thirty ``[b,
s, H, K]`` float32 intermediates for its backward: 0.15 GiB a (row,
head) pair of 8192 positions and 128 channels, 8.9 GiB for 2 rows of 32
heads.  Heads are independent, so a caller with many of them maps over
groups of heads, each group rematerialised (``models/kimi_linear.py``
does).  The kernels keep 112 KB a chunk and head under bf16 (the state
entering in float32, the inverse and the two pair squares in bf16): 14
MiB a (row, head) pair of 8192 positions, 112 MiB for a group of 4 heads
of 2 rows, 0.875 GiB for 2 rows of 32 heads.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import kda_chunk_kernel, kernel_paths

__all__ = ["kda_scan"]

_F32 = jnp.float32
SUB = 16                    # rows of a sub-block of the pair terms
_OWN_SLICE = 2048           # sub-blocks whose own pairs are formed at once


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=_F32)


def kda_scan(q, k, v, g, beta, chunk: int = 64):
    """``q``/``k [b, s, H, K]`` (as the scores take them: normalised and
    scaled by the caller), ``v [b, s, H, V]``, ``g [b, s, H, K]`` the
    log-decay (at most 0), ``beta [b, s, H]`` in (0, 1).  Returns ``o
    [b, s, H, V]`` in v's dtype.  ``chunk`` is ``SUB`` times a power of
    two.  Any length: the tail is padded with positions that neither
    decay nor write the state (g = beta = 0)."""
    blocks = int(chunk) // SUB
    if int(chunk) % SUB or blocks & (blocks - 1):
        raise ValueError(f"chunk must be {SUB} times a power of two (the "
                         f"pair terms halve it down to {SUB} rows), got "
                         f"{chunk}")
    supported = kda_chunk_kernel.serves(q.shape[-1], v.shape[-1], int(chunk))
    with jax.named_scope("kda_scan"):
        if not supported or not kda_chunk_kernel.available():
            kernel_paths.note_composite("kda_scan", supported)
            return _chunked(q, k, v, g, beta, int(chunk))
        kernel_paths.note("kda_scan", "kernel")
        return _in_vmem(q, k, v, g, beta, int(chunk))


def _whole_chunks(c, *arrays):
    """``arrays [b, s, ..]`` with ``s`` padded to whole chunks of ``c``
    by positions of zeros."""
    pad = (-arrays[0].shape[1]) % c
    if not pad:
        return arrays
    return tuple(jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                 for t in arrays)


def _in_vmem(q, k, v, g, beta, c):
    """The kernels' path: the same casts and padding as `_chunked`."""
    s, cdt = q.shape[1], v.dtype
    o = kda_chunk_kernel.scan(*_whole_chunks(
        c, q.astype(cdt), k.astype(cdt), v, g.astype(_F32),
        beta.astype(_F32)), c)
    return o[:, :s]


@jax.checkpoint
def _own_pairs(q, k, gam):
    """The pair terms inside sub-blocks: ``q``/``k``/``gam [N, SUB, K]``
    float32 -> ``(sum_c q_i k_j D, sum_c k_i k_j D) [N, SUB, SUB]`` with
    ``D[i, j, c] = exp(gam_i[c] - gam_j[c])`` for ``i >= j`` and 0 above
    the diagonal."""
    rows = jnp.arange(q.shape[1])
    seen = (rows[:, None] >= rows[None, :])[None, :, :, None]
    diff = gam[:, :, None, :] - gam[:, None, :, :]
    d = jnp.exp(jnp.where(seen, diff, -jnp.inf)) * k[:, None, :, :]
    return (jnp.sum(q[:, :, None, :] * d, -1),
            jnp.sum(k[:, :, None, :] * d, -1))


def _pair_terms(q, k, gam):
    """``q``/``k [.., C, K]`` (any dtype), ``gam [.., C, K]`` float32 ->
    ``(m_qk, m_kk) [.., C, C]`` float32, ``sum_c x_i[c] k_j[c]
    exp(gam_i[c] - gam_j[c])`` for ``j <= i`` and 0 elsewhere.

    Below the diagonal sub-blocks the square is halved again and again
    (``C = SUB * 2**levels``): at a level, the rows of a block's second
    half against those of its first, decays relative to the second half's
    first row, so that every needed product is formed once and no other."""
    lead, (c, kdim) = q.shape[:-2], q.shape[-2:]
    cdt = q.dtype
    q32, k32 = q.astype(_F32), k.astype(_F32)

    def on_diagonal(blocks, size):
        """``blocks [.., nb, size, size]`` laid along the diagonal of
        ``[.., nb * size, nb * size]``."""
        nb = blocks.shape[-3]
        eye = jnp.eye(nb, dtype=_F32)[:, None, :, None]
        return (blocks[..., :, :, None, :] * eye).reshape(
            lead + (nb * size, nb * size))

    m_qk = m_kk = 0.0
    half = c // 2
    while half >= SUB:
        halves = lambda t: t.reshape(lead + (c // (2 * half), 2, half, kdim))
        gh, qh, kh = halves(gam), halves(q32), halves(k32)
        first = gh[..., 1, :1, :]           # the second half's first row
        into = jnp.exp(gh[..., 1, :, :] - first)            # both <= 1
        upto = jnp.exp(first - gh[..., 0, :, :])
        k_out = (kh[..., 0, :, :] * upto).astype(cdt)
        below = lambda x_in: jnp.pad(
            _dot("...ik,...jk->...ij", (x_in * into).astype(cdt), k_out),
            [(0, 0)] * (len(lead) + 1) + [(half, 0), (0, half)])
        m_qk = m_qk + on_diagonal(below(qh[..., 1, :, :]), 2 * half)
        m_kk = m_kk + on_diagonal(below(kh[..., 1, :, :]), 2 * half)
        half //= 2
    # the sub-blocks' own pairs, slice by slice
    n = c // SUB
    total = math.prod(lead) * n
    slices = math.gcd(total, max(1, total // _OWN_SLICE))
    flat = lambda t: t.reshape((slices, -1, SUB, kdim))
    own_qk, own_kk = jax.lax.map(
        lambda args: _own_pairs(*args), (flat(q32), flat(k32), flat(gam)))
    own = lambda t: on_diagonal(t.reshape(lead + (n, SUB, SUB)), SUB)
    return m_qk + own(own_qk), m_kk + own(own_kk)


def _wy(m_kk, beta, k_plus, v):
    """The delta rule's correction inside a chunk: ``T = (I + A)^-1
    Diag(beta)`` with ``A = beta o m_kk`` below the diagonal (one
    triangular solve in float32), then ``W = T K+`` and ``U = T V``."""
    c, cdt = m_kk.shape[-1], v.dtype
    a = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1),
                  beta[..., None] * m_kk, 0.0)
    t_inv = (jax.lax.linalg.triangular_solve(
        a, jnp.broadcast_to(jnp.eye(c, dtype=_F32), a.shape),
        left_side=True, lower=True, unit_diagonal=True) *
        beta[..., None, :]).astype(cdt)
    return (_dot("...ij,...jk->...ik", t_inv, k_plus).astype(cdt),
            _dot("...ij,...jv->...iv", t_inv, v).astype(cdt))


def _chunked(q, k, v, g, beta, c):
    bsz, s, n_heads, kdim = q.shape
    vdim = v.shape[-1]
    cdt = v.dtype
    q, k, v, g, beta = _whole_chunks(c, q, k, v, g, beta)
    nc = q.shape[1] // c
    # [b, nc, H, C, .]
    heads = lambda t: jnp.moveaxis(
        t.reshape((bsz, nc, c, n_heads) + t.shape[3:]), 3, 2)
    q, k, v = heads(q.astype(cdt)), heads(k.astype(cdt)), heads(v)
    beta = heads(beta.astype(_F32))                     # [b, nc, H, C]
    gam = jnp.cumsum(heads(g.astype(_F32)), axis=-2)    # [b, nc, H, C, K]
    k32 = k.astype(_F32)

    m_qk, m_kk = _pair_terms(q, k, gam)
    w, u = _wy(m_kk, beta, (k32 * jnp.exp(gam)).astype(cdt), v)

    # a chunk's effect on the state: S' = decay o S + R - KW S
    to_end = gam[..., -1:, :] - gam
    k_end = (k32 * jnp.exp(to_end)).astype(cdt)
    kw = _dot("...ck,...cj->...kj", k_end, w).astype(cdt)   # [.., K, K]
    r = _dot("...ck,...cv->...kv", k_end, u)                # [.., K, V]
    decay = jnp.exp(gam[..., -1, :])[..., None]             # [.., K, 1]

    def carry_on(state, inp):
        kw_c, r_c, d_c = inp
        new = state * d_c + r_c - _dot("...kj,...jv->...kv", kw_c,
                                       state.astype(cdt))
        return new, state

    chunks_first = lambda t: jnp.moveaxis(t, 1, 0)
    _, before = jax.lax.scan(
        carry_on, jnp.zeros((bsz, n_heads, kdim, vdim), _F32),
        tuple(map(chunks_first, (kw, r, decay))))
    before = jnp.moveaxis(before, 0, 1).astype(cdt)         # state entering

    q_plus = (q.astype(_F32) * jnp.exp(gam)).astype(cdt)
    u_new = (u.astype(_F32) -
             _dot("...ck,...kv->...cv", w, before)).astype(cdt)
    o = _dot("...ck,...kv->...cv", q_plus, before) + \
        _dot("...ij,...jv->...iv", m_qk.astype(cdt), u_new)
    o = jnp.moveaxis(o, 2, 3).reshape(bsz, nc * c, n_heads, vdim)
    return o[:, :s].astype(cdt)
