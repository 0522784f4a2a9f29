"""Chunked state-space scan (Mamba-2's SSD form).

The recurrence, per head h with a scalar decay and a state ``[P, N]``::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        y_t = C_t . h_t

is computed in chunks of ``chunk`` positions (Dao & Gu 2024, "Transformers
are SSMs", section 6).  Inside a chunk it is its dual, a masked
attention-like product ``((C B^T) o L) (dt x)`` with the decay matrix
``L[i, j] = exp(cum_i - cum_j)`` for ``i >= j``; a chunk's effect on what
follows is one state ``[P, N]``, and only those states are passed on, by a
short sequential scan over the chunks.  Everything but that scan is a
batched matrix product, so the MXU does the work; the backward is the
same chunked form, differentiated by JAX (the layer's remat recomputes
the forward).

Precision: the decay (``dt A``, its cumulative sums and their
exponentials) and the carried states stay float32 whatever the inputs;
the matrix products take their operands in the inputs' dtype (bf16 under
AMP) and accumulate in float32.

The chunked form has ONE implementation, in XLA's own operations, on the
chip and off it, so ``ssd_scan`` has no choice for ``ops.kernel_paths``
to record; the single-token step below has one too.  A Pallas kernel for
the chunk's product (a chunk's
group of 8 heads a grid step, ``C B^T`` once, then mask, decay and a
``[128, 128] x [128, 64]`` product a head, the backward as the same
kernel three more times with the operands' roles exchanged) was written,
agreed with this file to rounding, and LOST on the chip: 173.6 ms a step
against 96.1 for these einsums at 2 x 8192 positions (PERF.md, PR 33): a
head's products are too small to fill the MXU from inside one grid step,
and XLA batches them over all heads and chunks.  It was deleted.

Serving reads the same recurrence through three more entries:
``ssd_scan_with_state`` (the chunked form from an initial state, handing
back the state after the last position: a prefill), ``ssd_step`` (one
token a slot over a state ``[slots, H, P, N]`` float32 updated where it
lies: the decode tick), and the convolution's two serving forms, ``causal_conv1d_step`` over a
window of the last ``K - 1`` inputs a slot and ``conv_window``, which
cuts that window out of a prefill's inputs.  The step is XLA's own: ONE
fusion reads a donated state, decays it, adds the token's outer product,
stores it over itself and contracts it with C (0.413 ms a layer at 64
slots of ``[64, 64, 128]``, 79% of the bytes' roofline).  A Pallas kernel
for it (a grid step a slot, the state in VMEM, a head at a time, the
decays from SMEM) was written, agreed to rounding, and LOST on the chip:
0.486 ms a layer in the same probe, 65% inside the served tick (PERF.md,
PR 48): a head's 64 row sums over 128 lanes cost it as much as the
update.  It was deleted.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["ssd_scan", "ssd_scan_with_state", "ssd_step", "causal_conv1d",
           "causal_conv1d_step", "conv_window"]

_F32 = jnp.float32


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=_F32)


def ssd_scan(x, dt, a_neg, b_mat, c_mat, chunk: int = 128):
    """``x [b, s, H, P]``, ``dt [b, s, H]`` (after its softplus),
    ``a_neg [H]`` (negative), ``b_mat``/``c_mat [b, s, G, N]`` with head h
    reading group ``h // (H / G)``.  Returns ``y [b, s, H, P]`` in x's
    dtype, without the ``D x`` skip.  Any length: the tail is padded with
    ``dt = 0`` steps, which neither decay nor write the state."""
    with jax.named_scope("ssd_scan"):
        return _chunked(x, dt, a_neg, b_mat, c_mat, int(chunk))


def ssd_scan_with_state(x, dt, a_neg, b_mat, c_mat, chunk: int = 128,
                        state=None):
    """``ssd_scan`` from ``state [b, G, r, P, N]`` float32 (None: zeros),
    also handing back the state after the last position: ``(y, state)``.
    A position with ``dt = 0`` neither decays nor writes, so a caller
    that zeroes ``dt`` past a row's real tokens gets the state after its
    last real one (and garbage ``y`` past it)."""
    with jax.named_scope("ssd_scan"):
        return _chunked_from(x, dt, a_neg, b_mat, c_mat, int(chunk), state)


def ssd_step(x, dt, a_neg, b_mat, c_mat, state, active=None):
    """One token a slot: ``x [B, H, P]``, ``dt [B, H]`` (after its
    softplus), ``a_neg [H]``, ``b_mat``/``c_mat [B, G, N]``, ``state
    [B, H, P, N]`` float32.  ``S <- exp(dt A) S + dt x (x) B``, ``y = S
    C``: returns ``(y [B, H, P] float32, state)``, without the ``D x``
    skip, in ONE pass over the state (one fusion with two results),
    which a donated buffer takes where it lies.  A slot with ``active ==
    0`` neither decays nor writes."""
    if active is not None:
        dt = jnp.where((jnp.asarray(active) > 0)[:, None], dt, 0)
    with jax.named_scope("ssm_step"):
        bsz, n_heads, p = x.shape
        n_groups, n = b_mat.shape[1:]
        r = n_heads // n_groups
        dt = dt.astype(_F32)
        decay = jnp.exp(dt * a_neg.astype(_F32))                # [B, H]
        xdt = x.astype(_F32) * dt[..., None]                    # [B, H, P]
        s5 = state.reshape(bsz, n_groups, r, p, n)
        s5 = s5 * decay.reshape(bsz, n_groups, r, 1, 1) + \
            xdt.reshape(bsz, n_groups, r, p, 1) * \
            b_mat.astype(_F32)[:, :, None, None, :]
        y = jnp.sum(s5 * c_mat.astype(_F32)[:, :, None, None, :], axis=-1)
        return y.reshape(bsz, n_heads, p), s5.reshape(state.shape)


def _intra_chunk(cc, bc, xdt, cum):
    """A chunk's own product ((C B^T) o L) (dt x): ``cc``/``bc [b, nc, Q,
    G, N]``, ``xdt [b, nc, Q, G, r, P]``, ``cum [b, nc, G, r, Q]``."""
    q = xdt.shape[2]
    cb = _dot("bcign,bcjgn->bcgij", cc, bc)                 # [b,nc,G,Q,Q]
    seg = cum[..., :, None] - cum[..., None, :]             # [b,nc,G,r,Q,Q]
    causal = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    m = (cb[:, :, :, None] * decay).astype(xdt.dtype)
    return _dot("bcgrij,bcjgrp->bcigrp", m, xdt)


def _chunked(x, dt, a_neg, b_mat, c_mat, q):
    """``y`` alone, from a zero state: ``ssd_scan``'s body (the
    benchmark's planted faults wrap this name)."""
    return _chunked_from(x, dt, a_neg, b_mat, c_mat, q, None)[0]


def _chunked_from(x, dt, a_neg, b_mat, c_mat, q, state):
    """``(y, the state after the last position [b, G, r, P, N])`` from
    ``state`` (None: zeros)."""
    bsz, s, n_heads, p = x.shape
    n_groups, n = b_mat.shape[2], b_mat.shape[3]
    r = n_heads // n_groups
    cdt = x.dtype
    pad = (-s) % q
    if pad:
        widen = lambda t: jnp.pad(t, [(0, 0), (0, pad)] +
                                  [(0, 0)] * (t.ndim - 2))
        x, dt, b_mat, c_mat = map(widen, (x, dt, b_mat, c_mat))
    nc = (s + pad) // q
    dt = dt.astype(_F32)
    # [b, nc, G, r, Q]: the decay's exponent and its running sum, float32
    a = (dt * a_neg.astype(_F32)).reshape(bsz, nc, q, n_groups, r)
    cum = jnp.cumsum(jnp.moveaxis(a, 2, -1), axis=-1)
    xdt = (x.astype(_F32) * dt[..., None]).astype(cdt).reshape(
        bsz, nc, q, n_groups, r, p)
    bc = b_mat.astype(cdt).reshape(bsz, nc, q, n_groups, n)
    cc = c_mat.astype(cdt).reshape(bsz, nc, q, n_groups, n)

    # inside a chunk: ((C B^T) o L) (dt x)
    y = _intra_chunk(cc, bc, xdt, cum)

    # each chunk's own state at its end, then the states passed on
    to_end = jnp.exp(cum[..., -1:] - cum)                   # [b,nc,G,r,Q]
    xw = (xdt.astype(_F32) *
          jnp.moveaxis(to_end, -1, 2)[..., None]).astype(cdt)
    own = _dot("bcjgrp,bcjgn->bcgrpn", xw, bc)              # [b,nc,G,r,P,N]
    chunk_decay = jnp.exp(cum[..., -1])                     # [b,nc,G,r]

    def carry_on(h, inp):
        s_c, d_c = inp
        return h * d_c[..., None, None] + s_c, h

    if state is None:
        state = jnp.zeros((bsz, n_groups, r, p, n), _F32)
    last, before = jax.lax.scan(
        carry_on, state,
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    before = jnp.moveaxis(before, 0, 1).astype(cdt)         # state entering
    from_start = jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]
    y = y + _dot("bcign,bcgrpn->bcigrp", cc, before) * from_start
    y = y.reshape(bsz, nc * q, n_heads, p)
    return (y[:, :s] if pad else y).astype(cdt), last


def _taps(xp, w, s):
    """``sum_j xp[:, j:j + s] * w[:, j]`` over the K taps."""
    return sum(xp[:, j:j + s] * w[:, j] for j in range(w.shape[1]))


@jax.custom_vjp
def _conv(x, weight, bias):
    k = weight.shape[1]
    xp = jnp.pad(x, [(0, 0), (k - 1, 0), (0, 0)])
    return bias.astype(x.dtype) + _taps(xp, weight.astype(x.dtype),
                                        x.shape[1])


def _conv_fwd(x, weight, bias):
    return _conv(x, weight, bias), (x, weight)


def _conv_bwd(saved, dy):
    """dx as the forward computes (in dy's dtype); the gradients of
    ``weight`` and ``bias`` are sums over every position of the batch and
    are taken in float32: differentiated as written they are bf16
    reductions, which lose the small terms of 16,384 (PERF.md, PR 33)."""
    x, weight = saved
    k, s = weight.shape[1], x.shape[1]
    xp = jnp.pad(x, [(0, 0), (k - 1, 0), (0, 0)])
    dy32 = dy.astype(_F32)
    d_bias = jnp.sum(dy32, axis=(0, 1))
    d_weight = jnp.stack(
        [jnp.sum(dy32 * xp[:, j:j + s].astype(_F32), axis=(0, 1))
         for j in range(k)], axis=1)
    # out[t] reads x[t + j - (k - 1)], so x[u] feeds out[u + (k - 1) - j]
    dyp = jnp.pad(dy, [(0, 0), (0, k - 1), (0, 0)])
    dx = _taps(dyp, weight.astype(dy.dtype)[:, ::-1], s)
    return dx.astype(x.dtype), d_weight.astype(weight.dtype), \
        d_bias.astype(weight.dtype)


_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv1d(x, weight, bias=None):
    """Depthwise causal convolution over the sequence: ``x [b, s, C]``,
    ``weight [C, K]``, ``bias [C]`` (None: no bias); position t reads
    t-K+1 .. t."""
    if bias is None:
        bias = jnp.zeros(weight.shape[:1], weight.dtype)
    return _conv(x, weight, bias)


def causal_conv1d_step(window, x_new, weight, bias=None):
    """``causal_conv1d`` for ONE new position a slot: ``window [B, K-1,
    C]`` the slot's last ``K - 1`` inputs (oldest first), ``x_new [B,
    C]``.  Returns ``(y [B, C], the window moved on by one)``, y what
    ``causal_conv1d`` gives at that position, tap for tap."""
    full = jnp.concatenate([window, x_new[:, None].astype(window.dtype)], 1)
    if bias is None:
        bias = jnp.zeros(weight.shape[:1], weight.dtype)
    y = bias.astype(full.dtype) + _taps(full, weight.astype(full.dtype), 1)
    return y[:, 0], full[:, 1:]


def conv_window(x, real, k: int):
    """The window a prefill leaves: the inputs ``x [B, S, C]`` at the
    last ``k - 1`` real positions of each row (``real [B]`` of them are
    real), zeros where a row is shorter than that."""
    xp = jnp.pad(x, [(0, 0), (k - 1, 0), (0, 0)])
    at = jnp.asarray(real, jnp.int32)[:, None] + \
        jnp.arange(k - 1, dtype=jnp.int32)[None, :]
    return jnp.take_along_axis(xp, at[..., None], axis=1)
