"""Pallas kernels of the chunked KDA scan: a chunk stays in VMEM.

``ops/kda_scan.py`` states the mathematics and holds XLA's form, which
writes every ``[C, K]`` intermediate of a chunk to HBM.  Here a grid step
is one chunk of a few heads: its tiles of q, k, v, g and beta are read
once, the running sums, the pair terms, ``(I + A)^-1``, ``W``, ``U``,
the outputs and the next state are formed in VMEM, and the state is
carried from chunk to chunk in a VMEM scratch (the grid's chunk axis is
sequential), as ``S^T [V, K]`` so that a decay by key channel is a row.
The backward walks the chunks in reverse with the state's gradient in
the same scratch.  A step's body is straight-line code (no loop inside),
so the compiler is free to run one head's products beside another's.

Heads go in PACKS of ``128 // C`` (two at chunks of 64): a pack's rows
are its heads' chunks one under another, ``R = 128`` rows, and its
``[R, R]`` squares (pair terms, ``A``, the inverse) are block diagonal,
one block a head, so that a product over a pack fills the MXU's 128 rows
where a head alone fills half.

The pair terms ``sum_c x_i k_j exp(Gamma_i - Gamma_j)`` (``j < i``) never
see a positive exponent.  The square is halved level by level down to
single rows, as XLA's form halves it down to 16: at the level of ``h``
rows a block of ``2 h`` rows takes its second half's first row ``r`` as
reference, row ``i``'s factor is ``exp(-|Gamma_i - Gamma_r|)`` (into
``r`` for the rows from it on, up to ``r`` for the rows before it, both at
most 1), and ONE product a level serves every block of that level; the
entries a level does not own are finite and masked.  The diagonal (``q_i
. k_i``, no decay) is a row sum.

``(I + A)^-1`` is built by blocks: the inverse of a block of ``2 n`` rows
from those of its halves, ``-T22 A21 T11`` below them, ``n = 1, 2, ..``.

Precision is ``kda_scan``'s: ``g``, every sum and decay, the inverse and
the carried state are float32; the products take their operands in v's
dtype and accumulate in float32 (float32 operands at full precision).
The inverse's own products are float32 at full precision for float32
inputs and three bf16 passes (the operands split into a high and a low
half, the low-by-low product dropped: about 2**-17) under bf16, where
its result is rounded to bf16 for ``W`` and ``U`` anyway.

What the backward reads back rather than recomputes: the state entering
each chunk (float32) and, in v's dtype, ``(I + A)^-1`` and the two
pair-term squares.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_fa = importlib.import_module(__package__ + ".flash_attention")

__all__ = ["available", "serves", "scan"]

_F32 = jnp.float32
_BF16 = jnp.bfloat16
HEADS = 4                   # heads of a grid step, at most
_VMEM_LIMIT = 64 * 1024 * 1024


def available() -> bool:
    return _fa.flash_attention_available()


def serves(kdim: int, vdim: int, chunk: int) -> bool:
    """Whole lane tiles a head, and a chunk of whole sublane tiles that
    fits the MXU's rows (``kda_scan`` has checked that it is 16 times a
    power of two)."""
    return kdim % 128 == 0 and vdim % 128 == 0 and 16 <= chunk <= 128


# ---------------------------------------------------------------------------
# products
# ---------------------------------------------------------------------------
def _mm(a, b, dims):
    """``a`` and ``b`` contracted over ``dims``, accumulated in float32;
    float32 operands at full precision (the process-wide default does not
    reach a kernel's products)."""
    full = a.dtype == _F32
    return jax.lax.dot_general(
        a, b, ((dims[:1], dims[1:]), ((), ())),
        precision=jax.lax.Precision.HIGHEST if full
        else jax.lax.Precision.DEFAULT,
        preferred_element_type=_F32)


_nn = lambda a, b: _mm(a, b, (1, 0))        # a b
_nt = lambda a, b: _mm(a, b, (1, 1))        # a b^T
_tn = lambda a, b: _mm(a, b, (0, 0))        # a^T b


def _halves(a):
    """Float32 ``a`` as a high and a low bf16 half."""
    high = a.astype(_BF16)
    return high, (a - high.astype(_F32)).astype(_BF16)


def _nn_split(a, b):
    """``a b`` of float32 squares in three bf16 passes."""
    (a_hi, a_lo), (b_hi, b_lo) = _halves(a), _halves(b)
    return _nn(a_hi, b_hi) + (_nn(a_hi, b_lo) + _nn(a_lo, b_hi))


# ---------------------------------------------------------------------------
# rows and squares of a pack
# ---------------------------------------------------------------------------
def _square(r: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (r, r), 0),
            jax.lax.broadcasted_iota(jnp.int32, (r, r), 1))


def _levels(c: int):
    half, out = c // 2, []
    while half >= 1:
        out.append(half)
        half //= 2
    return out


def _owned(r: int, half: int):
    """The entries the level of ``half`` rows owns: ``i`` in the second
    half of a block of ``2 half`` rows, ``j`` in its first."""
    i, j = _square(r)
    return ((i // half) % 2 == 1) & (j // half == i // half - 1)


def _reference_rows(x, half: int):
    """Row ``i`` of the result is row ``((i // half) | 1) * half`` of
    ``x [R, K]``: the first row of the second half of ``i``'s block of ``2
    half`` rows (sublane broadcasts inside whole tiles of 8 rows)."""
    r, width = x.shape
    tile = max(2 * half, 8)
    tiles = x.reshape(r // tile, tile, width)
    if tile == 2 * half:
        out = jnp.broadcast_to(tiles[:, half:half + 1, :], tiles.shape)
    else:
        sub = jax.lax.broadcasted_iota(jnp.int32, tiles.shape, 1)
        out = jnp.zeros_like(tiles)
        for first in range(half, 8, 2 * half):
            out = jnp.where(
                sub // (2 * half) == first // (2 * half),
                jnp.broadcast_to(tiles[:, first:first + 1, :], tiles.shape),
                out)
    return out.reshape(r, width)


def _last_rows(x, c: int):
    """Row ``i`` of the result is the last row of ``i``'s chunk."""
    r, width = x.shape
    chunks = x.reshape(r // c, c, width)
    return jnp.broadcast_to(chunks[:, c - 1:, :], chunks.shape).reshape(
        r, width)


def _running(x, c: int, backwards: bool = False):
    """Sums of ``x [R, K]`` down the rows of each chunk of ``c``, row
    ``i``'s own included (``backwards``: up the rows)."""
    r = x.shape[0]
    at = jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0) % c
    step = 1
    while step < c:
        if backwards:
            x = x + jnp.where(at < c - step, pltpu.roll(x, r - step, 0), 0.0)
        else:
            x = x + jnp.where(at >= step, pltpu.roll(x, step, 0), 0.0)
        step *= 2
    return x


def _inverse(a, c: int, split: bool):
    """``(I + a)^-1`` of ``a [R, R]``, strictly lower triangular blocks of
    ``c`` rows along the diagonal: blocks of 2, 4, ... rows from their
    halves' inverses."""
    r = a.shape[0]
    i, j = _square(r)
    product = _nn_split if split else _nn
    t = (i == j).astype(_F32) - jnp.where(_owned(r, 1), a, 0.0)
    size = 2
    while size < c:
        below = jnp.where(_owned(r, size), a, 0.0)
        t = t - product(t, product(below, t))
        size *= 2
    return t


# ---------------------------------------------------------------------------
# a chunk of one pack of heads
# ---------------------------------------------------------------------------
def _factors(gam, c: int):
    """Each level's factors ``exp(-|gam_i - gam_r|) [R, K]``."""
    return [jnp.exp(-jnp.abs(gam - _reference_rows(gam, half)))
            for half in _levels(c)]


def _pair_terms(q32, k32, facs, c, cdt):
    """``(m_qk, m_kk) [R, R]`` float32: the pairs ``j <= i`` of a head
    (``m_kk``: ``j < i``), 0 elsewhere."""
    r = q32.shape[0]
    i, j = _square(r)
    m_qk = jnp.where(i == j, jnp.sum(q32 * k32, 1, keepdims=True), 0.0)
    m_kk = 0.0
    for half, fac in zip(_levels(c), facs):
        xk = (k32 * fac).astype(cdt)
        mine = _owned(r, half)
        m_qk = m_qk + jnp.where(mine, _nt((q32 * fac).astype(cdt), xk), 0.0)
        m_kk = m_kk + jnp.where(mine, _nt(xk, xk), 0.0)
    return m_qk, m_kk


def _under(tiles):
    """The tiles one under another."""
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, 0)


def _by_head(fn, c, *packed):
    """``fn`` over each head's rows of the packed arrays, the results one
    under another."""
    return _under([fn(h, *(t[h * c:(h + 1) * c] for t in packed))
                   for h in range(packed[0].shape[0] // c)])


def _through_state(q32, k32, v, gam, beta, sts, t_c, m_qk_c, c):
    """From the inverse and the pairs (in the compute dtype) to the
    outputs ``[R, V]`` and the heads' next states; also what the backward
    needs of the way there.  ``sts``: each head's state entering."""
    cdt = v.dtype
    kdim = q32.shape[1]
    e = jnp.exp(gam)
    kv = jnp.concatenate([k32 * e, v.astype(_F32)], 1)      # [K+ | V]
    wu = _nn(t_c, (beta * kv).astype(cdt)).astype(cdt)      # [W | U]
    w = wu[:, :kdim]
    s_cs = [st.astype(cdt) for st in sts]
    u_new = (wu[:, kdim:].astype(_F32) - _by_head(
        lambda h, w_h: _nt(w_h, s_cs[h]), c, w)).astype(cdt)
    q_plus = (q32 * e).astype(cdt)
    o = _by_head(lambda h, q_h: _nt(q_h, s_cs[h]), c, q_plus) + \
        _nn(m_qk_c, u_new)
    last = _last_rows(gam, c)
    to_end, decay = jnp.exp(last - gam), jnp.exp(last)
    k_end = k32 * to_end
    k_end_c = k_end.astype(cdt)
    news = [sts[h] * decay[h * c:h * c + 1] +
            _tn(u_new[h * c:(h + 1) * c], k_end_c[h * c:(h + 1) * c])
            for h in range(len(sts))]
    return o, news, (e, kv, wu, s_cs, u_new, q_plus, k_end, to_end, decay)


def _packed(ref, heads, width):
    """The heads' ``[C, width]`` tiles of a ``[1, C, hb width]`` block,
    one under another."""
    return _under([ref[0, :, h * width:(h + 1) * width] for h in heads])


def _packed_beta(ref, heads):
    return _under([ref[0, 0, :, h:h + 1] for h in heads])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                heads: int, pack: int, save: bool):
    if save:
        st_ref, t_ref, mqk_ref, mkk_ref = rest[:4]
    state = rest[-1]
    c = q_ref.shape[1]
    kdim = q_ref.shape[-1] // heads
    vdim = v_ref.shape[-1] // heads
    cdt = v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    for n in range(heads // pack):
        mine = range(n * pack, (n + 1) * pack)
        q, k = _packed(q_ref, mine, kdim), _packed(k_ref, mine, kdim)
        v, g = _packed(v_ref, mine, vdim), _packed(g_ref, mine, kdim)
        beta = _packed_beta(beta_ref, mine)
        q32, k32 = q.astype(_F32), k.astype(_F32)
        gam = _running(g, c)
        m_qk, m_kk = _pair_terms(q32, k32, _factors(gam, c), c, cdt)
        t = _inverse(beta * m_kk, c, split=cdt != _F32)
        sts = [state[h] for h in mine]
        t_c, m_qk_c = t.astype(cdt), m_qk.astype(cdt)
        o, news, _ = _through_state(q32, k32, v, gam, beta, sts, t_c,
                                    m_qk_c, c)
        for at, h in enumerate(mine):
            o_ref[0, :, h * vdim:(h + 1) * vdim] = \
                o[at * c:(at + 1) * c].astype(o_ref.dtype)
            state[h] = news[at]
            if save:
                st_ref[0, 0, h] = sts[at]
        if save:
            t_ref[0, 0, n] = t_c
            mqk_ref[0, 0, n] = m_qk_c
            mkk_ref[0, 0, n] = m_kk.astype(cdt)


# ---------------------------------------------------------------------------
# the backward of a chunk of one pack
# ---------------------------------------------------------------------------
def _backward_pack(q, k, v, g, beta, sts, t_c, m_qk_c, m_kk_c, do, dsts, c):
    """Cotangents ``do [R, V]`` and ``dsts`` (each head's, of the state
    LEAVING the chunk) -> ``(dq, dk, dv, dg [R, .], dbeta [R, 1], each
    head's cotangent of the state entering)``, float32."""
    r, kdim = q.shape
    cdt = v.dtype
    q32, k32 = q.astype(_F32), k.astype(_F32)
    gam = _running(g, c)
    _, _, (e, kv, wu, s_cs, u_new, q_plus, k_end, to_end, decay) = \
        _through_state(q32, k32, v, gam, beta, sts, t_c, m_qk_c, c)
    w = wu[:, :kdim]
    do_c = do.astype(cdt)
    dst_cs = [d.astype(cdt) for d in dsts]
    k_end_c = k_end.astype(cdt)
    i, j = _square(r)
    same = i // c == j // c

    # the outputs and the next state
    du_new = (_tn(m_qk_c, do_c) + _by_head(
        lambda h, k_h: _nt(k_h, dst_cs[h]), c, k_end_c)).astype(cdt)
    dm_qk = jnp.where(same & (i >= j), _nt(do_c, u_new), 0.0)
    dq_plus = _by_head(lambda h, do_h: _nn(do_h, s_cs[h]), c, do_c)
    dk_end = _by_head(lambda h, u_h: _nn(u_h, dst_cs[h]), c, u_new)
    kend_term = dk_end * k_end
    dsts_in, d_last = [], []
    for h, (st, dst) in enumerate(zip(sts, dsts)):
        rows = slice(h * c, (h + 1) * c)
        dsts_in.append(_tn(do_c[rows], q_plus[rows]) +
                       dst * decay[h * c:h * c + 1] -
                       _tn(du_new[rows], w[rows]))
        d_last.append(jnp.sum(dst * st, 0, keepdims=True) *
                      decay[h * c:h * c + 1] +
                      jnp.sum(kend_term[rows], 0, keepdims=True))
    # [W | U] = T [beta K+ | beta V]
    dwu = jnp.concatenate([-_by_head(
        lambda h, du_h: _nn(du_h, s_cs[h]), c, du_new),
        du_new.astype(_F32)], 1)
    x = _tn(t_c, dwu.astype(cdt))                           # [R, K + V]
    d_a = jnp.where(same & (i > j), -_nt(x.astype(cdt), wu), 0.0)
    dbeta = jnp.sum(x * kv, 1, keepdims=True) + \
        jnp.sum(d_a * m_kk_c.astype(_F32), 1, keepdims=True)
    dm_kk = beta * d_a
    dk_plus = beta * x[:, :kdim]
    dv = beta * x[:, kdim:]

    # the decays that are not pair terms
    at = jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0) % c
    dgam = (dq_plus * q32 + dk_plus * k32) * e - kend_term
    dgam = dgam + jnp.where(at == c - 1, _by_head(
        lambda h, t: jnp.broadcast_to(d_last[h], t.shape), c, dgam), 0.0)
    dq = dq_plus * e
    dk = dk_plus * e + dk_end * to_end

    # the pair terms: the diagonal, then level by level
    on_diagonal = jnp.sum(jnp.where(i == j, dm_qk, 0.0), 1, keepdims=True)
    dq = dq + on_diagonal * k32
    dk = dk + on_diagonal * q32
    for half, fac in zip(_levels(c), _factors(gam, c)):
        xq, xk = q32 * fac, k32 * fac
        mine = _owned(r, half)
        dm = jnp.concatenate([jnp.where(mine, dm_qk, 0.0),
                              jnp.where(mine, dm_kk, 0.0)], 0).astype(cdt)
        xk_c = xk.astype(cdt)
        d_in = _nn(dm, xk_c)                                # [2 R, K]
        d_out = _tn(dm, jnp.concatenate([xq.astype(cdt), xk_c], 0))
        dk_level = d_in[r:] + d_out
        dq = dq + d_in[:r] * fac
        dk = dk + dk_level * fac
        # the exponent is gam_i - gam_r from the reference row on and
        # gam_r - gam_i before it; a pair's two uses of gam_r cancel
        after = (at // half) % 2 == 1
        d_exp = d_in[:r] * xq + dk_level * xk
        dgam = dgam + jnp.where(after, d_exp, -d_exp)

    return dq, dk, dv, _running(dgam, c, backwards=True), dbeta, dsts_in


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, st_ref, t_ref,
                mqk_ref, mkk_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                dbeta_ref, dstate, *, heads: int, pack: int):
    c = q_ref.shape[1]
    kdim = q_ref.shape[-1] // heads
    vdim = v_ref.shape[-1] // heads

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    lane = jax.lax.broadcasted_iota(jnp.int32, dbeta_ref.shape[2:], 1)
    dbetas = jnp.zeros(dbeta_ref.shape[2:], _F32)
    for n in range(heads // pack):
        mine = range(n * pack, (n + 1) * pack)
        dq, dk, dv, dg, dbeta, dsts_in = _backward_pack(
            _packed(q_ref, mine, kdim), _packed(k_ref, mine, kdim),
            _packed(v_ref, mine, vdim), _packed(g_ref, mine, kdim),
            _packed_beta(beta_ref, mine), [st_ref[0, 0, h] for h in mine],
            t_ref[0, 0, n], mqk_ref[0, 0, n], mkk_ref[0, 0, n],
            _packed(do_ref, mine, vdim), [dstate[h] for h in mine], c)
        for at, h in enumerate(mine):
            rows = slice(at * c, (at + 1) * c)
            ks = slice(h * kdim, (h + 1) * kdim)
            dq_ref[0, :, ks] = dq[rows].astype(dq_ref.dtype)
            dk_ref[0, :, ks] = dk[rows].astype(dk_ref.dtype)
            dv_ref[0, :, h * vdim:(h + 1) * vdim] = \
                dv[rows].astype(dv_ref.dtype)
            dg_ref[0, :, ks] = dg[rows]
            dbetas = jnp.where(lane == h, dbeta[rows], dbetas)
            dstate[h] = dsts_in[at]
    dbeta_ref[0, 0] = dbetas


# ---------------------------------------------------------------------------
# the calls
# ---------------------------------------------------------------------------
def _largest(n: int, most: int) -> int:
    return max(m for m in range(1, most + 1) if n % m == 0)


def _heads_a_step(n_heads: int) -> int:
    return _largest(n_heads, HEADS)


def _heads_a_pack(hb: int, c: int) -> int:
    return _largest(hb, max(1, 128 // c))


def _specs(c, hb, pack, kdim, vdim, order):
    """Block specs of a chunk of ``hb`` heads; ``order`` maps the grid's
    chunk index to the chunk (the backward walks them in reverse)."""
    wide = lambda width: pl.BlockSpec(
        (1, c, hb * width), lambda b, h, n: (b, order(n), h))
    beta = pl.BlockSpec((1, 1, c, hb), lambda b, h, n: (b, h, order(n), 0))
    kept = lambda count, *shape: pl.BlockSpec(
        (1, 1, count) + shape, lambda b, h, n: (b, order(n), h, 0, 0))
    r = pack * c
    return (wide(kdim), wide(vdim), beta, kept(hb, vdim, kdim),
            kept(hb // pack, r, r))


def _sizes(q, v, beta, c):
    hb = beta.shape[-1]
    n_heads = beta.shape[1] * hb
    return (hb, _heads_a_pack(hb, c), n_heads, q.shape[-1] // n_heads,
            v.shape[-1] // n_heads)


def _call(kernel, name, grid, hb, vdim, kdim, **specs):
    return pl.pallas_call(
        kernel, grid=grid,
        scratch_shapes=[pltpu.VMEM((hb, vdim, kdim), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_fa._INTERPRET, name=name, **specs)


def _forward(q, k, v, g, beta, c, save):
    """``q``/``k``/``g [b, s, H K]``, ``v [b, s, H V]``, ``beta [b, H /
    hb, s, hb]`` with ``s`` whole chunks -> ``o [b, s, H V]`` and, with
    ``save``, what the backward reads back."""
    bsz, s, _ = q.shape
    hb, pack, n_heads, kdim, vdim = _sizes(q, v, beta, c)
    nc, r = s // c, pack * c
    by_k, by_v, by_beta, states, squares = _specs(
        c, hb, pack, kdim, vdim, lambda n: n)
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    out_specs = [by_v]
    if save:
        square = jax.ShapeDtypeStruct(
            (bsz, nc, n_heads // pack, r, r), v.dtype)
        out_shape += [jax.ShapeDtypeStruct(
            (bsz, nc, n_heads, vdim, kdim), _F32)] + [square] * 3
        out_specs += [states] + [squares] * 3
    out = _call(
        functools.partial(_fwd_kernel, heads=hb, pack=pack, save=save),
        "kda_chunk_fwd", (bsz, n_heads // hb, nc), hb, vdim, kdim,
        in_specs=[by_k, by_k, by_v, by_k, by_beta],
        out_specs=out_specs, out_shape=out_shape)(q, k, v, g, beta)
    return out if save else out[0]


def _backward(q, k, v, g, beta, kept, do, c):
    bsz, s, _ = q.shape
    hb, pack, n_heads, kdim, vdim = _sizes(q, v, beta, c)
    nc = s // c
    by_k, by_v, by_beta, states, squares = _specs(
        c, hb, pack, kdim, vdim, lambda n: nc - 1 - n)
    like = lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype)
    return _call(
        functools.partial(_bwd_kernel, heads=hb, pack=pack),
        "kda_chunk_bwd", (bsz, n_heads // hb, nc), hb, vdim, kdim,
        in_specs=[by_k, by_k, by_v, by_k, by_beta, states] +
        [squares] * 3 + [by_v],
        out_specs=[by_k, by_k, by_v, by_k, by_beta],
        out_shape=[like(q), like(k), like(v), like(g), like(beta)],
    )(q, k, v, g, beta, *kept, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(q, k, v, g, beta, c):
    return _forward(q, k, v, g, beta, c, save=False)


def _scan_fwd(q, k, v, g, beta, c):
    o, *kept = _forward(q, k, v, g, beta, c, save=True)
    return o, (q, k, v, g, beta, kept)


def _scan_bwd(c, saved, do):
    q, k, v, g, beta, kept = saved
    with jax.named_scope("kda_scan"):
        return tuple(_backward(q, k, v, g, beta, kept, do, c))


_scan.defvjp(_scan_fwd, _scan_bwd)


def scan(q, k, v, g, beta, c):
    """`kda_scan`'s arguments (``q``/``k`` in v's dtype, ``g`` and
    ``beta`` float32, the length whole chunks of ``c``) -> ``o [b, s, H,
    V]`` in v's dtype."""
    bsz, s, n_heads, _ = q.shape
    hb = _heads_a_step(n_heads)
    flat = lambda t: t.reshape(bsz, s, -1)
    # [b, s, H] -> [b, H / hb, s, hb]: a step's heads side by side
    beta = jnp.moveaxis(beta.reshape(bsz, s, n_heads // hb, hb), 2, 1)
    o = _scan(flat(q), flat(k), flat(v), flat(g), beta, c)
    return o.reshape(bsz, s, n_heads, -1)
