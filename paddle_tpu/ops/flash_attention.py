"""Pallas TPU flash attention — fused forward AND backward.

The reference has no training-time fused attention (only the inference
fused/multihead_matmul_op.cu); this kernel is the TPU-native upgrade: the
[B,H,S,S] score matrix never leaves VMEM in either direction — forward
streams k/v blocks through the MXU with a running max/denominator
(online softmax), backward recomputes the probabilities blockwise from
the saved per-row logsumexp (the standard flash recompute strategy), so
HBM traffic is O(S·D) instead of O(S²) for fwd and bwd alike.

Backward = ONE kernel per key block, looping over the query tiles (and,
across grid steps, the GQA groups): the probabilities and ds are rebuilt
once a tile and feed all three gradients, five products a tile
  dv_j = Σ_i p_ij do_i, dk_j = scale * Σ_i ds_ij q_i,
  dq_i = scale * Σ_j ds_ij k_j (summed over the key blocks in VMEM)
  with p_ij = exp(scale·q_i·k_j − lse_i), ds_ij = p_ij (do_i·v_j − δ_i),
  δ_i = do_i·o_i (one cheap XLA rowsum before the kernel).
All inner [block_k, block_q] tiles live in registers/VMEM only.

GQA is native: q is laid out [B·Hkv, G, S, D] and k/v [B·Hkv, S, D]; the
grid walks (kv-head, group, block), so grouped-query models never
materialize repeat_interleaved K/V (G enters as a grid dimension, and
the backward kernel accumulates dk/dv over it in-place across grid steps).

An optional key-padding mask [B, S] (1 = attend, 0 = masked) covers the
padded-batch pretraining case without an O(S²) bias tensor; arbitrary
additive masks still fall back to the XLA composite.

What a remat policy can keep: the forward rule of the differentiated
call (`_flash_fwd`) passes the two things the backward needs from the
kernel through `jax.ad_checkpoint.checkpoint_name`: the output, as the
caller gets it, under `flash_out` and the per-row log-sum-exp under
`flash_lse` (RESIDUAL_NAMES).  A `jax.checkpoint` policy that saves those
names (`distributed.recompute.checkpoint_policy("dots" |
"dots_no_batch")` does) spares the backward a second run of the forward
kernel; under any other policy a name is an identity.  The
undifferentiated call (`_flash`: serving's prefills) carries no name.

Layout contract: q [B, S, H, D], k [B, S, Hkv, D], v [B, S, Hkv, Dv] with
H % Hkv == 0.  The scores' width D and the values' width Dv are two
numbers: every kernel takes q and k at D (the softmax scale is
``D ** -0.5``) and v, o and do at Dv, so latent attention's 192-wide
scores over 128-wide values (128 + 64 channels, no padding to 256) run
through the same kernels as D == Dv.  Each width is 64, 192 or a
multiple of 128.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from . import kernel_paths

_INTERPRET = False  # set True in tests to run the kernel on CPU
# what the differentiated call names for a remat policy to keep: the
# kernel's output and its per-row log-sum-exp
RESIDUAL_NAMES = ("flash_out", "flash_lse")
_NEG = -1e30


def check_interpret_allowed(flag) -> None:
    """Interpret mode is a CPU test switch: refuse it on a chip, where
    it would quietly replace every kernel with its interpreter."""
    if flag and jax.default_backend() == "tpu":
        raise RuntimeError(
            "Pallas interpret mode is a CPU test switch and cannot be "
            "turned on when the backend is tpu")


def run_kernel(dtype, call, *operands):
    """Invoke a built ``pl.pallas_call`` on its operands.  A process-wide
    ``jax_default_matmul_precision`` of 'highest' (the test suite pins it,
    and so may a user checking numerics) reaches every dot traced without
    an explicit precision, the kernels' included — and Mosaic refuses an
    fp32-precision contraction of bf16 or int8 operands ("Bad lhs type").
    Kernels over float32 operands keep the caller's precision; all others
    trace at the default, which is exact for them anyway (bf16 x bf16 and
    int8 x int8 products fit the f32/int32 accumulator)."""
    if jnp.dtype(dtype) == jnp.float32:
        return call(*operands)
    with jax.default_matmul_precision("default"):
        return call(*operands)


def set_interpret_mode(flag: bool):
    global _INTERPRET
    check_interpret_allowed(flag)
    _INTERPRET = bool(flag)


def flash_attention_available() -> bool:
    return _INTERPRET or jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# what the kernels wait for
#
# Timed alone on a v5e (PERF.md section 6, PR 39) the kernels are bound
# by how many rows they push through the MXU, not by the vector unit: a
# head of 64 fills half the array's depth (q.k, do.v) or half its width
# (p.v and the three gradients), and knocking every mask out of the
# loops bought 6%.  What pays is fewer products (the backward rebuilds
# the scores and ds once, not once for dq and once for dk/dv), fewer
# reductions across lanes (every tile is [bk, bq]: below), and fewer
# elements visited (the tiles in _AUTOTUNE_TABLE).  Each body is still
# built from what a call can observe, all of it static:
#   - no key mask passed: no mask operand, no compare and select, and no
#     guard for fully masked rows (under `causal` alone a row attends at
#     least its own position, so exp(_NEG - m) is exactly 0);
#   - `causal`: each loop runs twice, first over the tiles that lie
#     wholly under the diagonal (no iota, compare or select), then over
#     the tile or tiles the diagonal crosses; those above stay unvisited;
#   - the scores stay as the MXU leaves them, q.k unscaled, and `scale`
#     goes into the exponent's constant: exp2(s * scale * log2 e - ...).
#     The running max is kept in the scores' own units; `lse` is stored
#     in natural-log units of the scaled scores, as it always was.
# ---------------------------------------------------------------------------
_LOG2E = math.log2(math.e)


def _attends(shape, q_axis: int, q0, k0):
    """Bool tile: the element's query (along ``q_axis``, counted from q0)
    is at or past its key (along the other axis, counted from k0)."""
    ahead = (jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
             - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))
    return ahead >= k0 - q0


def _visit(tile, init, whole, crossed):
    """``tile(j, carry, crossed)`` over the range of tiles the diagonal
    leaves whole, then over the range it crosses (None: not causal).
    Whole tiles first: a row's first tile then holds a key it attends,
    so its running max is finite before a tile that masks all of it."""
    carry = jax.lax.fori_loop(
        *whole, functools.partial(tile, crossed=False), init)
    if crossed is None:
        return carry
    return jax.lax.fori_loop(
        *crossed, functools.partial(tile, crossed=True), carry)


def _key_tiles(q_start, block_q: int, block_k: int, n_k: int, causal):
    """(whole, crossed) ranges of the key tiles a query block visits."""
    if not causal:
        return (0, n_k), None
    first_crossed = (q_start + 1) // block_k
    end = jnp.minimum((q_start + block_q + block_k - 1) // block_k, n_k)
    return (0, first_crossed), (first_crossed, end)


def _query_tiles(k_start, block_q: int, block_k: int, n_q: int, causal):
    """(whole, crossed) ranges of the query tiles a key block meets:
    those it crosses come first in the sequence, the whole ones after."""
    if not causal:
        return (0, n_q), None
    first_whole = jnp.minimum(
        (k_start + block_k + block_q - 2) // block_q, n_q)
    return (first_whole, n_q), (k_start // block_q, first_whole)


# ---------------------------------------------------------------------------
# the kernels.  Every tile is [bk, bq], keys down the sublanes and
# queries along the lanes: what there is one of a query (the running max
# and sum, lse, delta) is then a dense row of lanes and not a column
# with one lane in use, and a reduction over the keys runs elementwise
# down the tile and not across lanes.  The forward's accumulator is
# turned too, [dv, bq] = vT.pT, so its rescale is a row against rows
# and it is turned back once, on its way out (a third off the forward
# at heads of 64); of the backward's products only dq contracts a tile
# over its first dimension.
# ---------------------------------------------------------------------------
def _scores_t(k_blk, q_blk, kv_f, q0, k0):
    """sT [bk, bq] = k.q, unscaled, masked to _NEG.  ``kv_f``: the key
    mask's slice, or None; ``q0``: where the tile's queries start when
    the diagonal crosses it (its keys start at k0), or None."""
    st = jax.lax.dot_general(
        k_blk, q_blk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                # [bk, bq]
    if kv_f is not None:
        st = jnp.where(kv_f[:, None] > 0, st, _NEG)
    if q0 is not None:
        st = jnp.where(_attends(st.shape, 1, q0, k0), st, _NEG)
    return st


def _probs_t(st, shift, expo: float, guard: bool):
    """exp2(st * expo - shift) over a tile of masked scores; with a key
    mask a row may attend nothing, and its masked scores then read 0
    whatever the shift is."""
    pT = jnp.exp2(st * expo - shift)
    return jnp.where(st <= _NEG / 2, 0.0, pT) if guard else pT


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, block_k: int, causal: bool,
                scale: float):
    """One (bh, g, q_block) program. q_ref [bq,d]; k [S,d]; v [S,dv];
    m_ref (1,S), the key mask, only when the caller passed one; outputs
    o [bq,dv] and lse (1,bq)."""
    *mask, o_ref, lse_ref = rest
    m_ref = mask[0] if mask else None
    block_q = q_ref.shape[0]
    s, dv = v_ref.shape
    expo = scale * _LOG2E

    # keep q/k/v in their storage dtype (bf16) for the MXU dots — f32
    # matmul inputs run at a fraction of the bf16 MXU rate; accumulation
    # stays f32 via preferred_element_type (the standard mixed scheme)
    q = q_ref[:]
    q_start = pl.program_id(2) * block_q

    m0 = jnp.full((1, block_q), _NEG, jnp.float32)
    l0 = jnp.zeros((1, block_q), jnp.float32)
    acc0 = jnp.zeros((dv, block_q), jnp.float32)

    def tile(j, carry, crossed):
        m, l, acc = carry
        k_start = j * block_k
        k_blk = k_ref[pl.ds(k_start, block_k), :]
        v_blk = v_ref[pl.ds(k_start, block_k), :]
        st = _scores_t(
            k_blk, q,
            None if m_ref is None else m_ref[0, pl.ds(k_start, block_k)],
            q_start if crossed else None, k_start)
        m_new = jnp.maximum(m, jnp.max(st, axis=0, keepdims=True))
        pT = _probs_t(st, m_new * expo, expo, m_ref is not None)
        alpha = jnp.exp2((m - m_new) * expo)               # [1, bq]
        l_new = l * alpha + jnp.sum(pT, axis=0, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            v_blk, pT.astype(v_blk.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [dv, bq]
        return m_new, l_new, acc_new

    m, l, acc = _visit(tile, (m0, l0, acc0), *_key_tiles(
        q_start, block_q, block_k, s // block_k, causal))
    l = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l).T.astype(o_ref.dtype)
    lse_ref[:] = m * scale + jnp.log(l)


def _bwd_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dl_ref, *rest,
                block_q: int, causal: bool, scale: float, n_groups: int):
    """One (bh, g, k_block): this key block against every query tile it
    meets.  The probabilities and ds are rebuilt ONCE a tile and feed
    all three gradients (five products a tile, where a dq kernel beside
    a dk/dv kernel paid seven):
      - dk [bk,d] / dv [bk,dv] are summed over the query tiles here and
        over the GQA group across grid steps: with one group they are
        this block's rows, written in k's dtype; with several the whole
        sequence stays in VMEM in float32 (g is not the innermost grid
        dimension) and the rows are added in place;
      - dq is summed over the key blocks, the innermost grid dimension,
        in the float32 scratch ``dq_acc`` [S,d], and written once, at
        the last key block."""
    *mask, dq_ref, dk_ref, dv_ref, dq_acc = rest
    block_k, d = k_ref.shape
    dv = v_ref.shape[1]
    n_q = q_ref.shape[0] // block_q
    g, kj = pl.program_id(1), pl.program_id(2)
    k_start = kj * block_k

    # bf16 MXU inputs, f32 accumulation (see _fwd_kernel note)
    k_blk = k_ref[:]
    v_blk = v_ref[:]
    kv_f = mask[0][0, pl.ds(k_start, block_k)] if mask else None  # (bk,)

    @pl.when(kj == 0)
    def _first_key_block():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def tile(i, carry, crossed):
        dk_acc, dv_acc = carry
        q_rows = pl.ds(i * block_q, block_q)
        q_blk = q_ref[q_rows, :]                           # [bq, d]
        do_blk = do_ref[q_rows, :]
        lse2 = lse_ref[0, q_rows] * _LOG2E                 # (bq,)
        delta = dl_ref[0, q_rows]
        pT = _probs_t(
            _scores_t(k_blk, q_blk, kv_f,
                      i * block_q if crossed else None, k_start),
            lse2[None, :], scale * _LOG2E, kv_f is not None)
        dv_acc = dv_acc + jax.lax.dot_general(
            pT.astype(do_blk.dtype), do_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bk, dv]
        dpT = jax.lax.dot_general(
            v_blk, do_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bk, bq]
        dsT = (pT * (dpT - delta[None, :])).astype(q_blk.dtype)
        dk_acc = dk_acc + jax.lax.dot_general(
            dsT, q_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bk, d]
        dq_acc[q_rows, :] += jax.lax.dot_general(
            dsT, k_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bq, d]
        return dk_acc, dv_acc

    init = (jnp.zeros((block_k, d), jnp.float32),
            jnp.zeros((block_k, dv), jnp.float32))
    dk, d_v = _visit(tile, init, *_query_tiles(
        k_start, block_q, block_k, n_q, causal))
    # dk_j = scale * Σ ds_ij q_i and dq_i = scale * Σ ds_ij k_j: the
    # scale is applied once, to the sums
    dk = dk * scale

    if n_groups == 1:
        dk_ref[:] = dk.astype(dk_ref.dtype)
        dv_ref[:] = d_v.astype(dv_ref.dtype)
    else:
        k_rows = pl.ds(k_start, block_k)

        @pl.when(g == 0)
        def _first_group():
            dk_ref[k_rows, :] = dk
            dv_ref[k_rows, :] = d_v

        @pl.when(g > 0)
        def _later_group():
            dk_ref[k_rows, :] += dk
            dv_ref[k_rows, :] += d_v

    @pl.when(kj == pl.num_programs(2) - 1)
    def _last_key_block():
        dq_ref[:] = (dq_acc[:] * scale).astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers over the GQA layout
#   q4 [BHkv, G, S, D], k3 [BHkv, S, D], v3 [BHkv, S, Dv], mask [B, 1, S]
# ---------------------------------------------------------------------------
def _pick_block(s, want=256):
    while s % want:
        want //= 2
    return want


# ---------------------------------------------------------------------------
# block sizes
#
# The right tile trades the elements a causal loop visits for nothing
# (a tile the diagonal crosses is computed whole: block / S of the
# causal half) against what every tile costs whatever its size (the
# loop's turn, the running max and sum, the accumulator's rescale), and
# the balance shifts with sequence length and head width.  The table
# below holds ONLY what a chip measured: forward + backward of these
# kernels timed alone on one TPU v5e over tiles of 128 to 1024, bf16, at
# the shapes the benchmark's cells run (PERF.md section 6, PR 39, has
# every timing).  Other shapes take the nearest tabled sequence of
# their (device, head_dim, causal) and finally the fixed defaults, and
# every choice is clamped by _pick_block so a bad entry can never
# produce an invalid grid.
# ---------------------------------------------------------------------------
_DEFAULT_BLOCKS = (512, 512)

# (device_kind, seq, head_dim, causal) -> (block_q, block_k); head_dim
# is the scores' width.  ms forward + backward at the entry | at the
# runner-up | at the tiles the shape had before:
_AUTOTUNE_TABLE = {
    # [96,1,2048,64], gpt3-350m: 3.21 | (1024,1024) 3.48 | (512,1024) 3.68
    ("v5e", 2048, 64, True): (512, 512),
    # [4,16,8192,128], 32 heads on 2: 27.3 | (1024,512) 28.1 | (512,512) 28.3
    ("v5e", 8192, 128, True): (1024, 1024),
    # [64,1,8192,192] on values of 128: 40.2 | (1024,512) 41.0 | (512,512) 41.2
    ("v5e", 8192, 192, True): (1024, 1024),
    # the serving prefill's buckets, [16,1,s,128], forward alone: 0.071 |
    # (1024,1024) 0.074 | (256,512) 0.119; under 1024 one tile wins
    ("v5e", 1024, 128, True): (512, 512),
    ("v5e", 512, 128, True): (512, 512),
    ("v5e", 256, 128, True): (256, 256),
    ("v5e", 128, 128, True): (128, 128),
}

# (what a device's kind holds, the table's name for it).  The table is
# keyed by the short names; a chip gives the long one ("TPU v5 lite"),
# and a miss here sends every tabled shape to _DEFAULT_BLOCKS without a
# word.
_KIND_ALIASES = (("v5 lite", "v5e"), ("v5litepod", "v5e"),
                 ("v5e", "v5e"), ("v5p", "v5p"),
                 ("v6 lite", "v6e"), ("v6e", "v6e"),
                 ("v4", "v4"), ("v3", "v3"), ("v2", "v2"))


def _normalize_kind(kind: str) -> str:
    """Canonical short device kind ('TPU v5 lite' -> 'v5e', ...)."""
    k = (kind or "").lower()
    for alias, canon in _KIND_ALIASES:
        if alias in k:
            return canon
    return k


def _device_kind() -> str:
    """Normalized kind of the local default device ('' when unknown)."""
    try:
        return _normalize_kind(getattr(jax.devices()[0], "device_kind", ""))
    except Exception:  # pragma: no cover
        return ""


def get_block_sizes(seq: int, head_dim: int, causal: bool,
                    device_kind: str | None = None):
    """(block_q, block_k) for this shape: the table's entry for the
    nearest tabled seq (the shape's own, where it is tabled) of the same
    kind, width and causality, else the fixed defaults. Always clamped
    to divide seq."""
    kind = _normalize_kind(device_kind) if device_kind is not None \
        else _device_kind()
    near = [(s, v) for (k, s, d, c), v in _AUTOTUNE_TABLE.items()
            if k == kind and d == head_dim and c == bool(causal)]
    if near:
        _, (bq, bk) = min(near, key=lambda sv: abs(sv[0] - seq))
    else:
        bq, bk = _DEFAULT_BLOCKS
    return _pick_block(seq, bq), _pick_block(seq, bk)


_SCOPED_VMEM = 16 * 2 ** 20     # what a kernel may use unless it asks


def _strip_room(s: int, d: int, dv: int, itemsize: int,
                backward_groups: int = 0) -> dict:
    """Every kernel keeps whole strips of the sequence in VMEM, each
    double-buffered and each width rounded up to whole lane tiles: the
    forward k and v; the backward q and do, dq as it goes out and as its
    float32 sum, and with several groups dk and dv in float32 too.  Up
    to half the compiler's own budget they fit beside the blocks and the
    score tile, and the call is built as it always was; past that it
    asks for the strips and that budget on top."""
    lanes = lambda w: -(-w // 128) * 128
    strips = 2 * s * (lanes(d) + lanes(dv)) * itemsize
    if backward_groups:
        strips += s * lanes(d) * (2 * itemsize + 4)
        if backward_groups > 1:
            strips += 2 * s * (lanes(d) + lanes(dv)) * 4
    if strips <= _SCOPED_VMEM // 2:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=strips + _SCOPED_VMEM)}


def _mask_spec(mask, bhkv: int, s: int):
    """The key mask's operand and BlockSpec, for either kernel's grid
    (its first index is the kv head's): a one-element list each, or
    empty ones when the caller passed no mask."""
    if mask is None:
        return [], []
    hkv = bhkv // mask.shape[0]
    return [mask], [pl.BlockSpec((None, 1, s),
                                 lambda b, i, j: (b // hkv, 0, 0))]


def _fwd_gqa(q4, k3, v3, mask, causal, block_q=512, block_k=512):
    bhkv, g, s, d = q4.shape
    dv = v3.shape[2]
    block_q = _pick_block(s, block_q)
    block_k = _pick_block(s, block_k)
    scale = 1.0 / math.sqrt(d)
    grid = (bhkv, g, s // block_q)
    mask, mask_spec = _mask_spec(mask, bhkv, s)
    kernel = functools.partial(_fwd_kernel, block_k=block_k,
                               causal=causal, scale=scale)
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda b, gi, i: (b, gi, i, 0)),
            pl.BlockSpec((None, s, d), lambda b, gi, i: (b, 0, 0)),
            pl.BlockSpec((None, s, dv), lambda b, gi, i: (b, 0, 0)),
            *mask_spec,
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, dv),
                         lambda b, gi, i: (b, gi, i, 0)),
            pl.BlockSpec((None, None, 1, block_q),
                         lambda b, gi, i: (b, gi, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bhkv, g, s, dv), q4.dtype),
            jax.ShapeDtypeStruct((bhkv, g, 1, s), jnp.float32),
        ],
        interpret=_INTERPRET,
        **_strip_room(s, d, dv, q4.dtype.itemsize),
    )
    return run_kernel(q4.dtype, call, q4, k3, v3, *mask)


def _bwd_gqa(q4, k3, v3, mask, o4, lse, do4, causal,
             block_q=512, block_k=512):
    bhkv, g, s, d = q4.shape
    dv = v3.shape[2]
    block_q = _pick_block(s, block_q)
    block_k = _pick_block(s, block_k)
    mask, mask_spec = _mask_spec(mask, bhkv, s)
    # delta_i = do_i · o_i — one fused XLA rowsum, O(S·D)
    delta = jnp.sum(do4.astype(jnp.float32) * o4.astype(jnp.float32),
                    axis=-1)[:, :, None, :]                # [BHkv,G,1,S]

    kernel = functools.partial(
        _bwd_kernel, block_q=block_q, causal=causal,
        scale=1.0 / math.sqrt(d), n_groups=g)
    strip = lambda w: pl.BlockSpec((None, None, s, w),
                                   lambda b, gi, j: (b, gi, 0, 0))
    row = pl.BlockSpec((None, None, 1, s), lambda b, gi, j: (b, gi, 0, 0))
    # dk and dv: a key block's rows, or with several groups the whole
    # sequence in float32, resident while the groups add into it
    if g == 1:
        summed = lambda w: pl.BlockSpec((None, block_k, w),
                                        lambda b, gi, j: (b, j, 0))
        summed_dtype = k3.dtype
    else:
        summed = lambda w: pl.BlockSpec((None, s, w),
                                        lambda b, gi, j: (b, 0, 0))
        summed_dtype = jnp.float32
    call = pl.pallas_call(
        kernel,
        grid=(bhkv, g, s // block_k),   # key blocks innermost: dq sums
        in_specs=[
            pl.BlockSpec((None, block_k, d),
                         lambda b, gi, j: (b, j, 0)),       # k
            pl.BlockSpec((None, block_k, dv),
                         lambda b, gi, j: (b, j, 0)),       # v
            strip(d),                                       # q (one group)
            strip(dv),                                      # do
            row,                                            # lse
            row,                                            # delta
            *mask_spec,
        ],
        out_specs=[strip(d), summed(d), summed(dv)],
        out_shape=[
            jax.ShapeDtypeStruct((bhkv, g, s, d), q4.dtype),
            jax.ShapeDtypeStruct((bhkv, s, d), summed_dtype),
            jax.ShapeDtypeStruct((bhkv, s, dv), summed_dtype),
        ],
        scratch_shapes=[pltpu.VMEM((s, d), jnp.float32)],
        interpret=_INTERPRET,
        **_strip_room(s, d, dv, q4.dtype.itemsize, backward_groups=g),
    )
    dq, dk, d_v = run_kernel(q4.dtype, call, k3, v3, q4, do4, lse, delta,
                             *mask)
    return dq, dk.astype(k3.dtype), d_v.astype(v3.dtype)


# ---------------------------------------------------------------------------
# layout shuffles [B,S,H,D] <-> GQA grid layout
# ---------------------------------------------------------------------------
def _to_gqa_q(x, hkv):
    """[B, S, H, W] -> [B*Hkv, G, S, W]: q, and what has q's heads (the
    output, its gradient)."""
    b, s, h, w = x.shape
    # q head index = hk * g + gi (repeat_interleave convention)
    return jnp.swapaxes(x, 1, 2).reshape(b * hkv, h // hkv, s, w)


def _to_gqa(q, k, v):
    b, s, _, d = q.shape
    hkv = k.shape[2]
    q4 = _to_gqa_q(q, hkv)
    k3 = jnp.swapaxes(k, 1, 2).reshape(b * hkv, s, d)
    v3 = jnp.swapaxes(v, 1, 2).reshape(b * hkv, s, v.shape[3])
    return q4, k3, v3


def _from_gqa_q(o4, b, s, h, d):
    return jnp.swapaxes(o4.reshape(b, h, s, d), 1, 2)


def _composite(q, k, v, causal, kv_mask=None):
    """XLA reference math on [B,S,H,D] (k/v may have fewer heads, v
    another width)."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    if hkv != h:
        k = jnp.repeat(k, h // hkv, axis=2)
        v = jnp.repeat(v, h // hkv, axis=2)
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    if causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(mask, scores, _NEG)
    if kv_mask is not None:
        scores = jnp.where(kv_mask[:, None, None, :] > 0, scores, _NEG)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    # fully-masked rows: softmax over all-_NEG scores is uniform, but the
    # Pallas kernel emits exact zeros there (l -> 0 guard) — zero them so
    # kernel and composite agree bit-for-bit in convention
    probs = jnp.where(
        jnp.max(scores, axis=-1, keepdims=True) <= _NEG / 2,
        jnp.zeros_like(probs), probs)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _flash(q, k, v, mask, causal):
    return _flash_fwd_impl(q, k, v, mask, causal)[0]


def _flash_fwd_impl(q, k, v, mask, causal):
    """(the output [B, S, H, Dv], the kernel's lse [B*Hkv, G, 1, S])."""
    b, s, h, d = q.shape
    q4, k3, v3 = _to_gqa(q, k, v)
    bq, bk = get_block_sizes(s, d, causal)
    o4, lse = _fwd_gqa(q4, k3, v3, mask, causal, block_q=bq, block_k=bk)
    return _from_gqa_q(o4, b, s, h, v.shape[3]), lse


def _flash_fwd(q, k, v, mask, causal):
    # the forward rule alone names what the backward needs from the
    # kernel, so that a remat policy can keep it (RESIDUAL_NAMES); the
    # output is kept as the caller gets it, [B, S, H, Dv]: stacked by a
    # layer scan in the kernel's own [B*Hkv, G, S, 64] it pays a relayout
    o, lse = _flash_fwd_impl(q, k, v, mask, causal)
    o = checkpoint_name(o, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse, RESIDUAL_NAMES[1])
    return o, (q, k, v, mask, o, lse)


def _flash_bwd(causal, res, g_out):
    q, k, v, mask, o, lse = res
    b, s, h, d = q.shape
    hkv, dv = k.shape[2], v.shape[3]
    q4, k3, v3 = _to_gqa(q, k, v)
    o4, do4 = _to_gqa_q(o, hkv), _to_gqa_q(g_out, hkv)
    bq, bk = get_block_sizes(s, d, causal)
    dq4, dk3, dv3 = _bwd_gqa(q4, k3, v3, mask, o4, lse, do4, causal,
                             block_q=bq, block_k=bk)
    dq = _from_gqa_q(dq4, b, s, h, d).astype(q.dtype)
    dk = jnp.swapaxes(dk3.reshape(b, hkv, s, d), 1, 2)
    d_v = jnp.swapaxes(dv3.reshape(b, hkv, s, dv), 1, 2)
    return dq, dk, d_v, None if mask is None else jnp.zeros_like(mask)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _width_served(d: int) -> bool:
    """A head width the kernels take: whole lane tiles, 64, or 192 (a
    tile and a half: latent attention's 128 + 64 score channels)."""
    return d % 128 == 0 or d in (64, 192)


def flash_attention(q, k, v, causal=False, kv_mask=None):
    """q [B,S,H,D]; k [B,S,Hkv,D]; v [B,S,Hkv,Dv] (GQA native — no head
    expansion; Dv may differ from D, the output is [B,S,H,Dv]); kv_mask
    optional [B,S] (1 = key attended, 0 = padding). Pallas fused fwd+bwd
    when shapes allow, XLA composite otherwise."""
    b, s, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    supported = (s == sk and s % 128 == 0 and _width_served(d)
                 and _width_served(v.shape[3]) and h % hkv == 0)
    if not supported or not flash_attention_available():
        kernel_paths.note_composite("flash_attention", supported)
        return _composite(q, k, v, causal, kv_mask)
    kernel_paths.note("flash_attention", "kernel")
    # which body was built: the key mask is a static choice, like causal
    kernel_paths.note("flash_attention.key_mask" if kv_mask is not None
                      else "flash_attention.no_key_mask", "kernel")
    mask = None if kv_mask is None \
        else kv_mask.reshape(b, 1, s).astype(jnp.float32)
    part = _mesh_partition(b, h, hkv)
    if part is None:
        return _flash(q, k, v, mask, causal)
    where, qkv_spec, mask_spec = part
    from ..distributed.mesh import shard_map
    return shard_map(
        lambda q, k, v, *mask: _flash(q, k, v, *(mask or (None,)), causal),
        in_specs=(qkv_spec,) * 3 + (mask_spec,) * (mask is not None),
        out_specs=qkv_spec, check_vma=False, **where)(
            q, k, v, *(() if mask is None else (mask,)))


def _mesh_partition(b: int, h: int, hkv: int):
    """(shard_map mesh arguments, q/k/v spec, mask spec) when a compiled
    trainer is tracing its step over a multi-device mesh, else None.
    The Pallas calls are custom calls GSPMD cannot partition ("Mosaic
    kernels cannot be automatically partitioned"), and attention is
    independent per sequence and per head: shard_map them — batch over
    the data axes, heads over 'tp' — with no collectives.  Axes that
    divide neither stay replicated (every device of that axis computes
    the same).

    A caller already inside a shard_map body (the overlapped ZeRO-3
    scan, the pipeline schedules) holds per-shard operands: the axes
    that body made Manual are left alone, and only the still-automatic
    ones are mapped, through the context mesh shard_map insists on
    there.  With every axis Manual the kernel is called as it is."""
    from jax.sharding import PartitionSpec as P
    from ..distributed.mesh import get_compile_mesh
    mesh = get_compile_mesh()
    if mesh is None or mesh.size == 1:
        return None
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    auto = [ax for ax in mesh.axis_names if ax not in manual]
    if not auto:
        return None
    data = tuple(ax for ax in ("dcn", "dp")
                 if ax in auto and mesh.shape[ax] > 1)
    if data and b % math.prod(mesh.shape[ax] for ax in data):
        data = ()
    tp = mesh.shape["tp"] if "tp" in auto else 1
    heads = "tp" if tp > 1 and h % tp == 0 and hkv % tp == 0 else None
    where = {"axis_names": frozenset(auto)} if manual else {"mesh": mesh}
    return (where, P(data or None, None, heads, None),
            P(data or None, None, None))
