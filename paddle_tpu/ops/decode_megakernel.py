"""Fused Pallas megakernel for ONE GPT layer decode step.

The decode hot loop (inference.engine) spends each layer step on a chain
of small ops — LayerNorm, qkv projection, cache write, fused attention,
output projection, residual, LayerNorm, MLP up/gelu/down, residual —
and between every pair the [B, H] activations round-trip HBM and XLA
pays a dispatch.  Decode is bandwidth-bound: the useful bytes per layer
step are the layer's parameters (streamed once) and the KV cache strips
(streamed once per slot); everything else is overhead.  This module
fuses the WHOLE layer step into one Pallas kernel — the TPU analogue of
the reference framework fusing per-op dispatch away in its kernel layer
(PAPER.md §1 layers 2-3):

    grid (ns + 1 + nf, B)   # phases outer, slots inner

    phase p == 0        ln_1(x) -> qkv projection -> split q / k_new /
                        v_new into VMEM scratch, init online softmax
    phase p <  ns       stream KV block p of slot b ([block_s, Hkv, D]
                        strips; int8 blocks dequantized IN VMEM after
                        the DMA), online-softmax update for all heads
    phase p == ns       fold the NEW token's k/v (never written to HBM
                        first — it lives in scratch), finalize softmax,
                        output projection, residual, ln_2 into scratch
    phase p >  ns       MLP tile t = p-ns-1: gelu(h2 @ up_t + b_t) @
                        down_t accumulated in scratch; the last tile
                        adds the residual and writes x_out / k_new /
                        v_new back to HBM

With slots innermost, a weight tile is fetched ONCE and reused by every
slot before the phase advances, and each slot's KV blocks stream exactly
once; the only HBM writes of the whole layer step are x_out [B, H] and
the new token's k/v [B, Hkv, D] (the caller scatters those into the
cache, exactly like the composed path).  All intermediates — q, the new
k/v, the online-softmax state, the post-attention residual — live in
VMEM scratch for the kernel's lifetime.

Two layouts, mirroring ops.decode_attention:

- :func:`decode_layer_step` — Static (dense) cache ``[B, cap, Hkv, D]``
  streamed strip by strip, lengths via scalar prefetch.
- :func:`decode_layer_step_paged` — Paged block pool
  ``[NB, Hkv, bs, D]`` streamed through the slot's block table, the
  same scalar-prefetch indirection as ``paged_decode_attention`` (MLP
  phases pin the KV index map to the null block so no stray re-fetch
  rides the weight tiles).

Both accept int8 caches with per-(position, head) f32 scale strips and
dequantize inside the block loop.  The XLA composite (`quantize=` also
routes here — its projections then run ops.quantized_matmul with int8
qmm tiles from the unified tuning table) reproduces the COMPOSED
kernels path op for op, which makes the composed engine the parity
oracle: on CPU the two lower to the same XLA ops and agree bitwise; the
Pallas kernel is tested against it in interpret mode at 1e-5.

Tensor-parallel serving (ISSUE 18): the megakernel STANDS DOWN under
tp>1.  Its whole-layer fusion assumes every projection's full weight is
resident in one kernel's VMEM plan, which contradicts the tp layout
(qkv/up column-split, out/down row-split with a psum between) — the
per-head shard_map treatment that works for the attention-only decode
kernels (ops.decode_attention) cannot cover the row-split matmuls
without growing collectives inside the kernel.  ``gpt.
_megakernel_active`` checks the live mesh and keeps the composed GSPMD
path whenever the tp axis has extent > 1; ``engine.stats
["decode_megakernel"]`` reports what actually runs, so an armed knob
that stood down is visible, not silent.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

import importlib

from . import kernel_paths

# the package __init__ rebinds sibling names to public functions; fetch
# the modules themselves (their _INTERPRET flags are live state)
_fa = importlib.import_module(__package__ + ".flash_attention")
_da = importlib.import_module(__package__ + ".decode_attention")

__all__ = ["decode_layer_step", "decode_layer_step_paged",
           "decode_megakernel_available", "megakernel_enabled",
           "set_interpret_mode", "LAYER_WEIGHTS"]

_NEG = -1e30
_STATE = {"interpret": None}  # None = follow flash_attention's flag

# the 12 per-layer arrays a fused step consumes, in argument order
LAYER_WEIGHTS = ("ln1_w", "ln1_b", "w_qkv", "b_qkv", "w_out", "b_out",
                 "ln2_w", "ln2_b", "w_up", "b_up", "w_down", "b_down")

# conservative VMEM budget for the fused kernel's resident blocks
# (~16MB/core on v5e; leave headroom for Mosaic's own allocations and
# double buffering of the streamed operands, which the estimate below
# already counts at 2x)
_VMEM_BUDGET = int(os.environ.get("PADDLE_TPU_MEGAKERNEL_VMEM",
                                  14 * 2**20))


def set_interpret_mode(flag):
    """True/False force interpret mode; None follows
    flash_attention.set_interpret_mode (one test switch for all
    kernels)."""
    _fa.check_interpret_allowed(flag)
    _STATE["interpret"] = flag


def _interpret() -> bool:
    if _STATE["interpret"] is not None:
        return bool(_STATE["interpret"])
    return _fa._INTERPRET


def decode_megakernel_available() -> bool:
    """Pallas fused path available (needs scalar prefetch, same surface
    as the paged decode kernel)."""
    return _interpret() or jax.default_backend() == "tpu"


def megakernel_enabled(cfg) -> bool:
    """The serving knob: PADDLE_TPU_DECODE_MEGAKERNEL overrides (any
    value but "0" arms it), else ``cfg.decode_megakernel``.  Read at
    trace time — the engine compiles its decode executable once per
    process, so the flag is process-stable by construction."""
    env = os.environ.get("PADDLE_TPU_DECODE_MEGAKERNEL")
    if env is not None:
        return env != "0"
    return bool(getattr(cfg, "decode_megakernel", False))


def _pick_blocks(seq_extent: int, ffn: int, qkv_cols: int = 0,
                 h: int = 0):
    """(block_s, block_f, block_q, block_o) for the KV stream / MLP
    tiles / qkv-projection column tiles / out-projection row tiles; env
    PADDLE_TPU_MEGAKERNEL_BLOCKS="s,f[,q,o]" overrides, clamped to
    divide.  Tiling the qkv/out weight fetches (instead of keeping both
    matrices resident) is what lets gpt3-350m-class layers fit the VMEM
    gate — a tile is fetched once per phase with slots innermost, so
    the HBM traffic is unchanged."""
    env = os.environ.get("PADDLE_TPU_MEGAKERNEL_BLOCKS", "").strip()
    want_s, want_f, want_q, want_o = 512, 256, 512, 512
    if env:
        try:
            parts = [int(x) for x in env.split(",")]
            if len(parts) >= 2:
                want_s, want_f = parts[0], parts[1]
            if len(parts) >= 4:
                want_q, want_o = parts[2], parts[3]
        except ValueError:
            pass
    return (_fa._pick_block(seq_extent, want_s),
            _fa._pick_block(ffn, want_f),
            _fa._pick_block(qkv_cols, want_q) if qkv_cols else 0,
            _fa._pick_block(h, want_o) if h else 0)


def _vmem_estimate(h, kvd, f, block_s, block_f, block_q, block_o, hkv,
                   d, w_item, kv_item, quantized, batch):
    """Rough resident-VMEM bytes: streamed operands counted at 2x
    (double buffering) — which, after the qkv/out tiling, is EVERY
    weight matrix; only the LayerNorm/bias vectors stay resident —
    plus the per-slot scratch."""
    resident = 8 * h * w_item                    # ln1/ln2 w+b, bout, bdown
    streamed = 2 * (h * block_q + block_q) * w_item          # qkv tile
    streamed += 2 * block_o * h * w_item                     # out tile
    streamed += 2 * (h * block_f + block_f + block_f * h) * w_item  # mlp
    streamed += 2 * 2 * block_s * hkv * d * kv_item          # k+v strips
    if quantized:
        streamed += 2 * 2 * block_s * hkv * 4                # scale strips
    qkv_cols = h + 2 * kvd
    heads = h // d
    scratch = batch * (qkv_cols + 5 * h + heads * d) * 4 \
        + batch * 2 * heads * 128 * 4
    return resident + streamed + scratch


def _gelu_tanh(x):
    # jax.nn.gelu(approximate=True): the tanh form the composed GPTMLP
    # uses — the kernel must match it, not erf gelu
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x * x * x)))


# ---------------------------------------------------------------------------
# the fused kernel (shared body; dense and paged differ only in how KV
# blocks are addressed, which the BlockSpec index maps own)
# ---------------------------------------------------------------------------
def _mega_kernel(len_ref, x_ref, ln1w_ref, ln1b_ref, wqkv_ref, bqkv_ref,
                 wout_ref, bout_ref, ln2w_ref, ln2b_ref, wup_ref, bup_ref,
                 wdown_ref, bdown_ref, k_ref, v_ref, ks_ref, vs_ref,
                 xo_ref, kn_ref, vn_ref,
                 qkv_scr, m_scr, l_scr, acc_scr,
                 attn_scr, o_scr, x2_scr, h2_scr, mlp_scr,
                 *, nq: int, ns: int, no: int, nf: int, block_s: int,
                 block_q: int, block_o: int, heads: int, hkv: int,
                 d: int, h: int, scale: float, eps: float, cap: int,
                 quantized: bool, paged: bool):
    """One (phase, slot) program.  Scalar-prefetched ``len_ref`` carries
    per-slot lengths (EXCLUDING the new token, engine convention); for
    the paged layout the block table already acted inside the index
    maps, so the body sees one head-major ``[Hkv, block_s, D]`` strip
    per phase either way (the dense layer and the block pool share
    that layout).
    ``ks_ref``/``vs_ref`` are the f32 scale strips of an int8 cache
    (aliases of k_ref/v_ref in the fp path, unread).

    Phase layout (nq qkv column tiles, ns KV blocks, 1 softmax
    finalize, no out-proj row tiles, nf MLP tiles — every weight
    matrix STREAMS tile by tile, the widened-VMEM-gate satellite):

        [0, nq)                qkv tile t = p: ln1(x) recomputed (one
                               [1,H] VPU pass per tile — noise), one
                               [H, block_q] weight tile, result into
                               the qkv scratch column slice
        [nq, nq+ns)            KV block j = p-nq, online softmax
        nq+ns                  fold new token, finalize -> attn scratch
        (nq+ns, nq+ns+no]      out-proj row tile t accumulates into the
                               o scratch; the LAST tile adds residual +
                               bias and runs ln2
        (nq+ns+no, +nf]        MLP tiles; the last one also writes"""
    p = pl.program_id(0)
    b = pl.program_id(1)
    g = heads // hkv
    kvd = hkv * d
    bsl = pl.ds(b, 1)

    # the slot's logical write position for the new token: the composed
    # path clamps to cap-1 (dense) so the mask must clamp identically
    length = len_ref[b]
    idx = jnp.minimum(length, cap - 1) if not paged else length

    @pl.when(p < nq)
    def _qkv_tile():
        xb = x_ref[...].astype(jnp.float32)               # [1, H]
        mu = jnp.mean(xb, axis=-1, keepdims=True)
        var = jnp.mean((xb - mu) ** 2, axis=-1, keepdims=True)
        h1 = (xb - mu) * jax.lax.rsqrt(var + eps)
        h1 = h1 * ln1w_ref[...].astype(jnp.float32) + \
            ln1b_ref[...].astype(jnp.float32)
        tile = jax.lax.dot_general(
            h1.astype(wqkv_ref.dtype), wqkv_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) + \
            bqkv_ref[...].astype(jnp.float32)             # [1, block_q]
        qkv_scr[bsl, pl.ds(p * block_q, block_q)] = tile

    @pl.when(p == nq - 1)
    def _attend_init():
        m_scr[bsl] = jnp.full((1,) + m_scr.shape[1:], _NEG, jnp.float32)
        l_scr[bsl] = jnp.zeros((1,) + l_scr.shape[1:], jnp.float32)
        acc_scr[bsl] = jnp.zeros((1, heads, d), jnp.float32)

    @pl.when((p >= nq) & (p < nq + ns))
    def _attend():
        q = qkv_scr[bsl, :h].reshape(heads, d)            # [heads, d] f32
        pos = (p - nq) * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1)
        valid = pos < idx                                 # [1, block_s]
        scores, vals = [], []
        for hk in range(hkv):
            kh, vh = k_ref[hk], v_ref[hk]                 # [block_s, d]
            if quantized:
                kh = kh.astype(jnp.float32) * ks_ref[hk][:, None]
                vh = vh.astype(jnp.float32) * vs_ref[hk][:, None]
            qg = q[hk * g:(hk + 1) * g].astype(kh.dtype)  # [g, d]
            scores.append(jax.lax.dot_general(
                qg, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))      # [g, block_s]
            vals.append(vh)
        sblk = jnp.concatenate(scores, axis=0) * scale    # [heads, bs]
        sblk = jnp.where(valid, sblk, _NEG)
        m_prev = m_scr[bsl][0][:, :1]                     # [heads, 1]
        l_prev = l_scr[bsl][0][:, :1]
        acc_prev = acc_scr[bsl][0]                        # [heads, d]
        m_new = jnp.maximum(m_prev, jnp.max(sblk, axis=1, keepdims=True))
        pmat = jnp.exp(sblk - m_new)
        pmat = jnp.where(sblk <= _NEG / 2, 0.0, pmat)     # fully masked
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(pmat, axis=1, keepdims=True)
        accs = [jax.lax.dot_general(
            pmat[hk * g:(hk + 1) * g].astype(vals[hk].dtype), vals[hk],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) for hk in range(hkv)]
        acc_new = acc_prev * alpha + jnp.concatenate(accs, axis=0)
        m_scr[bsl] = jnp.broadcast_to(m_new[None, :, :],
                                      (1,) + m_scr.shape[1:])
        l_scr[bsl] = jnp.broadcast_to(l_new[None, :, :],
                                      (1,) + l_scr.shape[1:])
        acc_scr[bsl] = acc_new[None]

    @pl.when(p == nq + ns)
    def _finalize():
        q = qkv_scr[bsl, :h].reshape(heads, d)            # [heads, d]
        kn = qkv_scr[bsl, h:h + kvd].reshape(hkv, d)      # [hkv, d] f32
        vn = qkv_scr[bsl, h + kvd:].reshape(hkv, d)
        if quantized:
            # the composed path STORES the new k/v quantized and attends
            # the dequantized codes; reproduce that round trip exactly
            kamax = jnp.maximum(jnp.max(jnp.abs(kn), axis=-1,
                                        keepdims=True), 1e-8)
            vamax = jnp.maximum(jnp.max(jnp.abs(vn), axis=-1,
                                        keepdims=True), 1e-8)
            ksc, vsc = kamax / 127.0, vamax / 127.0
            kn = jnp.clip(jnp.round(kn / ksc), -127.0, 127.0) * ksc
            vn = jnp.clip(jnp.round(vn / vsc), -127.0, 127.0) * vsc
        kn_rep = jnp.repeat(kn, g, axis=0)                # [heads, d]
        vn_rep = jnp.repeat(vn, g, axis=0)
        s_new = jnp.sum(q * kn_rep, axis=-1,
                        keepdims=True) * scale            # [heads, 1]
        m_prev = m_scr[bsl][0][:, :1]
        l_prev = l_scr[bsl][0][:, :1]
        acc_prev = acc_scr[bsl][0]
        m_new = jnp.maximum(m_prev, s_new)
        pnew = jnp.exp(s_new - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + pnew
        acc = acc_prev * alpha + pnew * vn_rep
        attn = acc / jnp.maximum(l_new, 1e-30)            # [heads, d]
        attn_scr[bsl] = attn.reshape(1, 1, h)
        o_scr[bsl] = jnp.zeros((1, 1, h), jnp.float32)

    @pl.when((p > nq + ns) & (p <= nq + ns + no))
    def _out_tile():
        t = p - nq - ns - 1
        attn_t = attn_scr[bsl, :, pl.ds(t * block_o, block_o)] \
            .reshape(1, block_o)
        part = jax.lax.dot_general(
            attn_t.astype(wout_ref.dtype), wout_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [1, H]
        o_scr[bsl] = o_scr[bsl] + part[None]

    @pl.when(p == nq + ns + no)
    def _residual_ln2():
        # the LAST out-proj tile just accumulated above (source order);
        # close the attention half: bias + residual + ln2
        o = o_scr[bsl][0] + bout_ref[...].astype(jnp.float32)
        x2 = x_ref[...].astype(jnp.float32) + o
        mu = jnp.mean(x2, axis=-1, keepdims=True)
        var = jnp.mean((x2 - mu) ** 2, axis=-1, keepdims=True)
        h2 = (x2 - mu) * jax.lax.rsqrt(var + eps)
        h2 = h2 * ln2w_ref[...].astype(jnp.float32) + \
            ln2b_ref[...].astype(jnp.float32)
        x2_scr[bsl] = x2[None]
        h2_scr[bsl] = h2[None]
        mlp_scr[bsl] = jnp.zeros((1, 1, h), jnp.float32)

    @pl.when(p > nq + ns + no)
    def _mlp():
        h2 = h2_scr[bsl][0]                               # [1, H] f32
        u = jax.lax.dot_general(
            h2.astype(wup_ref.dtype), wup_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) + \
            bup_ref[...].astype(jnp.float32)              # [1, block_f]
        act = _gelu_tanh(u)
        part = jax.lax.dot_general(
            act.astype(wdown_ref.dtype), wdown_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [1, H]
        mlp_scr[bsl] = mlp_scr[bsl] + part[None]

    @pl.when(p == nq + ns + no + nf)
    def _write():
        # the LAST visit of slot b's output blocks: earlier phases flush
        # whatever the buffers held, but this write lands last and wins.
        # k_new/v_new leave RAW (pre-quantization) — the caller owns the
        # cache write, exactly like the composed path
        xo_ref[...] = (x2_scr[bsl][0] + mlp_scr[bsl][0] +
                       bdown_ref[...].astype(jnp.float32)
                       ).astype(xo_ref.dtype)
        kn_ref[...] = qkv_scr[bsl, h:h + kvd].reshape(
            1, hkv, d)[0].astype(kn_ref.dtype)
        vn_ref[...] = qkv_scr[bsl, h + kvd:].reshape(
            1, hkv, d)[0].astype(vn_ref.dtype)


def _run_mega(x, w, k_src, v_src, ks_src, vs_src, lengths, *, ns, cap,
              eps, quantized, paged, kv_map_factory, sc_map_factory,
              extra_scalars=()):
    """Shared pallas_call wrapper: builds grid/specs around the kernel
    body.  ``kv_map_factory``/``sc_map_factory`` take the qkv-tile
    phase count ``nq`` (the KV phases start at ``nq``) and return the
    layout's index map (dense strip walk vs paged table indirection)."""
    pltpu = _fa.pltpu
    (ln1_w, ln1_b, w_qkv, b_qkv, w_out, b_out,
     ln2_w, ln2_b, w_up, b_up, w_down, b_down) = w
    bsz, h = x.shape
    hkv, d = k_src.shape[1], k_src.shape[3]
    kvd = hkv * d
    # q width is the qkv columns minus the two kv blocks; head count
    # from the cache head_dim
    heads = (w_qkv.shape[1] - 2 * kvd) // d
    f = w_up.shape[1]
    qkv_cols = h + 2 * kvd
    if paged:
        block_s = k_src.shape[2]          # one pool block per phase
        _, block_f, block_q, block_o = _pick_blocks(block_s, f,
                                                    qkv_cols, h)
    else:
        block_s, block_f, block_q, block_o = _pick_blocks(
            k_src.shape[2], f, qkv_cols, h)
    nq = qkv_cols // block_q
    no = h // block_o
    nf = f // block_f
    np_total = nq + ns + 1 + no + nf
    scale = 1.0 / math.sqrt(d)
    kv_index_map = kv_map_factory(nq)
    sc_index_map = sc_map_factory(nq)

    def vec2(a):
        return a.reshape(1, -1)

    n_scal = 1 + len(extra_scalars)
    # weight specs: every matrix streams tile by tile — qkv columns
    # during the leading phases, out rows after the softmax finalize,
    # up/down during the MLP phases; only the LN/bias vectors keep a
    # constant block index (one fetch, resident)
    def _const(shape):
        return pl.BlockSpec(shape, lambda p, b, *s: (0,) * len(shape))

    def _tile_qkv(p, b, *s):
        return (0, jnp.clip(p, 0, nq - 1))

    def _tile_out(p, b, *s):
        return (jnp.clip(p - nq - ns - 1, 0, no - 1), 0)

    def _tile_up(p, b, *s):
        return (0, jnp.clip(p - nq - ns - no - 1, 0, nf - 1))

    def _tile_down(p, b, *s):
        return (jnp.clip(p - nq - ns - no - 1, 0, nf - 1), 0)

    kv_block = (None, hkv, block_s, d)
    sc_block = kv_block[:-1]
    if quantized:
        sc_spec = pl.BlockSpec(sc_block, sc_index_map)
    else:
        # unread placeholder: one block pinned at index 0, fetched once
        sc_spec = pl.BlockSpec(sc_block, lambda p, b, *s: (0, 0, 0))
    in_specs = [
        pl.BlockSpec((None, 1, h), lambda p, b, *s: (b, 0, 0)),  # x
        _const((1, h)), _const((1, h)),                         # ln1 w/b
        pl.BlockSpec((h, block_q), _tile_qkv),                  # qkv w
        pl.BlockSpec((1, block_q), _tile_qkv),                  # qkv b
        pl.BlockSpec((block_o, h), _tile_out),                  # out w
        _const((1, h)),                                         # out b
        _const((1, h)), _const((1, h)),                         # ln2 w/b
        pl.BlockSpec((h, block_f), _tile_up),                   # up w
        pl.BlockSpec((1, block_f), _tile_up),                   # up b
        pl.BlockSpec((block_f, h), _tile_down),                 # down w
        _const((1, h)),                                         # down b
        pl.BlockSpec(kv_block, kv_index_map),                   # k
        pl.BlockSpec(kv_block, kv_index_map),                   # v
        sc_spec,                                                # k scale
        sc_spec,                                                # v scale
    ]
    out_specs = [
        pl.BlockSpec((None, 1, h), lambda p, b, *s: (b, 0, 0)),
        pl.BlockSpec((None, hkv, d), lambda p, b, *s: (b, 0, 0)),
        pl.BlockSpec((None, hkv, d), lambda p, b, *s: (b, 0, 0)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_scal,
        grid=(np_total, bsz),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((bsz, qkv_cols), jnp.float32),    # qkv (q|k|v new)
            pltpu.VMEM((bsz, heads, 128), jnp.float32),  # running max
            pltpu.VMEM((bsz, heads, 128), jnp.float32),  # running denom
            pltpu.VMEM((bsz, heads, d), jnp.float32),    # attn accum
            pltpu.VMEM((bsz, 1, h), jnp.float32),        # attn out
            pltpu.VMEM((bsz, 1, h), jnp.float32),        # out-proj accum
            pltpu.VMEM((bsz, 1, h), jnp.float32),        # x2 residual
            pltpu.VMEM((bsz, 1, h), jnp.float32),        # ln2 output
            pltpu.VMEM((bsz, 1, h), jnp.float32),        # mlp accum
        ],
    )
    kernel = functools.partial(
        _mega_kernel, nq=nq, ns=ns, no=no, nf=nf, block_s=block_s,
        block_q=block_q, block_o=block_o, heads=heads,
        hkv=hkv, d=d, h=h, scale=scale, eps=eps, quantized=quantized,
        paged=paged, cap=cap)
    n_extra = len(extra_scalars)
    if n_extra:
        # the body only consumes lengths; extra scalar refs (the paged
        # block table) act entirely inside the BlockSpec index maps
        body = lambda *a: kernel(*a[n_extra:])   # noqa: E731
    else:
        body = kernel
    if quantized:
        ks_in, vs_in = (ks_src.astype(jnp.float32),
                        vs_src.astype(jnp.float32))
    else:
        # unread by the kernel; one-block placeholders keep arity fixed
        ks_in = jnp.zeros((1,) + sc_block[1:], jnp.float32)
        vs_in = ks_in
    scalars = tuple(jnp.asarray(s, jnp.int32) for s in extra_scalars) + \
        (lengths.astype(jnp.int32),)
    out_shapes = [
        jax.ShapeDtypeStruct((bsz, 1, h), x.dtype),
        jax.ShapeDtypeStruct((bsz, hkv, d), x.dtype),
        jax.ShapeDtypeStruct((bsz, hkv, d), x.dtype),
    ]
    call = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=_interpret(),
    )
    xo, k_new, v_new = _fa.run_kernel(
        w_qkv.dtype, call, *scalars, x[:, None, :], vec2(ln1_w), vec2(ln1_b),
        w_qkv, vec2(b_qkv), w_out, vec2(b_out), vec2(ln2_w), vec2(ln2_b), w_up,
        vec2(b_up), w_down, vec2(b_down), k_src, v_src, ks_in, vs_in)
    return xo[:, 0, :], k_new, v_new


# ---------------------------------------------------------------------------
# composite fallback: the composed kernels path, op for op
# ---------------------------------------------------------------------------
def _mm(x2, w, bias, quantize):
    """The projection math of the composed path: F.linear, or the
    fake-quant forward when the model trains/serves quantized (same
    numbers as ops.quantized_matmul — int8 qmm tiles from the unified
    tuning table when the Pallas qmm kernel engages)."""
    if quantize:
        from .quantized_matmul import quantized_matmul
        y = quantized_matmul(x2, w, dtype=quantize, out_dtype=x2.dtype)
    else:
        y = jnp.matmul(x2, w)
    if bias is not None:
        y = y + bias
    return y


def _ln_f32(x2, w, bias, eps):
    xf = x2.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * w + bias
    return out.astype(x2.dtype)


def _split_qkv(qkv, h, hkv, d):
    bsz = qkv.shape[0]
    kvd = hkv * d
    heads = (qkv.shape[1] - 2 * kvd) // d
    q = qkv[:, :h].reshape(bsz, heads, d)
    k_new = qkv[:, h:h + kvd].reshape(bsz, hkv, d)
    v_new = qkv[:, h + kvd:].reshape(bsz, hkv, d)
    return q, k_new, v_new


def _composite(x, w, lengths, attend, *, quantize, eps, hkv, d):
    """Shared composite body; ``attend(q, k_new, v_new)`` runs the
    layout's attention (dense/paged) over the cache WITH the new token
    folded in, mirroring the composed write-then-attend order."""
    (ln1_w, ln1_b, w_qkv, b_qkv, w_out, b_out,
     ln2_w, ln2_b, w_up, b_up, w_down, b_down) = w
    h = x.shape[1]
    h1 = _ln_f32(x, ln1_w, ln1_b, eps)
    qkv = _mm(h1, w_qkv, b_qkv, quantize)
    q, k_new, v_new = _split_qkv(qkv, h, hkv, d)
    attn = attend(q, k_new, v_new)                  # [B, heads, d]
    o = _mm(attn.reshape(x.shape[0], -1).astype(x.dtype), w_out, None,
            quantize) + b_out
    x2 = x + o.astype(x.dtype)
    h2 = _ln_f32(x2, ln2_w, ln2_b, eps)
    u = _mm(h2, w_up, b_up, quantize)
    act = jax.nn.gelu(u, approximate=True)
    mlp = _mm(act, w_down, None, quantize) + b_down
    x_out = x2 + mlp.astype(x.dtype)
    return x_out, k_new, v_new


def _dense_attend(q, k_new, v_new, k_cache, v_cache, lengths, k_scale,
                  v_scale):
    cap = k_cache.shape[2]
    idx = jnp.minimum(lengths.astype(jnp.int32), cap - 1)
    if k_scale is not None:
        from .quantized_matmul import kv_quant_mode, quantize_kv
        mode = kv_quant_mode(k_cache.dtype)
        kq, ks = quantize_kv(k_new, mode)
        vq, vs = quantize_kv(v_new, mode)
        return _da.decode_attention(
            q, _da.write_kv(k_cache, idx, kq),
            _da.write_kv(v_cache, idx, vq), idx + 1,
            _da.write_kv(k_scale, idx, ks), _da.write_kv(v_scale, idx, vs))
    return _da.decode_attention(
        q.astype(k_cache.dtype), _da.write_kv(k_cache, idx, k_new),
        _da.write_kv(v_cache, idx, v_new), idx + 1).astype(q.dtype)


def _paged_attend(q, k_new, v_new, k_pool, v_pool, tables, lengths,
                  k_scale, v_scale):
    bsz = q.shape[0]
    bs = k_pool.shape[2]
    mb = tables.shape[1]
    lens = lengths.astype(jnp.int32)
    blk_pos = jnp.minimum(lens // bs, mb - 1)
    off = lens % bs
    rows = jnp.arange(bsz)
    blk = tables[rows, blk_pos]
    if k_scale is not None:
        from .quantized_matmul import kv_quant_mode, quantize_kv
        mode = kv_quant_mode(k_pool.dtype)
        kq, ks = quantize_kv(k_new, mode)
        vq, vs = quantize_kv(v_new, mode)
        k_eff = k_pool.at[blk, :, off].set(kq)
        v_eff = v_pool.at[blk, :, off].set(vq)
        ks_eff = k_scale.at[blk, :, off].set(ks.astype(k_scale.dtype))
        vs_eff = v_scale.at[blk, :, off].set(vs.astype(v_scale.dtype))
        return _da.paged_decode_attention(q, k_eff, v_eff, tables,
                                         lens + 1, ks_eff, vs_eff)
    k_eff = k_pool.at[blk, :, off].set(k_new.astype(k_pool.dtype))
    v_eff = v_pool.at[blk, :, off].set(v_new.astype(v_pool.dtype))
    return _da.paged_decode_attention(
        q.astype(k_pool.dtype), k_eff, v_eff, tables,
        lens + 1).astype(q.dtype)


def _fused_refusal(x, w, hkv, d, block_s, quantize, kv_dtype,
                   kv_item, quantized) -> str:
    """Why the fused kernel does not serve this call ('' = it does).
    The reason lands in ops.kernel_paths, so a composite that stood in
    for the megakernel is visible to the engine's stats."""
    (ln1_w, ln1_b, w_qkv, b_qkv, w_out, b_out,
     ln2_w, ln2_b, w_up, b_up, w_down, b_down) = w
    h = x.shape[1]
    f = w_up.shape[1]
    kvd = hkv * d
    heads = (w_qkv.shape[1] - 2 * kvd) // d
    if not decode_megakernel_available():
        return "backend is not tpu"
    if quantize:
        # quantized COMPUTE runs the composite (whose projections take
        # the int8 qmm path with tuned tiles); the fused kernel serves
        # the fp-compute case, with or without an int8 KV cache
        return "quantized compute rides the composite"
    if quantized and kv_dtype != jnp.int8:
        return "fp8 caches ride the composite"
    if (heads * d != h or heads % hkv or h % 128 or f % 128
            or (d != 64 and d % 128) or block_s % 128):
        return "shape not served by the kernel"
    _, block_f, block_q, block_o = _pick_blocks(block_s, f,
                                                h + 2 * kvd, h)
    w_item = jnp.dtype(w_qkv.dtype).itemsize
    est = _vmem_estimate(h, kvd, f, block_s, block_f, block_q, block_o,
                         hkv, d, w_item, kv_item, quantized, x.shape[0])
    if not _interpret() and est > _VMEM_BUDGET:
        return (f"VMEM estimate {est} bytes over the budget "
                f"{_VMEM_BUDGET}")
    return ""


def _note_path(refusal: str) -> None:
    if refusal:
        kernel_paths.note("decode_megakernel", "composite", refusal)
    else:
        kernel_paths.note("decode_megakernel", "kernel")


def decode_layer_step(x, w, k_cache, v_cache, lengths, k_scale=None,
                      v_scale=None, *, quantize=None, eps: float = 1e-5):
    """ONE fused GPT layer decode step over a Static (dense) KV cache.

    x ``[B, H]`` — the residual stream at this layer for the new token;
    ``w`` — the 12 per-layer arrays in :data:`LAYER_WEIGHTS` order;
    k_cache/v_cache ``[B, Hkv, cap, D]`` — one head-major layer of the
    cache BEFORE the new
    token is written (the kernel folds the new token's k/v from VMEM;
    the CALLER scatters the returned ``k_new``/``v_new`` into the cache,
    exactly like the composed path does); lengths ``[B]`` int32 tokens
    already cached (excluding the new one).  int8 caches pass their
    ``[B, Hkv, cap]`` f32 scale planes.  Returns
    ``(x_out [B, H], k_new [B, Hkv, D] f32, v_new)``.

    Pallas fused kernel when shapes/VMEM allow, XLA composite (the
    composed kernels path op for op — the parity oracle) otherwise;
    ``quantize`` (int8 compute) always routes the composite, whose
    projections then run the int8 qmm kernel with tiles from the
    unified tuning table.
    """
    hkv, d = k_cache.shape[1], k_cache.shape[3]
    quantized = k_scale is not None
    cap = k_cache.shape[2]
    block_s = _pick_blocks(cap, w[8].shape[1])[0]
    refusal = "shape not served by the kernel" if cap % block_s else \
        _fused_refusal(x, w, hkv, d, block_s, quantize, k_cache.dtype,
                       jnp.dtype(k_cache.dtype).itemsize, quantized)
    _note_path(refusal)
    if refusal:
        attend = functools.partial(_dense_attend, k_cache=k_cache,
                                   v_cache=v_cache, lengths=lengths,
                                   k_scale=k_scale, v_scale=v_scale)
        return _composite(x, w, lengths, attend, quantize=quantize,
                          eps=eps, hkv=hkv, d=d)
    ns = cap // block_s

    def kv_maps(nq):
        def kv_map(p, b, lens):
            in_kv = (p >= nq) & (p < nq + ns)
            return (jnp.where(in_kv, b, 0), 0,
                    jnp.clip(p - nq, 0, ns - 1), 0)
        return kv_map

    def sc_maps(nq):
        def sc_map(p, b, lens):
            in_kv = (p >= nq) & (p < nq + ns)
            return (jnp.where(in_kv, b, 0), 0,
                    jnp.clip(p - nq, 0, ns - 1))
        return sc_map

    return _run_mega(x, w, k_cache, v_cache, k_scale, v_scale, lengths,
                     ns=ns, cap=cap, eps=eps, quantized=quantized,
                     paged=False, kv_map_factory=kv_maps,
                     sc_map_factory=sc_maps)


def decode_layer_step_paged(x, w, k_pool, v_pool, tables, lengths,
                            k_scale=None, v_scale=None, *, quantize=None,
                            eps: float = 1e-5):
    """ONE fused GPT layer decode step over a PAGED KV cache: the same
    fused body as :func:`decode_layer_step`, with the slot's KV blocks
    resolved through its scalar-prefetched block table (the
    ``paged_decode_attention`` indirection) — MLP phases pin the index
    map to the null block so the weight-tile phases never re-stream KV.
    tables ``[B, MB]`` int32; lengths EXCLUDE the new token.  Returns
    ``(x_out, k_new, v_new)`` — the caller scatters the new k/v at
    ``(tables[b, lengths[b]//bs], lengths[b]%bs)``."""
    hkv, d = k_pool.shape[1], k_pool.shape[3]
    quantized = k_scale is not None
    bs = k_pool.shape[2]
    mb = tables.shape[1]
    refusal = _fused_refusal(x, w, hkv, d, bs, quantize, k_pool.dtype,
                             jnp.dtype(k_pool.dtype).itemsize, quantized)
    _note_path(refusal)
    if refusal:
        attend = functools.partial(_paged_attend, k_pool=k_pool,
                                   v_pool=v_pool, tables=tables,
                                   lengths=lengths, k_scale=k_scale,
                                   v_scale=v_scale)
        return _composite(x, w, lengths, attend, quantize=quantize,
                          eps=eps, hkv=hkv, d=d)

    def kv_maps(nq):
        def kv_map(p, b, tbl, lens):
            blk = tbl[b, jnp.clip(p - nq, 0, mb - 1)]
            in_kv = (p >= nq) & (p < nq + mb)
            return (jnp.where(in_kv, blk, 0), 0, 0, 0)
        return kv_map

    def sc_maps(nq):
        def sc_map(p, b, tbl, lens):
            blk = tbl[b, jnp.clip(p - nq, 0, mb - 1)]
            in_kv = (p >= nq) & (p < nq + mb)
            return (jnp.where(in_kv, blk, 0), 0, 0)
        return sc_map

    return _run_mega(x, w, k_pool, v_pool, k_scale, v_scale, lengths,
                     ns=mb, cap=mb * bs, eps=eps, quantized=quantized,
                     paged=True, kv_map_factory=kv_maps,
                     sc_map_factory=sc_maps,
                     extra_scalars=(tables,))
