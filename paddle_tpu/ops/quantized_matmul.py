"""AQT-style int8 (fp8-ready) quantized matmul + KV-cache quantization.

Training stalled at ~35% MFU with the step time dominated by
bf16 matmul FLOPs and, on the serving side, by KV bytes streamed from
HBM.  Both halve under 8-bit arithmetic — the v5e MXU runs int8 at 2×
the bf16 rate, and an int8 KV cache moves half the bytes per decode
step.  This module is the compute half of that attack (the KV half
lives in ops/decode_attention.py + the cache classes):

- :func:`quantize_channel` / :func:`quantize_kv` — symmetric amax
  scaling.  ``quantize_channel`` scales per channel along a named axis
  (per token row for activations, per output column for weights);
  ``quantize_kv`` scales per (position, head) over the trailing
  head_dim axis — the granularity the decode kernels dequantize at.
- :func:`quantized_matmul` — y ≈ (q_x · q_w) · s_x · s_w.  A Pallas TPU
  kernel (int8 MXU dots, int32 accumulation, f32 rescale) with an XLA
  ``dot_general`` composite fallback that is the CPU parity oracle: the
  int8 path accumulates in int32 (exact — f32 would lose bits past
  2^24), the fp8 path in f32 via ``preferred_element_type``.
- :func:`fake_quant_matmul` — the AQT-style training op: forward runs
  the quantized matmul, backward is the straight-through estimator
  (grads flow through the DEQUANTIZED operands as if quantization were
  identity), so ``GPTConfig(quantize='int8')`` trains through quantized
  forward matmuls without touching the optimizer or the parameters'
  dtype.  Equivalent to ``fq(x) @ fq(w)`` with
  ``fq(t) = t + stop_gradient(qdq(t) - t)`` — the reference the tests
  check the custom VJP against.

fp8 readiness: every helper accepts ``dtype='fp8'`` (E4M3) when this
jax build ships ``jnp.float8_e4m3fn``; the Pallas kernel currently
serves int8 only and fp8 rides the composite.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

import importlib

from . import kernel_paths

# live view of the sibling module's mutable interpret flag (the package
# __init__ rebinds `flash_attention` to the public function)
_fa = importlib.import_module(__package__ + ".flash_attention")

__all__ = ["quantized_matmul", "quantized_matmul_available",
           "fake_quant_matmul", "quantize_channel", "quantize_kv",
           "dequantize_kv", "kv_storage_dtype", "kv_quant_supported",
           "kv_quant_mode", "resolve_kv_quant", "get_qmm_tiles",
           "QUANT_DTYPES"]

QUANT_DTYPES = ("int8", "fp8")
_EPS = 1e-8


def _check_mode(dtype: str) -> str:
    if dtype not in QUANT_DTYPES:
        raise ValueError(f"quantize dtype must be one of {QUANT_DTYPES}, "
                         f"got {dtype!r}")
    if dtype == "fp8" and not hasattr(jnp, "float8_e4m3fn"):
        raise ValueError("quantize='fp8' needs a jax with "
                         "jnp.float8_e4m3fn; this build has none — "
                         "use 'int8'")
    return dtype


def _qmax(dtype: str) -> float:
    return 127.0 if dtype == "int8" else 448.0   # E4M3 finite max


def kv_storage_dtype(dtype: str):
    """The jnp storage dtype for a quantized KV cache."""
    _check_mode(dtype)
    return jnp.int8 if dtype == "int8" else jnp.float8_e4m3fn


def kv_quant_supported(dtype) -> bool:
    """True when `dtype` names a usable quantized-KV mode here."""
    try:
        _check_mode(dtype)
        return True
    except ValueError:
        return False


def kv_quant_mode(storage_dtype) -> str:
    """Inverse of :func:`kv_storage_dtype`: the mode name for a
    quantized cache's storage dtype."""
    if storage_dtype == jnp.int8:
        return "int8"
    if hasattr(jnp, "float8_e4m3fn") and storage_dtype == jnp.float8_e4m3fn:
        return "fp8"
    raise ValueError(f"not a quantized KV storage dtype: {storage_dtype}")


def resolve_kv_quant(name=None):
    """Normalize a kv_dtype knob (arg or PADDLE_TPU_KV_DTYPE env) to a
    quant mode or None (= full-precision cache, the default)."""
    import os
    if name is None:
        name = os.environ.get("PADDLE_TPU_KV_DTYPE", "")
    name = str(name).strip().lower()
    if name in ("", "0", "none", "off", "dense", "fp32", "bf16",
                "bfloat16", "float32"):
        return None
    _check_mode(name)
    return name


def _cast_q(x_scaled, dtype: str):
    """Scaled values -> storage dtype (round+clip for int8, cast for
    fp8 — the f8 cast saturates/rounds in hardware convention)."""
    if dtype == "int8":
        return jnp.clip(jnp.round(x_scaled), -127.0, 127.0) \
            .astype(jnp.int8)
    return x_scaled.astype(jnp.float8_e4m3fn)


def quantize_channel(x, axis: int, dtype: str = "int8"):
    """Symmetric amax quantization per channel along ``axis`` (which is
    the axis REDUCED per channel — the contracting dim for a matmul
    operand).  Returns ``(q, scale)`` with ``scale`` keepdims-shaped so
    ``q.astype(f32) * scale ≈ x``."""
    _check_mode(dtype)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, _EPS) / _qmax(dtype)
    return _cast_q(xf / scale, dtype), scale


def quantize_kv(x, dtype: str = "int8"):
    """KV-cache quantization at per-(position, head) granularity:
    ``x [..., head_dim]`` -> ``(q [..., head_dim], scale [...])`` with
    ``q.astype(f32) * scale[..., None] ≈ x``.  One f32 scale per
    head_dim values — a 1/64..1/128 metadata overhead next to the 2×
    byte saving on the values themselves."""
    q, scale = quantize_channel(x, axis=-1, dtype=dtype)
    return q, scale[..., 0]


def dequantize_kv(q, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize_kv` (``scale`` without the trailing
    head_dim axis)."""
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)
            ).astype(dtype)


# ---------------------------------------------------------------------------
# composite (the CPU parity oracle)
# ---------------------------------------------------------------------------
def _qmm_composite(qx, qw, sx, sw, out_dtype):
    """(q_x · q_w) · s_x · s_w via one XLA dot_general.  int8 inputs
    accumulate in int32 (exact), fp8 in f32 (preferred_element_type)."""
    if qx.dtype == jnp.int8:
        acc = jax.lax.dot_general(
            qx, qw, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
    else:
        acc = jax.lax.dot_general(
            qx, qw, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return (acc * sx * sw).astype(out_dtype)


# ---------------------------------------------------------------------------
# Pallas kernel: int8 MXU dots, int32 accumulation, f32 rescale
# ---------------------------------------------------------------------------
def quantized_matmul_available() -> bool:
    return _fa._INTERPRET or jax.default_backend() == "tpu"


def _qmm_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, *, block_k: int):
    """One (m_block, n_block) program: x_ref [bm, K] int8 row strip,
    w_ref [K, bn] int8 column strip, sx (bm, 1) / sw (1, bn) f32
    per-channel scales; o_ref [bm, bn]."""
    k = x_ref.shape[1]
    n_k = k // block_k
    bm, bn = o_ref.shape

    def body(j, acc):
        x_blk = x_ref[:, pl.ds(j * block_k, block_k)]
        w_blk = w_ref[pl.ds(j * block_k, block_k), :]
        return acc + jax.lax.dot_general(
            x_blk, w_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    acc = jax.lax.fori_loop(0, n_k, body,
                            jnp.zeros((bm, bn), jnp.int32))
    o_ref[:] = (acc.astype(jnp.float32) * sx_ref[:] * sw_ref[:]) \
        .astype(o_ref.dtype)


def get_qmm_tiles(m: int, n: int, k: int):
    """(block_m, block_n, block_k) for the quantized-matmul kernel:
    defaults clamped to divide the problem."""
    # defaults sized for the MXU: [bm, K]+[K, bn] int8 strips + the
    # [bm, bn] int32 accumulator stay well under VMEM at K ≤ 8192
    return (_fa._pick_block(m, 256), _fa._pick_block(n, 256),
            _fa._pick_block(k, 512))


def _qmm_pallas(qx, qw, sx, sw, out_dtype):
    m, k = qx.shape
    n = qw.shape[1]
    bm, bn, bk = get_qmm_tiles(m, n, k)
    kernel = functools.partial(_qmm_kernel, block_k=bk)
    call = pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=_fa._INTERPRET,
    )
    return _fa.run_kernel(qx.dtype, call, qx, qw, sx, sw)


def _qmm_forward(x, w, dtype, out_dtype):
    """Shared quantize + dispatch body of quantized_matmul and the
    fake-quant forward: returns ``(y [..., N], qx, sx, qw, sw)`` with
    qx/sx over the flattened ``[M, K]`` activations.  ONE home for the
    kernel-gating predicate (m % 32: int8's native sublane tile —
    single-token decode matmuls take the composite, where they are
    noise anyway)."""
    _check_mode(dtype)
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    qx, sx = quantize_channel(x2, axis=1, dtype=dtype)     # sx [M, 1]
    qw, sw = quantize_channel(w, axis=0, dtype=dtype)      # sw [1, N]
    supported = (dtype == "int8" and m % 32 == 0 and n % 128 == 0
                 and k % 128 == 0)
    if supported and quantized_matmul_available():
        kernel_paths.note("quantized_matmul", "kernel")
        y = _qmm_pallas(qx, qw, sx, sw, out_dtype)
    else:
        kernel_paths.note_composite("quantized_matmul", supported)
        y = _qmm_composite(qx, qw, sx, sw, out_dtype)
    return y.reshape(*lead, n), qx, sx, qw, sw


def quantized_matmul(x, w, dtype: str = "int8", out_dtype=None):
    """``x [..., K] @ w [K, N]`` through ``dtype`` quantization:
    activations amax-scaled per row, weights per output column, the
    8-bit dot rescaled back to ``out_dtype`` (default ``x.dtype``).
    Pallas kernel when shapes/backend allow, XLA composite otherwise —
    the composite is the parity oracle the kernel is tested against."""
    y, *_ = _qmm_forward(x, w, dtype, out_dtype or x.dtype)
    return y


# ---------------------------------------------------------------------------
# fake-quant training op (straight-through estimator)
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def fake_quant_matmul(x, w, dtype: str = "int8"):
    """Quantized forward, straight-through backward.  Numerically equal
    to ``fq(x) @ fq(w)`` with ``fq(t) = t + sg(qdq(t) - t)`` — the
    model sees (and learns under) quantization noise while grads flow
    as if the matmul were full precision over the dequantized operands.
    The parameters stay fp32/bf16, so optimizers are untouched."""
    y, _ = _fake_quant_fwd(x, w, dtype)
    return y


def _fake_quant_fwd(x, w, dtype):
    y, qx, sx, qw, sw = _qmm_forward(x, w, dtype, x.dtype)
    # residuals: the DEQUANTIZED operands in the inputs' shapes/dtypes
    # (exactly fq(x)/fq(w) of the STE reference — residual leaves must
    # be arrays, so shape/dtype bookkeeping rides on them)
    xdq = (qx.astype(jnp.float32) * sx).reshape(x.shape).astype(x.dtype)
    wdq = (qw.astype(jnp.float32) * sw).astype(w.dtype)
    return y, (xdq, wdq)


def _fake_quant_bwd(dtype, res, g):
    xdq, wdq = res
    k = xdq.shape[-1]
    n = g.shape[-1]
    g2 = g.reshape(-1, n).astype(jnp.float32)
    x2 = xdq.reshape(-1, k).astype(jnp.float32)
    # STE: d/dx [fq(x) @ fq(w)] = g @ fq(w)^T, d/dw = fq(x)^T @ g —
    # quantization treated as identity in the backward pass
    dx = jax.lax.dot_general(g2, wdq.astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dw = jax.lax.dot_general(x2, g2, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return dx.reshape(xdq.shape).astype(xdq.dtype), dw.astype(wdq.dtype)


fake_quant_matmul.defvjp(_fake_quant_fwd, _fake_quant_bwd)
