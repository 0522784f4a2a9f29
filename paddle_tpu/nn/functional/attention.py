"""Attention functionals.

The reference's attention is a chain of separate ops (matmul → scale →
softmax → dropout → matmul; fused only in inference via
fused/multihead_matmul_op.cu). Here the training path gets a real fused
kernel: on TPU, `flash_attention` lowers to a Pallas blockwise-softmax
kernel (paddle_tpu.ops.flash_attention) that never materializes the
[B,H,S,S] score matrix in HBM; elsewhere it falls back to the XLA
composite, which XLA still fuses reasonably.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...core.autograd import apply

__all__ = ["scaled_dot_product_attention", "flash_attention"]


def _sdpa_reference(q, k, v, mask=None, dropout_p=0.0, is_causal=False,
                    scale=None, key=None):
    """[B, S, H, D] layout (paddle convention for flash_attention)."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    qh = jnp.swapaxes(q, 1, 2)  # [B,H,S,D]
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                        preferred_element_type=jnp.float32) * s
    if is_causal:
        sq, sk = scores.shape[-2], scores.shape[-1]
        causal = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        scores = jnp.where(causal, scores, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, -1e30)
        else:
            scores = scores + mask
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, scale=None, name=None):
    """paddle.nn.functional.scaled_dot_product_attention parity;
    inputs [B, S, H, D]."""
    from ...core import random as prandom

    rng = prandom.next_key() if (dropout_p > 0.0 and training) else None
    p = dropout_p if training else 0.0

    def fn(q, k, v, *rest):
        m = rest[0] if rest else None
        return _sdpa_reference(q, k, v, m, p, is_causal, scale, rng)

    args = [query, key, value]
    if attn_mask is not None:
        args.append(attn_mask)
    return apply(fn, *args, name="scaled_dot_product_attention")


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, training=True, kv_mask=None,
                    name=None):
    """Flash-attention entry point; uses the Pallas TPU kernel when
    available (paddle_tpu.ops.flash_attention: fused fwd+bwd, native GQA
    — k/v may carry fewer heads), XLA composite otherwise. kv_mask [B,S]
    (1 = attend) covers padded-batch pretraining without an O(S^2) bias."""
    from ... import ops as _ops

    if (_ops.flash_attention_available() and dropout == 0.0
            and not return_softmax):
        def fn(q, k, v, *rest):
            m = rest[0] if rest else None
            return _ops.flash_attention(q, k, v, causal=causal, kv_mask=m)
        args = [query, key, value]
        if kv_mask is not None:
            args.append(kv_mask)
        out = apply(fn, *args, name="flash_attention")
        return (out, None) if return_softmax else out

    # composite fallback: expand GQA heads (the kernel handles them
    # natively; the composite needs full-head k/v)
    _ops.kernel_paths.note(
        "flash_attention", "composite",
        "backend is not tpu" if not _ops.flash_attention_available()
        else "dropout or return_softmax requested")
    h = (query.shape[2] if hasattr(query, "shape") else None)
    hkv = (key.shape[2] if hasattr(key, "shape") else None)
    if h is not None and hkv is not None and h != hkv:
        from ...tensor.manipulation import repeat_interleave
        key = repeat_interleave(key, h // hkv, axis=2)
        value = repeat_interleave(value, h // hkv, axis=2)
    mask_bias = None
    if kv_mask is not None:
        arr = kv_mask.data if hasattr(kv_mask, "data") else kv_mask
        mask_bias = jnp.where(arr[:, None, None, :] > 0, 0.0, -1e30) \
            .astype(jnp.float32)
    out = scaled_dot_product_attention(
        query, key, value, attn_mask=mask_bias, dropout_p=dropout,
        is_causal=causal, training=training)
    return (out, None) if return_softmax else out
