"""Sequence decoding: beam search, greedy, sampling + gather_tree.

Reference: /root/reference/paddle/fluid/operators/beam_search_op.h
(per-step top-k over K*V candidates with parent pointers),
beam_search_decode_op (backtracking), gather_tree_op.cc, and the Python
orchestration in fluid/layers/rnn.py (BeamSearchDecoder +
dynamic_decode).

TPU-native shape: the whole decode is ONE `lax.while_loop` over time —
the per-step top-k, parent gather, and finished masking are fixed-shape
jnp ops writing into preallocated [max_len, ...] buffers, so the entire
loop compiles to a single XLA while-program (the reference re-enters
the executor per step) AND exits early: once every batch row / beam has
emitted EOS the loop stops instead of burning the remaining max_len
steps (the buffers are EOS/identity-filled, so outputs are identical to
the full-length run).  States carry a leading [B*K] dim;
`step_fn(tokens, state) -> (logits, state)` is any jax function.

`gpt_step_fn` adapts a models.GPTForCausalLM + its StaticKVCache to
that contract (the cache's [layers, N, ...] leaves are re-gathered on
axis 1 by the beam parent shuffle), which is what wires these decoders
to the real transformer decode step.
"""
from __future__ import annotations

from typing import Callable, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, unwrap as _arr

__all__ = ["beam_search", "greedy_search", "gather_tree",
           "viterbi_decode", "gpt_step_fn"]

_NEG = -1e9


def gpt_step_fn(model) -> Callable:
    """step_fn over a GPTForCausalLM: ``step(tokens [N], cache) ->
    (logits [N, V], cache)`` where cache is a models.StaticKVCache with
    N slots (``model.init_kv_cache(N)``, optionally pre-filled with a
    prompt per slot via ``model.prefill``).  Every step appends one
    token per slot — recompile-free by construction.  Call
    ``model.eval()`` first so dropout layers are inert."""
    def step(tokens, cache):
        active = jnp.ones((cache.batch_slots,), jnp.int32)
        logits, cache = model.decode_step(tokens, cache, active)
        return logits, cache
    return step




def gather_tree(token_ids, parent_ids):
    """Backtrack beam parent pointers into full sequences
    (gather_tree_op.cc). token_ids/parent_ids: [T, B, K] -> [T, B, K]
    where output[:, b, k] is the COMPLETE sequence feeding beam k at the
    final step."""
    ids = _arr(token_ids)
    parents = _arr(parent_ids)
    T = ids.shape[0]

    def back(carry, t):
        beam = carry                               # [B, K] current beam
        tok = jnp.take_along_axis(ids[t], beam, axis=1)
        par = jnp.take_along_axis(parents[t], beam, axis=1)
        return par, tok

    k0 = jnp.broadcast_to(jnp.arange(ids.shape[2])[None, :],
                          ids.shape[1:])
    _, toks = jax.lax.scan(back, k0, jnp.arange(T - 1, -1, -1))
    return Tensor(toks[::-1])


def beam_search(step_fn: Callable, init_state, batch_size: int,
                beam_size: int, max_len: int, bos_id: int, eos_id: int,
                length_penalty: float = 0.0) -> Tuple[Tensor, Tensor]:
    """Compiled beam search. Returns (sequences [B, K, max_len],
    scores [B, K]) sorted best-first.

    step_fn(tokens [B*K], state) -> (logits [B*K, V], new_state); state
    leaves carry a leading B*K dim (tile your encoder state K times).
    length_penalty: GNMT alpha — scores divided by ((5+len)/6)^alpha.
    """
    B, K = batch_size, beam_size

    def expand_logp(logits):
        return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)

    def regather(a, parent):
        """Shuffle a state leaf by beam parents.  Leaves with a leading
        [B*K] dim gather on axis 0 (a StaticKVCache's per-layer
        [B*K, Hkv, S, D] buffers among them); [L, B*K, ...] leaves
        (layer-stacked state) gather on axis 1.  (A leaf
        whose axis-0 length coincidentally equals B*K takes the axis-0
        branch — lay out such state batch-first.)"""
        if a.ndim >= 1 and a.shape[0] == B * K:
            r = a.reshape((B, K) + a.shape[1:])[
                jnp.arange(B)[:, None], parent]
            return r.reshape((B * K,) + a.shape[1:])
        if a.ndim >= 2 and a.shape[1] == B * K:
            r = a.reshape((a.shape[0], B, K) + a.shape[2:])[
                :, jnp.arange(B)[:, None], parent]
            return r.reshape((a.shape[0], B * K) + a.shape[2:])
        raise ValueError(
            f"beam_search state leaf {a.shape} carries no [B*K]={B * K} "
            f"dim on axis 0 or 1")

    def cond(carry):
        t, _, _, finished, _, _, _ = carry
        # EOS early-exit: the while-program stops the moment every beam
        # of every row has finished (the scan version always paid
        # max_len steps; the buffers are EOS/identity-initialized so
        # the output is bit-identical)
        return (t < max_len) & ~jnp.all(finished)

    def step(carry):
        t, tokens, cum, finished, state, toks_buf, par_buf = carry
        logits, state = step_fn(tokens.reshape(-1), state)
        V = logits.shape[-1]
        logp = expand_logp(logits).reshape(B, K, V)
        # finished beams emit ONLY eos at no cost (the reference keeps
        # them alive in the beam with frozen scores)
        eos_only = jnp.full((V,), _NEG).at[eos_id].set(0.0)
        logp = jnp.where(finished[..., None], eos_only[None, None, :],
                         logp)
        total = cum[..., None] + logp             # [B, K, V]
        flat = total.reshape(B, K * V)
        cum_new, idx = jax.lax.top_k(flat, K)     # [B, K]
        parent = idx // V
        token = idx % V
        finished = jnp.take_along_axis(finished, parent, axis=1) | \
            (token == eos_id)
        state = jax.tree_util.tree_map(lambda a: regather(a, parent),
                                       state)
        toks_buf = toks_buf.at[t].set(token)
        par_buf = par_buf.at[t].set(parent)
        return (t + 1, token, cum_new, finished, state, toks_buf,
                par_buf)

    tokens0 = jnp.full((B, K), bos_id, jnp.int32)
    # only beam 0 is live at t=0, or every beam would decode identically
    cum0 = jnp.tile(jnp.asarray([0.0] + [_NEG] * (K - 1),
                                jnp.float32)[None, :], (B, 1))
    fin0 = jnp.zeros((B, K), bool)
    # unexecuted steps: eos tokens with identity parents, so gather_tree
    # backtracks through them unchanged
    toks0 = jnp.full((max_len, B, K), eos_id, jnp.int32)
    par0 = jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32)[None, None, :],
                            (max_len, B, K))
    _, tokens, cum, finished, _, toks, parents = jax.lax.while_loop(
        cond, step,
        (jnp.asarray(0, jnp.int32), tokens0, cum0, fin0, init_state,
         toks0, par0))

    seqs = gather_tree(toks, parents).data        # [T, B, K]
    seqs = jnp.moveaxis(seqs, 0, 2)               # [B, K, T]
    # length penalty at final ranking (fluid/layers/rnn.py
    # BeamSearchDecoder's GNMT score)
    lengths = jnp.minimum(
        jnp.argmax((seqs == eos_id).astype(jnp.int32), axis=2) + 1,
        max_len).astype(jnp.float32)
    has_eos = (seqs == eos_id).any(axis=2)
    lengths = jnp.where(has_eos, lengths, float(max_len))
    denom = ((5.0 + lengths) / 6.0) ** length_penalty
    scores = cum / denom
    order = jnp.argsort(-scores, axis=1)
    seqs = jnp.take_along_axis(seqs, order[..., None], axis=1)
    scores = jnp.take_along_axis(scores, order, axis=1)
    return Tensor(seqs), Tensor(scores)


def greedy_search(step_fn: Callable, init_state, batch_size: int,
                  max_len: int, bos_id: int, eos_id: int
                  ) -> Tensor:
    """Greedy argmax decode as one XLA while-program with EOS
    early-exit: the loop stops once every row has finished (the output
    buffer is EOS-filled, so results match the full-length run).
    Returns [B, max_len]."""
    B = batch_size

    def cond(carry):
        t, _, finished, _, _ = carry
        return (t < max_len) & ~jnp.all(finished)

    def step(carry):
        t, tokens, finished, state, out = carry
        logits, state = step_fn(tokens, state)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(finished, eos_id, nxt)
        finished = finished | (nxt == eos_id)
        return t + 1, nxt, finished, state, out.at[t].set(nxt)

    tokens0 = jnp.full((B,), bos_id, jnp.int32)
    fin0 = jnp.zeros((B,), bool)
    out0 = jnp.full((max_len, B), eos_id, jnp.int32)
    _, _, _, _, toks = jax.lax.while_loop(
        cond, step,
        (jnp.asarray(0, jnp.int32), tokens0, fin0, init_state, out0))
    return Tensor(jnp.moveaxis(toks, 0, 1))


def viterbi_decode(potentials, transition, lengths=None,
                   include_bos_eos_tag=True):
    """CRF Viterbi decode (reference crf_decoding_op.h /
    paddle.text.viterbi_decode): emission potentials [B, T, N] +
    transition [N, N] -> (scores [B], best paths [B, T]).  One lax.scan
    forward pass keeping per-tag backpointers, one reverse scan to read
    the argmax path; rows past `lengths` freeze (mask convention).
    include_bos_eos_tag treats the last two tags as BOS/EOS like the
    reference (start/stop transition rows added at the boundaries)."""
    em = _arr(potentials).astype(jnp.float32)       # [B, T, N]
    tr = _arr(transition).astype(jnp.float32)       # [N, N]
    b, t, n = em.shape
    if lengths is None:
        ln = jnp.full((b,), t, jnp.int32)
    else:
        ln = _arr(lengths).astype(jnp.int32)

    if include_bos_eos_tag:
        # reference convention: tag N-2 = BOS, N-1 = EOS
        start = tr[n - 2]                           # [N]
        stop = tr[:, n - 1]                         # [N]
    else:
        start = jnp.zeros((n,), jnp.float32)
        stop = jnp.zeros((n,), jnp.float32)

    alpha0 = em[:, 0] + start[None, :]              # [B, N]

    def fwd(carry, i):
        alpha = carry                               # [B, N]
        # score of arriving at tag j from tag k
        cand = alpha[:, :, None] + tr[None, :, :]   # [B, from, to]
        best = cand.max(axis=1) + em[:, i]          # [B, N]
        bp = cand.argmax(axis=1).astype(jnp.int32)  # [B, N]
        keep = (i < ln)[:, None]
        alpha = jnp.where(keep, best, alpha)
        return alpha, bp

    alpha, bps = jax.lax.scan(fwd, alpha0, jnp.arange(1, t))
    # EOS transition applies at each row's LAST valid position
    final = alpha + stop[None, :]
    scores = final.max(axis=1)
    last_tag = final.argmax(axis=1).astype(jnp.int32)   # [B]

    def back(carry, i):
        tag = carry                                  # [B]
        # bps[i] maps position i+1's tag -> best previous tag
        prev = jnp.take_along_axis(bps[i], tag[:, None],
                                   axis=1)[:, 0]
        # positions at/after the row's end keep the frozen tag
        tag_new = jnp.where(i + 1 < ln, prev, tag)
        return tag_new, tag

    tag_final, tags_rev = jax.lax.scan(
        back, last_tag, jnp.arange(t - 2, -1, -1))
    path = jnp.concatenate(
        [tag_final[:, None],
         jnp.moveaxis(tags_rev[::-1], 0, 1)], axis=1)   # [B, T]
    return Tensor(scores), Tensor(path)
