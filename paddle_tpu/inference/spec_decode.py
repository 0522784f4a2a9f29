"""Speculative decoding: a small draft GPT proposes, the target verifies.

Leviathan et al., *Fast Inference from Transformers via Speculative
Decoding*: decode is bandwidth-bound — every single-token step streams
the whole model + KV cache through the chip to emit ONE token.  A small
draft model can propose K tokens cheaply; the target model then scores
all K+1 positions in ONE windowed forward (the multi-token variant of
``ops.decode_attention`` — same bytes streamed as a single decode step)
and keeps the longest prefix of accepted proposals plus one bonus token
from its own distribution.  Temperature-0 slots use the greedy rule
(match the target's argmax), so the emitted stream is TOKEN-IDENTICAL
to the target-only rollout — speculation changes the schedule, never
the text.  Temperature>0 slots run the FULL rejection-sampling rule
(Leviathan Alg. 1): proposal ``d_i ~ q_i`` accepts with probability
``min(1, p_i(d_i)/q_i(d_i))`` over the WARPED (temperature/top-k/top-p)
distributions, and the first rejected position resamples from the
residual ``norm(max(p - q, 0))`` — in-graph, fixed shapes, so the
committed stream is a faithful sample from the target distribution and
a seeded engine replays the same stream.  With an agreeable draft, each
tick emits ~K+1 tokens for one target pass + one host sync, and the
decode loop's HBM bytes per emitted token drop proportionally.

Mechanics per tick (ONE fixed-shape jitted call — the zero-recompile
contract of the engine survives):

1. **Draft catch-up**: the tokens the scheduler committed last tick that
   the draft has not processed (1..2 of them — the bonus token, plus the
   last proposal when everything was accepted) ride in as a fixed
   ``[B, K+1]`` window; a windowed draft forward folds them into the
   draft's own StaticKVCache and its last valid logit row proposes
   draft token 1.
2. **Propose**: K-1 single-token draft decode steps propose the rest.
3. **Verify**: the target runs ONE windowed forward over
   ``[last_committed, d_1..d_K]`` — writing all K+1 k/v into its cache
   in-graph (dense scatter or paged block-table scatter) — and takes
   greedy ``g_0..g_K``.
4. **Accept**: ``n_acc = longest prefix with d_i == g_{i-1}``; commit
   ``g_0..g_{n_acc}`` (the standard rule: every accepted draft plus one
   bonus token).  Cache lengths advance by the committed count
   in-graph; rejected positions hold garbage ABOVE the advanced length
   — the masked-garbage convention every decode path here already uses
   — and are overwritten by the next tick's window.

The draft always rides a dense StaticKVCache (it is small; block
accounting for it would buy nothing); the TARGET cache is whatever the
engine runs — dense or paged, fp or int8 — which is the matrix the
tests pin down.  ``PADDLE_TPU_SPEC_K`` arms it engine-wide, for greedy
AND sampled traffic (ISSUE 18: temperature>0 requests no longer bypass
the spec path).  Under a tp serving mesh the draft's params and cache
shard exactly like the target's (engine._shard_over_mesh helpers), so
the tick executable compiles SPMD end to end.

Capacity caveat: a tick writes its whole K+1 window before knowing how
much commits, so a stream retires once ``len + K + 1`` would pass
``max_seq_len`` — up to K tokens earlier than a non-speculative
engine.  Token identity therefore holds whenever
``prompt + max_new + K <= max_seq`` (the sane deployment shape);
streams cut by the window margin are counted in
``stats['spec_capacity_retirements']``.
"""
from __future__ import annotations

import os
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..distributed import moe as _moe
from ..func import functional_apply, functional_state

__all__ = ["SpecDecoder", "resolve_spec_k"]


def resolve_spec_k(spec_k: Optional[int]) -> int:
    """Draft window size: explicit arg, else PADDLE_TPU_SPEC_K, else 0
    (speculation off)."""
    if spec_k is not None:
        return int(spec_k)
    return int(os.environ.get("PADDLE_TPU_SPEC_K", 0) or 0)


class SpecDecoder:
    """The engine's speculative-decoding half: owns the draft model's
    params + dense KV cache and the compiled tick executables.

    The ENGINE stays the scheduler — admission, EOS/deadline retirement,
    preemption and block accounting are untouched; this class only
    replaces the one-token decode step with the K+1-token tick and
    keeps the per-slot catch-up window (`win`/`nprev`) that makes the
    draft cache converge to the committed stream.
    """

    def __init__(self, engine, draft_model, k: int):
        if k < 1:
            raise ValueError(f"spec_k must be >= 1, got {k}")
        draft_model.eval()
        dcfg = draft_model.cfg
        tcfg = engine.model.cfg
        if dcfg.vocab_size != tcfg.vocab_size:
            raise ValueError(
                f"draft vocab {dcfg.vocab_size} != target vocab "
                f"{tcfg.vocab_size}")
        if dcfg.max_seq_len < engine.max_seq_len:
            raise ValueError(
                f"draft max_seq_len {dcfg.max_seq_len} < engine "
                f"max_seq_len {engine.max_seq_len} — the draft must "
                f"reach every position the target serves")
        self.engine = engine
        self.k = int(k)
        self.draft = draft_model
        self.draft_params, _ = functional_state(draft_model)
        # the draft rides a DENSE static cache regardless of the
        # target's layout: per-slot lengths live in-graph (advanced by
        # the tick itself, including the rollback of rejected
        # proposals), so the host never tracks draft state
        self.draft_cache = draft_model.init_kv_cache(
            engine.batch_slots, engine.max_seq_len)
        # pod-scale serving (ISSUE 18): the draft rides the SAME mesh —
        # params by the parallel-layer pspecs, dense cache slots/heads
        # over dp/tp — so the whole tick compiles SPMD
        if engine.mesh is not None:
            try:
                self.draft_params = engine._shard_params_over(
                    engine.mesh, self.draft_params, draft_model)
                self.draft_cache = engine._shard_dense_cache_arrays(
                    engine.mesh, self.draft_cache)
            except Exception as e:
                engine._shard_failed("spec_draft", e)
        # per-slot catch-up window: committed tokens the draft has not
        # seen yet (1 after a fresh admission — the first sampled
        # token; up to 2 mid-stream)
        kp1 = self.k + 1
        self.win = np.zeros((engine.batch_slots, kp1), np.int32)
        self.nprev = np.ones(engine.batch_slots, np.int32)
        dargs = (2, 3) if engine._donate else ()
        self._tick_dense_jit = jax.jit(self._tick_dense_fn,
                                       donate_argnums=dargs)
        self._tick_paged_jit = jax.jit(self._tick_paged_fn,
                                       donate_argnums=dargs)
        self._draft_prefill_jit = jax.jit(
            self._draft_prefill_fn,
            donate_argnums=(1,) if engine._donate else ())

    # ---- compiled functions -------------------------------------------
    def _draft_prefill_fn(self, params, cache, ids, slot, prompt_len):
        return functional_apply(self.draft, "prefill", params, ids,
                                cache, slot, prompt_len)

    def _warped_probs(self, logits, temps, top_ps):
        """The engine sampler's warping (temperature + static top-k +
        per-slot top-p) as a PROBABILITY vector — the p and q the
        rejection rule compares must be the distributions actually
        sampled from, not the raw softmaxes.  logits [N, V] f32;
        returns [N, V] probs (rows with temp<=0 are still valid — they
        are simply never read, greedy rows use argmax)."""
        s_logits, sort_idx = self.engine._warp_sorted(logits, temps,
                                                      top_ps)
        s_probs = jax.nn.softmax(s_logits, axis=-1)
        # unsort to token order: sort_idx is a permutation, so sorting
        # by it carries each probability home with no gather
        return jax.lax.sort((sort_idx, s_probs), dimension=1,
                            num_keys=1)[1]

    def _propose_from(self, logits, key, temps, top_ps):
        """One proposal from the draft's logit row: greedy slots take
        argmax, sampled slots draw from the warped distribution q.
        Returns (token [B], q [B, V])."""
        q = self._warped_probs(logits, temps, top_ps)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sampled = jax.random.categorical(
            key, jnp.log(q + 1e-38), axis=-1).astype(jnp.int32)
        return jnp.where(temps > 0, sampled, greedy), q

    def _draft_propose(self, d_params, d_cache, last_win, nprev, active,
                       key, temps, top_ps):
        """Catch-up window + K-1 single-token steps -> K draft
        proposals (greedy slots: argmax; sampled slots: drawn from the
        warped draft distribution).  Returns (drafts [B, K],
        q [B, K, V] — the proposal distributions the accept rule
        needs — d_cache, key) with the draft cache advanced past
        everything it processed (catch-up tokens AND proposals — the
        tick rolls rejected proposals back)."""
        logits_d, d_cache = functional_apply(
            self.draft, "verify_step", d_params, last_win, d_cache)
        # advance the draft past the nprev real catch-up tokens
        d_cache = d_cache.with_lengths(
            d_cache.lengths + nprev.astype(jnp.int32) * active)
        idx = jnp.maximum(nprev.astype(jnp.int32) - 1, 0)
        last_logits = jnp.take_along_axis(
            logits_d, idx[:, None, None], axis=1)[:, 0]    # [B, V]
        key, sub = jax.random.split(key)
        d_prev, q0 = self._propose_from(last_logits, sub, temps, top_ps)
        drafts, qs = [d_prev], [q0]
        for _ in range(self.k - 1):
            lg, d_cache = functional_apply(
                self.draft, "decode_step", d_params, d_prev, d_cache,
                active)
            key, sub = jax.random.split(key)
            d_prev, qi = self._propose_from(lg, sub, temps, top_ps)
            drafts.append(d_prev)
            qs.append(qi)
        return (jnp.stack(drafts, axis=1), jnp.stack(qs, axis=1),
                d_cache, key)                   # [B, K], [B, K, V]

    def _accept(self, drafts, q, logits_t, active, key, temps, top_ps):
        """The rejection rule, both temperatures in one fixed-shape
        graph.  logits_t [B, K+1, V] — target logits over
        [last_committed, d_1..d_K]; q [B, K, V] — the warped draft
        distributions the proposals were drawn from.

        Greedy rows (temp<=0): accept while ``d_i == argmax p_i`` —
        the temperature-0 limit of the rule below, kept as the exact
        argmax comparison so greedy streams stay bit-identical to the
        non-speculative engine.

        Sampled rows: position i accepts iff ``u_i * q_i(d_i) <
        p_i(d_i)`` (u ~ U[0,1); the standard min(1, p/q) acceptance),
        and the commit stream is the accepted prefix plus one token
        from the residual ``norm(max(p - q, 0))`` at the first
        rejected position — with ``q_K ≡ 0`` so a fully-accepted
        window's bonus is a plain sample from ``p_K``.  The residual
        is computed at EVERY position (fixed shapes) and gathered at
        ``n_acc``; a numerically zero residual (p == q) falls back to
        sampling p itself, which is the correct limit.

        Returns (toks [B, K+1] — the committed stream per row,
        n_acc [B], n_emit [B] = (n_acc+1)·active, key)."""
        b, kp1, v = logits_t.shape
        g = jnp.argmax(logits_t, axis=-1).astype(jnp.int32)
        match = (drafts == g[:, :self.k]).astype(jnp.int32)
        n_acc_g = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
        # warped target probs p over all K+1 positions (row-broadcast
        # of the per-slot knobs)
        t_rep = jnp.repeat(temps, kp1)
        tp_rep = jnp.repeat(top_ps, kp1)
        p = self._warped_probs(logits_t.reshape(b * kp1, v),
                               t_rep, tp_rep).reshape(b, kp1, v)
        key, k_u, k_r = jax.random.split(key, 3)
        u = jax.random.uniform(k_u, (b, self.k))
        p_d = jnp.take_along_axis(
            p[:, :self.k], drafts[:, :, None], axis=2)[:, :, 0]
        q_d = jnp.take_along_axis(q, drafts[:, :, None], axis=2)[:, :, 0]
        acc_s = (u * q_d < p_d).astype(jnp.int32)
        n_acc_s = jnp.sum(jnp.cumprod(acc_s, axis=1), axis=1)
        q_pad = jnp.concatenate([q, jnp.zeros((b, 1, v), q.dtype)],
                                axis=1)
        res = jnp.maximum(p - q_pad, 0.0)
        rsum = jnp.sum(res, axis=-1, keepdims=True)
        res = jnp.where(rsum > 0, res / jnp.maximum(rsum, 1e-38), p)
        r_tok = jax.random.categorical(
            k_r, jnp.log(res.reshape(b * kp1, v) + 1e-38),
            axis=-1).reshape(b, kp1).astype(jnp.int32)
        # sampled-row commit stream: accepted drafts, then the residual
        # draw at n_acc (positions past it are never read by the host)
        pos = jnp.arange(kp1)[None, :]
        bonus = jnp.take_along_axis(r_tok, n_acc_s[:, None], axis=1)
        d_pad = jnp.concatenate(
            [drafts, jnp.zeros((b, 1), jnp.int32)], axis=1)
        toks_s = jnp.where(pos < n_acc_s[:, None], d_pad, bonus)
        sampled_row = (temps > 0)
        toks = jnp.where(sampled_row[:, None], toks_s, g)
        n_acc = jnp.where(sampled_row, n_acc_s, n_acc_g)
        n_emit = (n_acc + 1) * active.astype(jnp.int32)
        return toks, n_acc, n_emit, key

    def _draft_rollback(self, d_cache, n_acc, active):
        """Proposals past the accepted prefix are NOT part of the
        committed stream: roll the draft's in-graph lengths back over
        them (their k/v become masked garbage, overwritten by the next
        catch-up window).  Proposal d_K was never fed back, so the
        overshoot is K-1 - n_acc, floored at 0."""
        overshoot = jnp.maximum(self.k - 1 - n_acc, 0) * \
            active.astype(jnp.int32)
        return d_cache.with_lengths(d_cache.lengths - overshoot)

    def _tick_dense_fn(self, t_params, d_params, t_cache, d_cache,
                       last_win, nprev, active, key, temps, top_ps):
        """One dense-target spec tick; returns (out [B, K+2] int32 —
        the K+1 committed-stream tokens + the committed count, ONE host
        readback — key, t_cache, d_cache)."""
        drafts, q, d_cache, key = self._draft_propose(
            d_params, d_cache, last_win, nprev, active, key, temps,
            top_ps)
        idx = jnp.maximum(nprev.astype(jnp.int32) - 1, 0)
        t0 = jnp.take_along_axis(last_win, idx[:, None], axis=1)
        window = jnp.concatenate([t0, drafts], axis=1)     # [B, K+1]
        # expert-stats scope (ISSUE 19): the collector brackets only
        # the TARGET verify — a MoE draft (possibly with a different
        # expert count) must not fold into the target's load histogram
        with _moe.collect_expert_stats() as b:
            logits_t, t_cache = functional_apply(
                self.engine.model, "verify_step", t_params, window,
                t_cache)
        moe = _moe.fold_expert_stats(b)
        toks, n_acc, n_emit, key = self._accept(
            drafts, q, logits_t, active, key, temps, top_ps)
        t_cache = t_cache.with_lengths(
            jnp.minimum(t_cache.lengths + n_emit, t_cache.capacity))
        d_cache = self._draft_rollback(d_cache, n_acc, active)
        out = jnp.concatenate([toks, n_emit[:, None]], axis=1)
        return out, key, t_cache, d_cache, moe

    def _tick_paged_fn(self, t_params, d_params, t_cache, d_cache,
                       last_win, nprev, active, tables, t_lens, key,
                       temps, top_ps):
        """Paged-target spec tick: identical flow with the target's
        window scattered through the block tables; target lengths are
        HOST state (the scheduler advances them from the readback)."""
        drafts, q, d_cache, key = self._draft_propose(
            d_params, d_cache, last_win, nprev, active, key, temps,
            top_ps)
        idx = jnp.maximum(nprev.astype(jnp.int32) - 1, 0)
        t0 = jnp.take_along_axis(last_win, idx[:, None], axis=1)
        window = jnp.concatenate([t0, drafts], axis=1)
        with _moe.collect_expert_stats() as b:
            logits_t, t_cache = functional_apply(
                self.engine.model, "verify_step_paged", t_params, window,
                t_cache, tables, t_lens)
        moe = _moe.fold_expert_stats(b)
        toks, n_acc, n_emit, key = self._accept(
            drafts, q, logits_t, active, key, temps, top_ps)
        d_cache = self._draft_rollback(d_cache, n_acc, active)
        out = jnp.concatenate([toks, n_emit[:, None]], axis=1)
        return out, key, t_cache, d_cache, moe

    # ---- host-side hooks the engine calls -----------------------------
    def on_admit(self, req, slot: int, first_tok: int):
        """A request just prefilled into `slot` on the TARGET: prefill
        the draft over the same (full) prompt and seed the catch-up
        window with the first sampled token."""
        eng = self.engine
        prompt = req.effective_prompt()
        bucket = eng._bucket_for(prompt.size)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :prompt.size] = prompt
        _, cache = eng._timed_exec(
            "prefill_ms", ("draft_prefill", bucket),
            self._draft_prefill_jit,
            self.draft_params, self.draft_cache, jnp.asarray(ids),
            np.int32(slot), np.int32(prompt.size))
        self.draft_cache = cache
        self.win[slot, :] = 0
        self.win[slot, 0] = first_tok
        self.nprev[slot] = 1

    def on_release(self, slot: int):
        """Slot retired/preempted: neutralize its spec state (the draft
        cache row resets at the next admission's prefill)."""
        self.win[slot, :] = 0
        self.nprev[slot] = 1

    def after_commit(self, slot: int, emitted: np.ndarray):
        """The scheduler committed `emitted` tokens for `slot` this
        tick: queue the suffix the draft has not processed as the next
        catch-up window.  The draft HAS the accepted proposals it fed
        itself (min(n_acc, K-1) of them); it lacks the bonus token and,
        when everything was accepted, the never-fed d_K."""
        n_emit = len(emitted)
        in_cache = min(n_emit - 1, self.k - 1)
        tail = emitted[in_cache:]
        self.win[slot, :] = 0
        self.win[slot, :len(tail)] = tail
        self.nprev[slot] = len(tail)

    def tick(self, active: np.ndarray, accum_moe: bool = True):
        """Run one spec tick over the current slots; returns the host
        readback ``out [B, K+2]`` (K+1 committed-stream tokens +
        committed count per slot).  The engine's PRNG key threads
        through the tick (sampled acceptance + residual draws) and
        advances exactly once per tick, so a seeded engine replays the
        same stream.  ``accum_moe=False`` (warmup) discards the tick's
        expert-load fold — throwaway tokens stay out of the balance
        stats."""
        eng = self.engine
        if eng.kv_layout == "paged":
            out, key, t_cache, d_cache, moe = eng._timed_exec(
                "decode_ms", ("spec_tick", 0), self._tick_paged_jit,
                eng.params, self.draft_params, eng.cache,
                self.draft_cache, jnp.asarray(self.win),
                jnp.asarray(self.nprev), jnp.asarray(active),
                jnp.asarray(eng._tables),
                jnp.asarray(eng._slot_len.astype(np.int32)),
                eng._key, jnp.asarray(eng._temps),
                jnp.asarray(eng._top_ps))
        else:
            out, key, t_cache, d_cache, moe = eng._timed_exec(
                "decode_ms", ("spec_tick", 0), self._tick_dense_jit,
                eng.params, self.draft_params, eng.cache,
                self.draft_cache, jnp.asarray(self.win),
                jnp.asarray(self.nprev), jnp.asarray(active),
                eng._key, jnp.asarray(eng._temps),
                jnp.asarray(eng._top_ps))
        eng._key = key
        eng.cache = t_cache
        self.draft_cache = d_cache
        if accum_moe:
            eng._accum_moe(moe)
        return out

    def step_hbm_bytes(self) -> int:
        """One draft decode step's HBM read traffic (params amortized
        over the batch + the dense draft KV extent) — the spec-adjusted
        decode_hbm_bytes_per_tok accounting in engine.stats."""
        pbytes = 0
        for leaf in jax.tree_util.tree_leaves(self.draft_params):
            pbytes += int(np.prod(leaf.shape)) * \
                jnp.dtype(leaf.dtype).itemsize
        dcfg = self.draft.cfg
        eng = self.engine
        kv_item = jnp.dtype(self.draft_cache.dtype).itemsize
        kv = (2 * dcfg.num_layers * eng.max_seq_len *
              dcfg.num_kv_heads * dcfg.head_dim * kv_item)
        return int(pbytes / eng.batch_slots + kv)

    def warmup(self):
        """Compile the tick executable (and one draft prefill per
        engine bucket) before traffic, then zero both caches' lengths
        — the same throwaway-token discipline as engine.warmup."""
        eng = self.engine
        for b in eng.buckets:
            ids = jnp.zeros((1, b), jnp.int32)
            _, cache = eng._timed_exec(
                "prefill_ms", ("draft_prefill", b),
                self._draft_prefill_jit,
                self.draft_params, self.draft_cache, ids,
                np.int32(0), np.int32(1))
            self.draft_cache = cache
        active = np.zeros(eng.batch_slots, np.int32)
        self.tick(active, accum_moe=False)
        # reset lengths COMMITTED to the serving mesh, exactly like
        # engine._warmup_dense: an uncommitted zeros operand is a
        # different jit cache key than the committed one the warmup
        # trace used, and the first real prefill would recompile
        def zeros():
            # one buffer per cache: both caches are donated, and a
            # shared lengths buffer would die with whichever went first
            z = jnp.zeros((eng.batch_slots,), jnp.int32)
            if eng.mesh is not None:
                try:
                    z = eng._put(eng.mesh, z, ("dp",))
                except Exception as e:
                    eng._shard_failed("spec_warmup_lengths", e)
            return z
        self.draft_cache = self.draft_cache.with_lengths(zeros())
        if eng.kv_layout != "paged":
            eng.cache = eng.cache.with_lengths(zeros())
