"""Disaggregated prefill/decode serving (DistServe-style).

One engine interleaving prefill and decode has a structural tail
problem: a long prompt's prefill runs BETWEEN decode steps, so every
in-flight stream stalls for the whole prefill — decode p99 inflates
with prompt length even though decode work per tick is constant.
DistServe (Zhong et al.) splits the two phases onto separate resources:
prefill workers chew prompts at their own pace, decode engines tick
uninterrupted, and the KV handoff is the only coupling.

The paged block pool makes that handoff nearly free: a prefill WRITES
pool blocks, and handing the request to the decode engine is handing it
the block ids — no KV copy, no re-compute, just refcounted pointers
(exactly the currency the radix prefix cache already trades in).

Topology, two rungs:

* ``DisaggServingEngine(model)`` — SHARED-POOL disaggregation: the
  ``PrefillWorker`` holds its own compiled prefill executables over the
  same parameters and the same pool, interleaved on one device group.
  The scheduling boundary is real (the decode engine's ``step()`` never
  runs a prefill), the device boundary is not.
* ``DisaggServingEngine(model, prefill_devices=k)`` — DISJOINT device
  groups (ISSUE 18): the process device list is carved into a prefill
  group (first ``k`` devices) and a decode group (the rest), each with
  its own ``{"dp": 1, "tp": group}`` mesh.  The worker owns a SEPARATE
  copy of the parameters and a SEPARATE block pool / allocator / radix
  cache committed to the prefill mesh; the decode engine compiles
  against the decode mesh.  The KV handoff becomes a device-to-device
  block transfer: a fixed-shape gather on the prefill group, a resharding
  ``device_put`` across the group boundary, and a fixed-shape scatter
  into the decode group's pool (both executables compile once — the
  block-id rows are padded to ``blocks_per_slot``, padding rows travel
  through null block 0).

Flow per ``step()``:

1. prefill phase: up to ``prefills_per_step`` queued requests run on
   the PrefillWorker (radix-cache match -> block alloc -> suffix
   prefill -> trim + adopt into the radix tree) and park as HANDOFF
   records (req, blocks, logits);
2. admission phase: free decode slots adopt parked handoffs — under
   disjoint groups the blocks are first transferred into the decode
   pool — and sample the first token from the handed-off logits
   (``InferenceEngine.admit_handoff``);
3. decode phase: one uninterrupted decode tick (spec decoding rides
   along unchanged — the draft prefill is part of admission).
"""
from __future__ import annotations

import os
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .engine import InferenceEngine, Request
from .paged_kv import BlockAllocator, blocks_for, init_paged_cache
from .prefix_cache import RadixPrefixCache

__all__ = ["DisaggServingEngine", "PrefillWorker"]


class PrefillWorker:
    """The prefill half: its own jitted prefill executables writing
    either into the DECODE engine's shared pool (``mesh is None`` /
    the engine's own mesh) or — disjoint disaggregation — into its OWN
    pool committed to its own device-group mesh.  Either way the state
    ``domain`` (params / cache / allocator / radix cache) this worker
    exposes is what ``engine._paged_prefill`` runs against.
    Single-threaded interleave — the wrapper alternates phases, so
    cache/alloc state is never raced."""

    def __init__(self, engine: InferenceEngine, mesh=None):
        if engine.kv_layout != "paged":
            raise ValueError(
                "disaggregated prefill needs kv_layout='paged' — the "
                "KV handoff travels through the block pool")
        self.engine = engine
        self._own = mesh is not None and mesh is not engine.mesh
        self.mesh = mesh if mesh is not None else engine.mesh
        if self._own:
            # DistServe for real: a second copy of the weights and a
            # second pool, committed to the PREFILL group's mesh.  The
            # block handoff is now the only coupling to the decode side.
            try:
                self._params = engine._shard_params_over(
                    self.mesh, engine.params, engine.model)
            except Exception as e:  # pragma: no cover - degrade path
                engine._shard_failed("disagg_prefill_params", e)
                self._params = engine.params
            pool = init_paged_cache(engine.model, engine.num_blocks + 1,
                                    engine.block_size,
                                    engine._cache_dtype,
                                    kv_dtype=engine.kv_dtype)
            try:
                self._cache = engine._shard_paged_cache_arrays(
                    self.mesh, pool)
            except Exception as e:  # pragma: no cover - degrade path
                engine._shard_failed("disagg_prefill_pool", e)
                self._cache = pool
            self._own_alloc = BlockAllocator(engine.num_blocks + 1,
                                             engine.block_size)
            self._own_prefix = RadixPrefixCache(
                self._own_alloc, engine.block_size) \
                if engine._prefix is not None else None
        dargs = (1,) if engine._donate else ()
        cold_fn = engine._prefill_paged_cold_fn
        ext_fn = engine._prefill_paged_ext_fn
        if self._own:
            # distinct function identities: bound methods hash equal
            # across attribute accesses, so jax's trace cache would
            # otherwise REUSE the decode engine's traced jaxpr — fatal
            # once the MoE serve-ep dispatch bakes its concrete mesh
            # into a shard_map (the worker's group is a different
            # device set).  functools.partial hashes by identity, so
            # each wrapper traces under ITS mesh guard.
            import functools
            cold_fn = functools.partial(cold_fn)
            ext_fn = functools.partial(ext_fn)
        self._cold_jit = jax.jit(cold_fn, donate_argnums=dargs)
        self._ext_jit = jax.jit(ext_fn, donate_argnums=dargs)
        self.prefills = 0

    # ---- the state domain _paged_prefill runs against -----------------
    @property
    def params(self):
        return self._params if self._own else self.engine.params

    @property
    def cache(self):
        return self._cache if self._own else self.engine.cache

    @cache.setter
    def cache(self, value):
        if self._own:
            self._cache = value
        else:
            self.engine.cache = value

    @property
    def _alloc(self):
        return self._own_alloc if self._own else self.engine._alloc

    @property
    def _prefix(self):
        return self._own_prefix if self._own else self.engine._prefix

    def _alloc_blocks(self, n: int) -> Optional[List[int]]:
        if not self._own:
            return self.engine._alloc_blocks(n)
        if n <= 0:
            return []
        out = self._alloc.alloc(n)
        if out is None and self._prefix is not None:
            self._prefix.evict(n - self._alloc.num_free)
            out = self._alloc.alloc(n)
        return out

    def warmup(self, buckets: Optional[List[int]] = None):
        """Compile the worker's executables per bucket (transient pool
        blocks, same throwaway discipline as engine.warmup)."""
        eng = self.engine
        for b in (buckets or eng.buckets):
            n = blocks_for(b, eng.block_size)
            if n > self._alloc.capacity:
                continue
            blocks = self._alloc.alloc(n)
            assert blocks is not None, "warmup needs an empty pool"
            row = np.zeros(eng.blocks_per_slot, np.int32)
            row[:n] = blocks
            ids = jnp.zeros((1, b), jnp.int32)
            _, cache, _ = eng._timed_exec(
                "prefill_ms", ("disagg", b), self._cold_jit,
                self.params, self.cache, ids, jnp.asarray(row),
                np.int32(1), mesh=self.mesh)
            self.cache = cache
            if self._prefix is not None:
                _, cache, _ = eng._timed_exec(
                    "prefill_ms", ("disagg_ext", b), self._ext_jit,
                    self.params, self.cache, ids, jnp.asarray(row),
                    np.int32(0), np.int32(1), mesh=self.mesh)
                self.cache = cache
            self._alloc.decref(blocks)
        return self

    def try_prefill(self, req: Request):
        """Run one request's prefill; returns the handoff record
        ``(req, blocks, logits)`` — block ids in THIS worker's pool —
        or None when the pool cannot hold it yet (caller leaves it
        queued — head-of-line FIFO, same policy as engine admission).
        The match/alloc/shed/trim/adopt sequence is
        ``engine._paged_prefill`` — ONE implementation shared with
        in-engine admission, run here on the WORKER's executables over
        the WORKER's state domain."""
        rec = self.engine._paged_prefill(req, self._cold_jit,
                                         self._ext_jit, "disagg",
                                         domain=self)
        if rec is None:
            return None
        blocks, _plen, logits = rec
        self.prefills += 1
        return req, blocks, logits


class DisaggServingEngine:
    """Prefill/decode-disaggregated serving: duck-types the
    ``InferenceEngine`` driving surface (add_request / step /
    step_or_raise / has_work / run / drain / results / stats), so the
    load harness and router treat it as just another replica.

    ``prefill_devices=k`` (ISSUE 18) carves the process device list
    into REAL disjoint groups: devices ``[0, k)`` become the prefill
    mesh, the rest the decode mesh; the KV handoff then crosses the
    group boundary as a gather -> resharding device_put -> scatter
    block transfer.  ``prefill_tp``/``decode_tp`` override each
    group's tensor-parallel degree (default: the full group);
    ``prefill_ep``/``decode_ep`` (ISSUE 19) grow each group's mesh an
    'ep' axis for MoE expert parallelism — expert FFN weights shard
    over it per group and the MoE serving dispatch routes through the
    fixed-shape capacity a2a on that group's devices.  Defaults come
    from ``PADDLE_TPU_SERVE_EP`` so one env knob configures both the
    monolithic and the disaggregated topology."""

    def __init__(self, model, prefills_per_step: int = 1,
                 handoff_depth: int = 4, prefill_devices: int = 0,
                 prefill_tp: Optional[int] = None,
                 decode_tp: Optional[int] = None,
                 prefill_ep: Optional[int] = None,
                 decode_ep: Optional[int] = None, **engine_kw):
        engine_kw.setdefault("kv_layout", "paged")
        self._disjoint = int(prefill_devices) > 0
        prefill_mesh = None
        if self._disjoint:
            if engine_kw.get("mesh") is not None:
                raise ValueError(
                    "prefill_devices carves its own meshes — pass "
                    "either it or mesh=, not both")
            from ..distributed.mesh import create_mesh
            devs = list(jax.devices())
            k = int(prefill_devices)
            if k >= len(devs):
                raise ValueError(
                    f"prefill_devices={k} leaves no decode group "
                    f"(process has {len(devs)} devices)")
            n_dec = len(devs) - k
            env_ep = os.environ.get("PADDLE_TPU_SERVE_EP", "").strip()
            p_ep = int(prefill_ep if prefill_ep is not None
                       else (env_ep or 1))
            d_ep = int(decode_ep if decode_ep is not None
                       else (env_ep or 1))
            for nm, grp, ep in (("prefill", k, p_ep),
                                ("decode", n_dec, d_ep)):
                if ep < 1 or grp % ep != 0:
                    raise ValueError(
                        f"{nm}_ep={ep} does not divide the {nm} "
                        f"group ({grp} devices)")
            p_tp = int(prefill_tp or (k // p_ep))
            d_tp = int(decode_tp or (n_dec // d_ep))

            def _axes(n, tp, ep):
                axes = {"dp": n // (tp * ep), "tp": tp}
                if ep > 1:
                    axes["ep"] = ep
                return axes

            prefill_mesh = create_mesh(_axes(k, p_tp, p_ep),
                                       devices=devs[:k])
            engine_kw["mesh"] = create_mesh(_axes(n_dec, d_tp, d_ep),
                                            devices=devs[k:])
        self.decode = InferenceEngine(model, **engine_kw)
        self.worker = PrefillWorker(self.decode, mesh=prefill_mesh)
        self.prefills_per_step = int(prefills_per_step)
        self.handoff_depth = int(handoff_depth)
        self._queue: deque = deque()
        self._handoffs: deque = deque()
        self.handoffs_total = 0
        self.transfers = 0
        if self._disjoint:
            dargs = (0,) if self.decode._donate else ()
            self._gather_jit = jax.jit(self._handoff_gather_fn)
            self._scatter_jit = jax.jit(self._handoff_scatter_fn,
                                        donate_argnums=dargs)
        # telemetry: the disaggregation-specific counters ride the same
        # registry as the wrapped engine's serve_* metrics
        from ..observability import metrics as _metrics
        lbl = dict(engine=self.decode.telemetry_label)
        self._m_handoffs = _metrics.counter(
            "disagg_handoffs_total", "prefill->decode KV handoffs",
            labels=("engine",)).labels(**lbl)
        self._m_handoff_q = _metrics.gauge(
            "disagg_handoff_queue", "parked handoff records",
            labels=("engine",)).labels(**lbl)

    # ---- delegated surface --------------------------------------------
    @property
    def model(self):
        return self.decode.model

    @property
    def results(self) -> Dict[int, np.ndarray]:
        return self.decode.results

    @property
    def request_stats(self) -> Dict[int, dict]:
        return self.decode.request_stats

    @property
    def _timings(self):
        return self.decode._timings

    @property
    def _moe_load(self):
        # worker prefills accumulate into the DECODE engine's expert
        # counters (engine._accum_moe) — one combined histogram
        return self.decode._moe_load

    @property
    def _prefix(self):
        return self.worker._prefix

    @property
    def kv_layout(self):
        return self.decode.kv_layout

    @property
    def batch_slots(self):
        return self.decode.batch_slots

    @property
    def num_active(self) -> int:
        return self.decode.num_active

    @property
    def blocks_in_use(self):
        return self.decode.blocks_in_use

    @property
    def telemetry_label(self) -> str:
        return self.decode.telemetry_label

    def prefix_summary(self):
        return self.decode.prefix_summary()

    def warmup(self, buckets: Optional[List[int]] = None):
        self.decode.warmup(buckets)
        self.worker.warmup(buckets or self.decode.buckets)
        return self

    def add_request(self, prompt, **kw) -> int:
        """Queue on the WRAPPER (the decode engine's own queue stays
        empty — its admission loop never runs a prefill).  Validation
        rides the engine's add_request, then the request is lifted out."""
        rid = self.decode.add_request(prompt, **kw)
        req = self.decode._queue.pop()
        self._queue.append(req)
        return rid

    # ---- cross-group block transfer (disjoint mode) -------------------
    def _handoff_gather_fn(self, cache, row):
        """Fixed-shape gather of a slot's block rows out of the PREFILL
        pool: row is the ``blocks_per_slot``-padded block-id vector
        (padding = null block 0, whose garbage never gets read)."""
        out = [cache.k[:, row], cache.v[:, row]]
        if cache.k_scale is not None:
            out += [cache.k_scale[:, row], cache.v_scale[:, row]]
        return tuple(out)

    def _handoff_scatter_fn(self, cache, row, *rows):
        """Fixed-shape scatter of transferred block rows into the
        DECODE pool at freshly-allocated ids (padding rows land in null
        block 0 — harmless by construction)."""
        k = cache.k.at[:, row].set(rows[0])
        v = cache.v.at[:, row].set(rows[1])
        if len(rows) == 4:
            return type(cache)(k, v,
                               cache.k_scale.at[:, row].set(rows[2]),
                               cache.v_scale.at[:, row].set(rows[3]))
        return type(cache)(k, v)

    def _transfer_handoff(self, blocks) -> Optional[List[int]]:
        """Device-to-device KV handoff: gather the blocks on the
        prefill group, reshard across the group boundary, scatter into
        the decode pool.  Returns the DECODE pool block ids (slot
        refcounts taken) or None when the decode pool is full."""
        eng = self.decode
        dst = eng._alloc_blocks(len(blocks))
        if dst is None:
            return None
        row_src = np.zeros(eng.blocks_per_slot, np.int32)
        row_src[:len(blocks)] = blocks
        row_dst = np.zeros(eng.blocks_per_slot, np.int32)
        row_dst[:len(dst)] = dst
        rows = eng._timed_exec(
            "prefill_ms", ("handoff_gather", 0), self._gather_jit,
            self.worker.cache, jnp.asarray(row_src),
            mesh=self.worker.mesh)
        # the group boundary: recommit each gathered stack to the
        # decode group's pool sharding (this is the actual D2D copy)
        dims = [(None, None, "tp", None, None)] * 2 + \
            [(None, None, "tp", None)] * (len(rows) - 2)
        moved = tuple(eng._put(eng.mesh, r, d)
                      for r, d in zip(rows, dims))
        eng.cache = eng._timed_exec(
            "prefill_ms", ("handoff_scatter", 0), self._scatter_jit,
            eng.cache, jnp.asarray(row_dst), *moved)
        self.transfers += 1
        return dst

    # ---- the disaggregated step ---------------------------------------
    def _reclaim_preempted(self):
        """A decode-side preemption parks its victim on the DECODE
        engine's queue; pull it back so its resume prefill runs on the
        worker, keeping the decode path prefill-free."""
        if self.decode._queue:
            self._queue = deque(list(self.decode._queue) +
                                list(self._queue))
            self.decode._queue.clear()

    def _expire_queued(self):
        now = time.perf_counter()
        for r in [r for r in self._queue
                  if r.deadline is not None and now >= r.deadline]:
            self._queue.remove(r)
            self.decode.expire_queued_request(r, now)

    def step(self) -> int:
        """One disaggregated round: prefill phase -> handoff admission
        -> ONE pure decode tick."""
        produced = 0
        self._reclaim_preempted()
        self._expire_queued()
        # 1) prefill phase (bounded: parked handoffs hold pool blocks)
        done = 0
        while (self._queue and done < self.prefills_per_step
               and len(self._handoffs) < self.handoff_depth
               and self.decode._admitting):
            rec = self.worker.try_prefill(self._queue[0])
            if rec is None:
                break                     # pool full; head-of-line waits
            self._queue.popleft()
            self._handoffs.append(rec)
            self.handoffs_total += 1
            self._m_handoffs.inc()
            done += 1
        self._m_handoff_q.set(len(self._handoffs))
        # 2) admission: free slots adopt parked handoffs (crossing the
        #    device-group boundary first under disjoint disaggregation)
        for slot in range(self.decode.batch_slots):
            if not self._handoffs or not self.decode._admitting:
                break
            if self.decode._slots[slot] is None:
                req, blocks, logits = self._handoffs[0]
                if self._disjoint:
                    dst = self._transfer_handoff(blocks)
                    if dst is None:
                        break    # decode pool full; stays parked
                    self.worker._alloc.decref(blocks)
                    blocks = dst
                    logits = np.asarray(jax.device_get(logits))
                self._handoffs.popleft()
                self.decode.admit_handoff(req, slot, blocks, logits)
                produced += 1
        # 3) pure decode tick
        produced += self.decode.step()
        return produced

    def step_or_raise(self) -> int:
        produced = self.step()
        if (produced == 0 and self.decode.num_active == 0
                and not self._handoffs and self._queue
                and self.decode._admitting):
            raise RuntimeError(
                "admission stalled: queued requests but the prefill "
                "worker cannot place them and nothing active to retire")
        return produced

    @property
    def has_work(self) -> bool:
        return (bool(self._queue) or bool(self._handoffs)
                or self.decode.has_work)

    def run(self) -> Dict[int, np.ndarray]:
        while self.has_work:
            self.step_or_raise()
        return self.decode.results

    def generate(self, prompt, **kw) -> np.ndarray:
        rid = self.add_request(prompt, **kw)
        while rid not in self.decode.results:
            self.step_or_raise()
        return self.decode.results[rid]

    def _release_handoffs(self) -> List[Request]:
        """Return parked handoffs' blocks to the pool they live in
        (the WORKER's domain) and their requests to the caller (drain
        path)."""
        out = []
        while self._handoffs:
            req, blocks, _ = self._handoffs.popleft()
            self.worker._alloc.decref(blocks)
            out.append(req)
        return out

    def drain(self, timeout_s: Optional[float] = None) -> List[Request]:
        leftover = list(self._queue)
        self._queue.clear()
        leftover = self._release_handoffs() + leftover
        leftover = self.decode.drain(timeout_s) + leftover
        return leftover

    def check_leak_free(self):
        assert not self._handoffs, \
            "leak check requires drained handoffs"
        self.decode.check_leak_free()
        if self.worker._own:
            if self.worker._prefix is not None:
                self.worker._prefix.flush()
            self.worker._alloc.check_leak_free()

    @property
    def stats(self) -> dict:
        s = self.decode.stats
        s["disaggregated"] = True
        s["prefill_worker_prefills"] = self.worker.prefills
        s["handoffs"] = self.handoffs_total
        s["handoff_queue"] = len(self._handoffs)
        s["disjoint_groups"] = self._disjoint
        if self._disjoint:
            s["handoff_transfers"] = self.transfers
            s["prefill_mesh"] = {
                str(ax): int(n)
                for ax, n in self.worker.mesh.shape.items()}
            s["prefill_devices"] = [
                int(d.id)
                for d in np.asarray(self.worker.mesh.devices).flat]
            s["decode_devices"] = [
                int(d.id)
                for d in np.asarray(self.decode.mesh.devices).flat]
        return s
