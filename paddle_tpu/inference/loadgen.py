"""Poisson-arrival serving load harness.

A CLOSED loop (``engine.run()`` over a full queue) measures the engine
with every request enqueued up front, so the queue is always full and
the only number that comes out is peak throughput.  Real traffic is OPEN-loop —
requests arrive on their own clock whether or not the server keeps up —
and the metrics that matter are the ones a user feels: time-to-first-
token at the tail (p99), sustained tokens/sec, and how close the
slot/block pools run to exhaustion.  This module drives the engine with
exponential inter-arrival times (a Poisson process at ``rate_rps``) and
reports exactly those, consuming the engine's per-request records
(``InferenceEngine.stats['per_request']``).

Workload shape: ``SharedPrefixWorkload`` mints prompts where a fraction
share a fixed system-prompt prefix — the pattern the radix prefix cache
exists for — so the harness also measures the prefix hit rate it buys.

Everything is host-side scheduling around ``engine.step()``; the
compile-counter discipline applies unchanged (the smoke contract:
a whole Poisson run after warmup = ZERO new XLA compiles).
"""
from __future__ import annotations

import time
from typing import List, Optional

import numpy as np

from ..observability import doctor as _doctor
from ..observability import metrics as _obs_metrics
from ..observability import watchdog as _obs_watchdog
from ..observability.slo import SLOMonitor

__all__ = ["SharedPrefixWorkload", "MultiTenantWorkload", "run_loadtest",
           "run_fleet_loadtest"]


class SharedPrefixWorkload:
    """Prompt generator: with probability ``shared_frac`` a prompt is
    ``system_prefix + random tail``, otherwise fully random.  Tail and
    generation lengths are uniform over the given ranges."""

    def __init__(self, vocab_size: int, seed: int = 0,
                 shared_frac: float = 0.5, prefix_len: int = 16,
                 tail_len=(3, 12), max_new=(4, 12)):
        self._rng = np.random.RandomState(seed)
        self.vocab = int(vocab_size)
        self.shared_frac = float(shared_frac)
        self.tail_len = tail_len
        self.max_new = max_new
        self.system_prefix = self._rng.randint(
            1, self.vocab, (int(prefix_len),)).astype(np.int32)

    def sample(self):
        """Returns (prompt ids, max_new_tokens)."""
        rng = self._rng
        tail = rng.randint(1, self.vocab, (rng.randint(
            self.tail_len[0], self.tail_len[1] + 1),)).astype(np.int32)
        if rng.rand() < self.shared_frac:
            prompt = np.concatenate([self.system_prefix, tail])
        else:
            prompt = tail
        return prompt, int(rng.randint(self.max_new[0],
                                       self.max_new[1] + 1))


def run_loadtest(engine, num_requests: int, rate_rps: float,
                 workload: Optional[SharedPrefixWorkload] = None,
                 seed: int = 0, eos_id: Optional[int] = None,
                 deadline_s: Optional[float] = None,
                 slo_monitor: Optional[SLOMonitor] = None) -> dict:
    """Open-loop Poisson load test against a warmed engine.

    Arrival times are drawn up front (exponential gaps at ``rate_rps``);
    the drive loop enqueues every request whose arrival time has passed,
    then runs ``engine.step()`` — or, when the engine is fully idle,
    sleeps until the next arrival (an open-loop harness must not spin
    the decode batch on an empty engine; that would burn host time the
    real server would spend waiting on the network).

    Returns the report dict: TTFT p50/p99 (enqueue→first token, queueing
    included — that is the point of open loop), per-request decode
    tokens/sec p50, wall-clock tokens/sec, offered vs achieved request
    rate, slot/block occupancy, prefix hit rate, and preemptions.

    `deadline_s` gives every request a per-request deadline (the SLO
    column): requests past it are retired by the engine — slot and
    blocks freed — and counted in the report's ``timed_out_requests``
    instead of wedging a decode slot on an overloaded server.
    """
    workload = workload or SharedPrefixWorkload(
        getattr(engine.model.cfg, "vocab_size", 1 << 15), seed=seed)
    # cumulative engine counters are engine-LIFETIME; snapshot so the
    # report describes THIS window even on a reused engine
    t_snap = dict(engine._timings)
    _load0 = getattr(engine, "_moe_load", None)
    moe_load_snap = None if _load0 is None else _load0.copy()
    pc = engine._prefix
    # NB: the radix cache defines __len__, so an EMPTY tree is falsy —
    # the None-check must be identity, not truthiness
    pc_snap = (pc.queries, pc.hit_queries, pc.hit_blocks) \
        if pc is not None else None
    rng = np.random.RandomState(seed + 1)
    gaps = rng.exponential(1.0 / float(rate_rps), size=int(num_requests))
    arrivals = np.cumsum(gaps)
    plan = [(t,) + workload.sample() for t in arrivals]

    rids: List[int] = []
    pending = set()
    recs = {}
    # coordinated-omission correction: a request whose Poisson arrival
    # passed while the harness was blocked inside a decode step is
    # enqueued LATE — a real user's clock started at the planned
    # arrival, so that lateness belongs in its TTFT
    late_ms = {}

    def _drain():
        """Consume finished requests as they retire: their stat record
        AND their result leave the engine, so neither the engine's
        bounded per-request history (cap 4096) nor its results dict
        truncates or accumulates over an arbitrarily long run."""
        for r in [r for r in pending if r in engine.request_stats]:
            rec = engine.request_stats.pop(r)
            if rec["ttft_ms"] is not None:
                rec["ttft_ms"] = round(rec["ttft_ms"] + late_ms[r], 3)
            # the same correction for ITL: lateness delays the FIRST
            # inter-token interval the user observes — fold it there so
            # an overloaded harness can't flatter the tail
            gaps = rec.get("itl_gaps_ms")
            if gaps:
                gaps[0] = round(gaps[0] + late_ms[r], 3)
            recs[r] = rec
            engine.results.pop(r, None)
            pending.discard(r)

    t0 = time.perf_counter()
    i = 0
    while i < len(plan) or engine.has_work:
        now = time.perf_counter() - t0
        while i < len(plan) and plan[i][0] <= now:
            arrival_t, prompt, max_new = plan[i]
            rid = engine.add_request(prompt, max_new_tokens=max_new,
                                     eos_id=eos_id,
                                     deadline_s=deadline_s)
            late_ms[rid] = max(
                time.perf_counter() - t0 - arrival_t, 0.0) * 1e3
            rids.append(rid)
            pending.add(rid)
            i += 1
        if engine.has_work:
            # a wedged scheduler raises instead of busy-spinning the
            # harness (the same stall check run()/generate() use)
            engine.step_or_raise()
            _drain()
        elif i < len(plan):
            time.sleep(min(max(plan[i][0] - now, 0.0), 0.05))
    _drain()
    wall_s = time.perf_counter() - t0

    st = engine.stats
    t1 = engine._timings
    steps = max(t1["decode_steps"] - t_snap["decode_steps"], 1)
    recs = [recs[r] for r in rids if r in recs]
    ttfts = [r["ttft_ms"] for r in recs if r["ttft_ms"] is not None]
    dtps = [r["decode_tokens_per_sec"] for r in recs
            if r["decode_tokens_per_sec"]]
    # inter-token latency pooled across requests (per-token samples,
    # the CO-corrected first gaps included) — the tail chunked prefill
    # exists to fix: a monolithic admission freezes every in-flight
    # stream for the length of the longest prompt's prefill
    itl = [g for r in recs for g in r.get("itl_gaps_ms") or ()]
    total_tokens = sum(r["tokens"] for r in recs)
    report = {
        "num_requests": len(recs),
        "offered_rps": round(float(rate_rps), 3),
        "achieved_rps": round(len(recs) / wall_s, 3) if wall_s else None,
        "wall_s": round(wall_s, 3),
        "tokens_generated": total_tokens,
        "tokens_per_sec": round(total_tokens / wall_s, 2)
        if wall_s else None,
        "ttft_ms_p50": round(float(np.percentile(ttfts, 50)), 3)
        if ttfts else None,
        "ttft_ms_p99": round(float(np.percentile(ttfts, 99)), 3)
        if ttfts else None,
        "itl_ms_p50": round(float(np.percentile(itl, 50)), 3)
        if itl else None,
        "itl_ms_p99": round(float(np.percentile(itl, 99)), 3)
        if itl else None,
        "decode_tokens_per_sec_p50": round(float(np.percentile(dtps, 50)),
                                           2) if dtps else None,
        "slot_occupancy": round(
            (t1["occupancy_sum"] - t_snap["occupancy_sum"]) / steps, 4),
        "preemptions": (t1["preemptions"] - t_snap["preemptions"])
        if "preemptions" in t_snap else 0,
        # SLO column: how many requests blew their per-request deadline
        "deadline_s": deadline_s,
        "timed_out_requests": sum(
            1 for r in recs if r.get("timed_out")),
        "kv_layout": st["kv_layout"],
    }
    # SLO verdict over THIS window's corrected TTFTs (threshold from
    # PADDLE_TPU_SLO_TTFT_P99_MS / the monitor, regression vs the
    # monitor's baseline): the observability tentpole's rolling watch, reported —
    # never asserted — by the harness
    mon = slo_monitor or SLOMonitor()
    for t in ttfts:
        mon.observe(t)
    report["slo"] = mon.check()
    for k in ("kv_block_size", "kv_blocks_total"):
        if k in st:
            report[k] = st[k]
    if engine.kv_layout == "paged":
        report["block_occupancy"] = round(
            (t1["block_occupancy_sum"] - t_snap["block_occupancy_sum"])
            / steps, 4)
    if pc_snap is not None:
        dq = pc.queries - pc_snap[0]
        dh = pc.hit_queries - pc_snap[1]
        report["prefix_queries"] = dq
        report["prefix_hit_rate"] = round(dh / dq, 4) if dq else 0.0
        report["prefix_hit_blocks"] = pc.hit_blocks - pc_snap[2]
    # expert-balance columns (ISSUE 19), WINDOW-scoped like everything
    # else here: per-expert routed-token load, capacity-overflow drop
    # rate, and max/mean skew — the inputs the 'expert-imbalance'
    # doctor rule reads off the merged dict below
    if st.get("moe_num_experts"):
        assigned = (t1["moe_assigned_tokens"]
                    - t_snap.get("moe_assigned_tokens", 0.0))
        dropped = (t1["moe_dropped_tokens"]
                   - t_snap.get("moe_dropped_tokens", 0.0))
        report["moe_num_experts"] = st["moe_num_experts"]
        report["ep"] = st["ep"]
        report["moe_assigned_tokens"] = round(assigned, 1)
        report["moe_dropped_rate"] = round(dropped / assigned, 4) \
            if assigned > 0 else 0.0
        load = getattr(engine, "_moe_load", None)
        if load is not None:
            wload = load - (moe_load_snap if moe_load_snap is not None
                            else 0.0)
            report["moe_expert_load"] = [round(float(v), 1)
                                         for v in wload]
            mean = float(wload.mean())
            report["moe_load_skew"] = round(float(wload.max()) / mean,
                                            3) if mean > 0 else None
    # perf-doctor verdict for the window (observability.doctor): the
    # engine's steady signals with this window's columns layered on top
    merged = {k: v for k, v in st.items()
              if k not in ("per_request", "doctor")}
    merged.update(report)
    report["doctor"] = _doctor.diagnose(merged, kind="serve")
    return report


class MultiTenantWorkload:
    """Skewed multi-tenant traffic: ``num_tenants`` tenants, each with
    its OWN system prefix, arriving with Zipf-ish weights
    (``1/rank^skew``) — a few hot tenants dominate, a long tail of cold
    ones trickles.  This is the workload where a prefix-aware router
    earns its keep: routing a hot tenant's requests to the replica
    already holding its prefix turns N replicas into N *sharded*
    caches instead of N redundant cold ones."""

    def __init__(self, vocab_size: int, seed: int = 0,
                 num_tenants: int = 8, skew: float = 1.2,
                 prefix_len: int = 16, tail_len=(3, 12), max_new=(4, 12)):
        self._rng = np.random.RandomState(seed)
        self.vocab = int(vocab_size)
        self.tail_len = tail_len
        self.max_new = max_new
        self.prefixes = [
            self._rng.randint(1, self.vocab,
                              (int(prefix_len),)).astype(np.int32)
            for _ in range(int(num_tenants))]
        w = 1.0 / np.arange(1, num_tenants + 1) ** float(skew)
        self.weights = w / w.sum()

    def sample(self):
        """Returns (tenant id, prompt ids, max_new_tokens)."""
        rng = self._rng
        tenant = int(rng.choice(len(self.prefixes), p=self.weights))
        tail = rng.randint(1, self.vocab, (rng.randint(
            self.tail_len[0], self.tail_len[1] + 1),)).astype(np.int32)
        prompt = np.concatenate([self.prefixes[tenant], tail])
        return tenant, prompt, int(rng.randint(self.max_new[0],
                                               self.max_new[1] + 1))


def warm_fleet(router, workload, passes: int = 2):
    """Steady-state warmup: run every tenant's prefix through the
    router (closed loop, `passes` rounds) so the measured window that
    follows describes the fleet's STEADY behavior, not its cold start
    — first-touch prefix misses are unavoidable under any policy and
    land here for all of them.  Under a prefix-aware policy this also
    settles each tenant onto its home replica."""
    for _ in range(int(passes)):
        for prefix in workload.prefixes:
            # the prefix itself: admission adopts its full blocks into
            # the radix tree, which is all a later match() consults
            router.add_request(prefix, max_new_tokens=1)
    router.run()
    # consume the warmup traffic's records so the measured window's
    # bookkeeping starts clean
    for r in router.replicas:
        r.results.clear()
        r.request_stats.clear()


def run_fleet_loadtest(router, num_requests: int, rate_rps: float,
                       workload: Optional[MultiTenantWorkload] = None,
                       seed: int = 0, eos_id: Optional[int] = None,
                       deadline_s: Optional[float] = None,
                       slo_monitor: Optional[SLOMonitor] = None,
                       rpc: bool = False) -> dict:
    """Open-loop Poisson load test against a ROUTED fleet (a
    ``router.Router`` over warmed replicas) — the multi-replica twin of
    :func:`run_loadtest`.  Requests arrive on the Poisson clock, the
    router places each one (by prefix overlap, load, or round-robin —
    its policy), and every replica with work advances each drive round.

    The report adds the fleet columns the single-engine harness cannot
    have: per-replica request counts and slot occupancy, the ROUTER hit
    rate (how often cache affinity made the placement), the aggregate
    radix-cache hit rate across replicas (the number cache-aware
    routing is supposed to move), and accepted_tokens_per_tick when the
    replicas decode speculatively.

    Each replica runs on its OWN driver thread (the router only places
    requests; it never serializes the fleet): a replica's prefill work
    delays ITS streams, not the whole fleet — which is both how a real
    deployment behaves and what makes routing quality visible in the
    TTFT tail.  Engines stay single-threaded internally (one driver
    thread each; the main thread only enqueues and reads finished
    records).

    ``rpc=True`` (ISSUE 18 satellite) interposes the socket transport:
    each replica is wrapped in a ``ReplicaRPCServer``, a fresh Router
    over ``RPCReplicaProxy`` clients re-routes the same plan, and
    every placement, summary scrape and engine step crosses the
    length-prefixed JSON protocol — the wire contract replicas in
    separate processes would speak."""
    if rpc:
        from .router import ReplicaRPCServer, RPCReplicaProxy
        from .router import Router as _Router
        servers = [ReplicaRPCServer(r).start() for r in router.replicas]
        proxies = [RPCReplicaProxy(s.address) for s in servers]
        rpc_router = _Router(proxies, policy=router.policy,
                             max_load_gap=router.max_load_gap)
        try:
            report = run_fleet_loadtest(
                rpc_router, num_requests, rate_rps, workload=workload,
                seed=seed, eos_id=eos_id, deadline_s=deadline_s,
                slo_monitor=slo_monitor)
        finally:
            for p in proxies:
                p.close()
            for s in servers:
                s.stop()
        report["rpc"] = True
        return report
    replicas = router.replicas
    workload = workload or MultiTenantWorkload(
        getattr(replicas[0].model.cfg, "vocab_size", 1 << 15), seed=seed)
    t_snaps = [dict(r._timings) for r in replicas]
    pcs = [r._prefix for r in replicas]
    pc_snaps = [(pc.queries, pc.hit_queries, pc.hit_blocks)
                if pc is not None else None for pc in pcs]
    # router counters are router-LIFETIME (warm_fleet routes traffic
    # through them too): snapshot so the report describes THIS window
    rt_snap = (router.requests, router.prefix_routed, list(router.routed))
    rng = np.random.RandomState(seed + 1)
    gaps = rng.exponential(1.0 / float(rate_rps), size=int(num_requests))
    arrivals = np.cumsum(gaps)
    plan = [(t,) + workload.sample() for t in arrivals]

    pending = {}                  # (ridx, rid) -> arrival lateness ms
    order: List[tuple] = []
    recs = {}
    tenants = {}
    # fleet aggregation: the harness consumes records out of the
    # replicas (bounded history), so IT is the scrape point — corrected
    # TTFTs flow into the fleet histogram + SLO monitor as they retire
    mon = slo_monitor or SLOMonitor()
    m_ttft = _obs_metrics.histogram(
        "fleet_ttft_ms", "per-request time to first token",
        labels=("replica",))
    m_tokens = _obs_metrics.counter(
        "fleet_tokens_total", "generated tokens", labels=("replica",))

    def _drain():
        for key in [k for k in pending if k[1] in
                    replicas[k[0]].request_stats]:
            ridx, rid = key
            rec = replicas[ridx].request_stats.pop(rid)
            if rec["ttft_ms"] is not None:
                rec["ttft_ms"] = round(rec["ttft_ms"] + pending[key], 3)
                m_ttft.labels(replica=str(ridx)).observe(rec["ttft_ms"])
                mon.observe(rec["ttft_ms"])
            gaps = rec.get("itl_gaps_ms")
            if gaps:
                # arrival lateness delays the first observed
                # inter-token interval, same correction as TTFT
                gaps[0] = round(gaps[0] + pending[key], 3)
            m_tokens.labels(replica=str(ridx)).inc(rec.get("tokens", 0))
            rec["replica"] = ridx
            recs[key] = rec
            replicas[ridx].results.pop(rid, None)
            del pending[key]

    import threading
    stop = threading.Event()
    errors: List[BaseException] = []
    # engines are single-threaded by contract; the harness provides the
    # exclusion: each replica's step and its admissions share one lock
    # (a step's queue sweep iterates the deque an arrival would mutate)
    locks = {id(r): threading.Lock() for r in replicas}

    def _drive(replica):
        # one thread per replica: step while there is work, otherwise
        # yield — mirrors N independent serving processes
        lock = locks[id(replica)]
        try:
            while not stop.is_set():
                if replica.has_work:
                    with lock:
                        replica.step_or_raise()
                else:
                    time.sleep(0.001)
        except BaseException as e:  # surface replica crashes to caller
            errors.append(e)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=_drive, args=(r,), daemon=True)
               for r in replicas]
    for th in threads:
        th.start()
    i = 0
    try:
        while i < len(plan) or router.has_work or pending:
            if errors:
                raise errors[0]
            now = time.perf_counter() - t0
            while i < len(plan) and plan[i][0] <= now:
                arrival_t, tenant, prompt, max_new = plan[i]
                # route outside the lock (reads only), enqueue inside
                ridx = router.route(prompt)
                with locks[id(replicas[ridx])]:
                    rid = replicas[ridx].add_request(
                        prompt, max_new_tokens=max_new, eos_id=eos_id,
                        deadline_s=deadline_s)
                late = max(time.perf_counter() - t0 - arrival_t,
                           0.0) * 1e3
                pending[(ridx, rid)] = late
                order.append((ridx, rid))
                tenants[(ridx, rid)] = tenant
                i += 1
            _drain()
            if i < len(plan):
                time.sleep(min(max(plan[i][0] - now, 0.0), 0.005))
            else:
                time.sleep(0.001)
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=10.0)
    _drain()
    wall_s = time.perf_counter() - t0

    recs_l = [recs[k] for k in order if k in recs]
    ttfts = [r["ttft_ms"] for r in recs_l if r["ttft_ms"] is not None]
    itl = [g for r in recs_l for g in r.get("itl_gaps_ms") or ()]
    total_tokens = sum(r["tokens"] for r in recs_l)
    # per-replica occupancy + aggregate prefix hit rate over THIS window
    occ = []
    steps_total = 0
    preemptions = 0
    pq = ph = 0
    spec_committed = spec_slot_ticks = 0
    moe_assigned = moe_dropped = 0.0
    tick_ms: List[Optional[float]] = []
    for r, snap, pc, pcs0 in zip(replicas, t_snaps, pcs, pc_snaps):
        t1 = r._timings
        d_steps = t1["decode_steps"] - snap["decode_steps"]
        steps = max(d_steps, 1)
        steps_total += d_steps
        occ.append(round(
            (t1["occupancy_sum"] - snap["occupancy_sum"]) / steps, 4))
        # per-replica mean decode-tick wall time over THIS window — the
        # straggler detector's input
        tick_ms.append((t1["decode_ms"] - snap["decode_ms"]) / d_steps
                       if d_steps > 0 else None)
        preemptions += t1.get("preemptions", 0) - snap.get("preemptions",
                                                           0)
        spec_committed += t1["spec_tokens_committed"] - \
            snap["spec_tokens_committed"]
        spec_slot_ticks += t1["spec_slot_ticks"] - snap["spec_slot_ticks"]
        moe_assigned += (t1.get("moe_assigned_tokens", 0.0)
                         - snap.get("moe_assigned_tokens", 0.0))
        moe_dropped += (t1.get("moe_dropped_tokens", 0.0)
                        - snap.get("moe_dropped_tokens", 0.0))
        if pcs0 is not None:
            pq += pc.queries - pcs0[0]
            ph += pc.hit_queries - pcs0[1]
    report = {
        "num_requests": len(recs_l),
        "num_replicas": len(replicas),
        "policy": router.policy,
        "offered_rps": round(float(rate_rps), 3),
        "achieved_rps": round(len(recs_l) / wall_s, 3) if wall_s else None,
        "wall_s": round(wall_s, 3),
        "tokens_generated": total_tokens,
        "tokens_per_sec": round(total_tokens / wall_s, 2)
        if wall_s else None,
        "ttft_ms_p50": round(float(np.percentile(ttfts, 50)), 3)
        if ttfts else None,
        "ttft_ms_p99": round(float(np.percentile(ttfts, 99)), 3)
        if ttfts else None,
        "itl_ms_p50": round(float(np.percentile(itl, 50)), 3)
        if itl else None,
        "itl_ms_p99": round(float(np.percentile(itl, 99)), 3)
        if itl else None,
        "replica_occupancy": occ,
        "requests_per_replica": [n - n0 for n, n0 in
                                 zip(router.routed, rt_snap[2])],
        "router_hit_rate": round(
            (router.prefix_routed - rt_snap[1]) /
            max(router.requests - rt_snap[0], 1), 4),
        "prefix_queries": pq,
        "prefix_hit_rate": round(ph / pq, 4) if pq else 0.0,
        "preemptions": preemptions,
        "deadline_s": deadline_s,
        "timed_out_requests": sum(1 for r in recs_l if r.get("timed_out")),
        "decode_steps": steps_total,
        "tenants_seen": len(set(tenants.values())),
    }
    if spec_slot_ticks:
        report["accepted_tokens_per_tick"] = round(
            spec_committed / spec_slot_ticks, 3)
    if moe_assigned:
        # fleet-aggregate expert balance (ISSUE 19): routed-token and
        # overflow totals summed over the window across replicas
        report["moe_assigned_tokens"] = round(moe_assigned, 1)
        report["moe_dropped_rate"] = round(moe_dropped / moe_assigned, 4)
    # straggler verdict: per-replica tick-time skew vs the fleet median
    # (observability.watchdog; PADDLE_TPU_STRAGGLER_FACTOR) — a routed
    # fleet is only as fast as its slowest member, so the report says
    # WHICH member that is instead of burying it in a mean
    report["straggler"] = _obs_watchdog.detect_stragglers(tick_ms)
    # rolling SLO verdict for the fleet window (breach + regression
    # flags; reported, never asserted)
    report["slo"] = mon.check()
    # perf-doctor verdict over the fleet columns (prefix hit rate,
    # preemptions, spec acceptance — the serving rule table)
    report["doctor"] = _doctor.diagnose(report, kind="serve")
    return report
