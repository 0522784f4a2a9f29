"""Executable observatory: per-executable cost/memory registry + HBM
ledger + roofline attribution.

The telemetry layer (metrics/spans) and the flight recorder/doctor say
*that* a step is slow; nothing says *which compiled executable* eats
the time and whether it is compute- or bandwidth-bound — the evidence
ROADMAP item 1's hardware MFU run needs to pick the next knob.  The
reference framework attributes cost per-op through its profiler/kernel
registry (PAPER.md §1 layer 0); our unit of attribution is the XLA
executable, and this registry is also the scouting party for ROADMAP
item 5's unified ``Executable`` abstraction: every entry point that
compiles something (SpmdTrainer fused step, GPipeTrainer tick, engine
prefill buckets, dense/paged decode, spec verify tick, disagg prefill
worker) registers it here.

Three pieces:

- **ExecRegistry** — one entry per compiled executable, keyed
  ``(component, key)`` where ``component`` names the owner ("engine:e0",
  "trainer:s1") and ``key`` is the owner's own executable key
  (("prefill", 128), ("fused", 1, 1), ...).  Registration happens at
  compile time (the owner's first-call branch) and captures the name /
  kind / shape key / compile wall ms / donation config / input-sharding
  summary plus ShapeDtypeStructs of the call args; runtime pairing
  (``note_runtime``) is one dict lookup + two float adds per steady
  call — ZERO host syncs, zero jax calls, so arming the registry costs
  the hot path nothing (the contract tests/test_telemetry.py asserts).
  XLA ``cost_analysis`` / ``memory_analysis`` are EXPLICITLY deferred:
  ``analyze()`` AOT re-lowers the executable from the stored shape
  structs (a compile that the persistent cache serves as a deserialize)
  — the report CLI and tests arm it; the decode loop never
  pays it and never recompiles after warmup.  Owners are held by
  WEAKREF: a dead engine's entries degrade to timing-only instead of
  pinning its params in HBM.
- **Roofline** — per-device-kind peak FLOP/s and HBM GB/s tables.  A device
  kind that is not in the tables has NO peak: asking for one raises
  UnknownDevicePeak, and a snapshot taken there carries timings and XLA
  figures without roofline fractions.  Each analyzed entry
  reports achieved FLOP/s, achieved HBM bandwidth, arithmetic
  intensity, its ridge point, compute-vs-bandwidth classification,
  fraction of its own roof, MFU, and an MFU *attribution*: the share
  of the measured wall clock it owns and the share of the gap to the
  45% target chargeable to it.
- **HBMLedger** — live device-memory accounting: params, optimizer
  state, KV pools, draft caches tracked by their owners (weakref'd, so
  dead owners fall out), plus the worst per-executable temp/peak bytes
  the analyses surfaced, against device capacity
  (``device.memory_stats()['bytes_limit']`` where the backend exposes
  it, else a per-device-kind table, else ``PADDLE_TPU_HBM_BYTES``).
  Yields a headroom gauge and the doctor's oom-risk evidence.

Knobs: ``PADDLE_TPU_EXEC_REGISTRY=0`` disables registration entirely;
``PADDLE_TPU_PEAK_FLOPS`` / ``PADDLE_TPU_PEAK_HBM_GBPS`` /
``PADDLE_TPU_HBM_BYTES`` override the device tables (tests and exotic
parts use these).
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import metrics as _metrics

__all__ = [
    "ExecEntry", "ExecRegistry", "HBMLedger", "registry", "ledger",
    "register", "note_runtime", "analyze_all", "profile",
    "profile_from_snapshot", "snapshot", "track_bytes", "tree_bytes",
    "enabled", "device_kind", "peak_flops", "peak_hbm_bytes_per_s",
    "device_hbm_capacity", "MFU_TARGET", "OOM_HEADROOM_MIN",
]

MFU_TARGET = 0.45          # the ROADMAP item 1 north star
OOM_HEADROOM_MIN = 0.08    # headroom fraction below which = oom risk
# (shared with doctor.HBM_HEADROOM_MIN so the ledger's oom_risk flag
# and the doctor's oom-risk verdict can never disagree on the line)

# peak dense bf16 FLOP/s per chip by device kind (public spec sheets).
# NB: v5e's headline 394 TFLOPS is the INT8 number; bf16 peak is 197.
# (benchmark/peaks.json is the benchmark's own copy; this one serves the
# package's roofline digests.)
PEAK_FLOPS_BF16 = {
    "v5 lite": 197e12, "v5e": 197e12,
    "v5p": 459e12, "v5": 459e12,
    "v4": 275e12,
    "v6 lite": 918e12, "v6e": 918e12,
    "v3": 61.5e12,  # per chip-half (device == core on v3)
    "v2": 22.5e12,
}

# peak HBM bandwidth per chip (GB/s, public spec sheets)
PEAK_HBM_GBPS = {
    "v5 lite": 819.0, "v5e": 819.0,
    "v5p": 2765.0, "v5": 2765.0,
    "v4": 1228.0,
    "v6 lite": 1640.0, "v6e": 1640.0,
    "v3": 900.0,
    "v2": 700.0,
}

# HBM capacity per chip (bytes) for backends whose memory_stats() is
# unavailable; same device-kind matching
HBM_CAPACITY_BYTES = {
    "v5 lite": 16 << 30, "v5e": 16 << 30,
    "v5p": 95 << 30, "v5": 95 << 30,
    "v4": 32 << 30,
    "v6 lite": 32 << 30, "v6e": 32 << 30,
    "v3": 16 << 30,
    "v2": 8 << 30,
}


class UnknownDevicePeak(LookupError):
    """No peak is tabled for this device kind (a host backend, a new
    chip): utilization against it cannot be stated."""


def enabled() -> bool:
    return os.environ.get("PADDLE_TPU_EXEC_REGISTRY", "1") != "0"


def device_kind() -> str:
    try:
        import jax
        return getattr(jax.devices()[0], "device_kind", "").lower()
    except Exception:
        return ""


def _kind_lookup(table: Dict[str, float], kind: Optional[str]
                 ) -> Optional[float]:
    kind = (kind if kind is not None else device_kind()).lower()
    for key in sorted(table, key=len, reverse=True):
        if key in kind:
            return table[key]
    return None


def _peak(table: Dict[str, float], kind: Optional[str], env: str,
          what: str) -> float:
    override = os.environ.get(env)
    if override:
        return float(override)
    kind = kind if kind is not None else device_kind()
    hit = _kind_lookup(table, kind)
    if hit is None:
        raise UnknownDevicePeak(
            f"no {what} tabled for device kind {kind!r}; add it to "
            f"exec_registry or pin it with {env}")
    return hit


def peak_flops(kind: Optional[str] = None) -> float:
    """Peak dense bf16 FLOP/s for a device kind (PADDLE_TPU_PEAK_FLOPS
    overrides); an unknown kind raises UnknownDevicePeak."""
    return _peak(PEAK_FLOPS_BF16, kind, "PADDLE_TPU_PEAK_FLOPS",
                 "peak FLOP/s")


def peak_hbm_bytes_per_s(kind: Optional[str] = None) -> float:
    """Peak HBM bytes/s (PADDLE_TPU_PEAK_HBM_GBPS overrides); an
    unknown kind raises UnknownDevicePeak."""
    return _peak(PEAK_HBM_GBPS, kind, "PADDLE_TPU_PEAK_HBM_GBPS",
                 "peak HBM GB/s") * 1e9


def device_hbm_capacity() -> Optional[int]:
    """Device memory capacity in bytes: PADDLE_TPU_HBM_BYTES override,
    else the runtime's own memory_stats()['bytes_limit'], else the
    per-kind table, else None (host backends — unknown)."""
    env = os.environ.get("PADDLE_TPU_HBM_BYTES")
    if env:
        return int(float(env))
    try:
        import jax
        dev = jax.local_devices()[0]
        ms = dev.memory_stats() if hasattr(dev, "memory_stats") else None
        if ms and ms.get("bytes_limit"):
            return int(ms["bytes_limit"])
    except Exception:
        pass
    hit = _kind_lookup(HBM_CAPACITY_BYTES, None)
    return int(hit) if hit else None


def tree_bytes(tree) -> int:
    """Host-side byte count of a pytree of arrays (shape/dtype math
    only — never syncs, never touches device data)."""
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(np.prod(shape)) * np.dtype(dtype).itemsize
    return int(total)


def _sds(a):
    """A leaf's ShapeDtypeStruct (sharding-preserving when the leaf is
    a committed jax.Array) — what analyze() re-lowers from, so the
    registry never keeps device buffers alive."""
    import jax
    shape = getattr(a, "shape", None)
    dtype = getattr(a, "dtype", None)
    if shape is None or dtype is None:
        return a
    sh = getattr(a, "sharding", None)
    if sh is not None:
        try:
            return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sh)
        except Exception:
            pass
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _sharding_summary(args) -> List[str]:
    """Compact per-arg sharding strings for registered call args (first
    leaf of each arg; replicated/single-device collapse to 'single')."""
    import jax
    out = []
    for a in args:
        leaves = jax.tree_util.tree_leaves(a)
        if not leaves:
            out.append("-")
            continue
        sh = getattr(leaves[0], "sharding", None)
        if sh is None:
            out.append("host")
        else:
            s = str(sh)
            out.append("single" if "SingleDevice" in s else s[:120])
    return out


class ExecEntry:
    """One compiled executable's observatory record."""

    def __init__(self, component: str, key, kind: str, name: str,
                 donate_argnums=(), meta: Optional[dict] = None):
        self.component = component
        self.key = key
        self.kind = kind
        self.name = name
        self.donate_argnums = tuple(donate_argnums or ())
        self.meta = dict(meta or {})
        self.created = time.time()
        self.compile_ms: Optional[float] = None
        # steady-state pairing (note_runtime): GIL-atomic adds only
        self.calls = 0
        self.runtime_ms = 0.0
        # deferred XLA analysis
        self.analysis: Optional[dict] = None
        self.analysis_error: Optional[str] = None
        self.in_shardings: List[str] = []
        self._jit_ref = None            # weakref to the jitted callable
        self._arg_shapes = None         # SDS pytree for analyze()

    @property
    def alive(self) -> bool:
        return self._jit_ref is not None and self._jit_ref() is not None


class ExecRegistry:
    """Process-wide executable registry (one instance — ``registry()``;
    tests may build private ones)."""

    _CAP = 1024     # safety bound; dead-owner entries evicted first

    def __init__(self):
        self._entries: Dict[Tuple[str, Any], ExecEntry] = {}
        self._lock = threading.Lock()
        self._m_registered = _metrics.counter(
            "exec_registered_total", "executables joined the registry",
            labels=("kind",))
        self._m_failures = _metrics.counter(
            "exec_analysis_failures_total",
            "executable cost/memory analyses that degraded to "
            "timing-only", labels=("stage",))

    # ---- registration (compile-time; cheap) ---------------------------
    def register(self, component: str, key, kind: str, jitfn=None,
                 args=(), donate_argnums=(), meta: Optional[dict] = None,
                 name: Optional[str] = None) -> Optional[ExecEntry]:
        """Join one executable at compile time.  Call BEFORE invoking
        the executable so the arg shape structs are captured while the
        (possibly donated) buffers are still readable.  Idempotent per
        (component, key)."""
        if not enabled():
            return None
        k = (component, key)
        e = self._entries.get(k)
        if e is not None:
            return e
        e = ExecEntry(component, key, kind,
                      name or _default_name(key, kind),
                      donate_argnums=donate_argnums, meta=meta)
        try:
            import jax
            if jitfn is not None:
                e._jit_ref = weakref.ref(jitfn)
            e._arg_shapes = jax.tree_util.tree_map(_sds, tuple(args))
            e.in_shardings = _sharding_summary(args)
        except Exception as exc:   # registration must never take a step
            e.analysis_error = (f"register: {type(exc).__name__}: "
                                f"{str(exc)[:200]}")
        with self._lock:
            if k not in self._entries:
                if len(self._entries) >= self._CAP:
                    self._evict_dead_locked()
                self._entries[k] = e
        self._m_registered.labels(kind=kind).inc()
        return e

    def _evict_dead_locked(self):
        dead = [k for k, e in self._entries.items() if not e.alive]
        for k in dead[:max(len(self._entries) - self._CAP + 1,
                           len(dead) // 2)]:
            self._entries.pop(k, None)
        while len(self._entries) >= self._CAP:    # all alive: drop oldest
            self._entries.pop(next(iter(self._entries)))

    def note_compile(self, component: str, key, dt_ms: float):
        e = self._entries.get((component, key))
        if e is not None and e.compile_ms is None:
            e.compile_ms = dt_ms

    def note_runtime(self, component: str, key, dt_ms: float):
        """Steady-state pairing: one dict lookup + two adds.  The hot
        decode tick / train step calls this — nothing heavier belongs
        here."""
        e = self._entries.get((component, key))
        if e is not None:
            e.calls += 1
            e.runtime_ms += dt_ms

    # ---- deferred analysis --------------------------------------------
    def analyze(self, e: ExecEntry) -> bool:
        """AOT re-lower + compile from the stored shape structs and
        fold in XLA cost/memory analysis.  EXPLICIT and off the hot
        path: the compile it costs is served by the persistent cache as
        a deserialize, and a backend where any stage fails degrades the
        entry to timing-only (exec_analysis_failures_total counts it)
        instead of raising."""
        if e.analysis is not None:
            return True
        jitfn = e._jit_ref() if e._jit_ref is not None else None
        if jitfn is None or e._arg_shapes is None:
            self._m_failures.labels(stage="owner_released").inc()
            e.analysis_error = e.analysis_error or "owner released"
            return False
        try:
            # re-lower under the entry's own mesh, as its first call
            # traced: trace-time decisions that read the ambient mesh
            # (the Pallas kernels' shard_map wrappers) must repeat, or
            # the program lowered here is not the one that runs
            mesh = self._entry_mesh(e)
            guard = contextlib.nullcontext()
            if mesh is not None:
                from ..distributed.mesh import compile_mesh_guard
                guard = compile_mesh_guard(mesh)
            with guard:
                compiled = jitfn.lower(
                    *self._normalized_arg_shapes(e, mesh)).compile()
        except Exception as exc:
            self._m_failures.labels(stage="lower_compile").inc()
            e.analysis_error = (f"lower_compile: {type(exc).__name__}: "
                                f"{str(exc)[:200]}")
            return False
        from ..profiler import cost_stats, memory_stats
        cost = cost_stats(compiled)
        mem = memory_stats(compiled)
        out_sh: List[str] = []
        try:
            outs, _ = compiled.output_shardings \
                if isinstance(compiled.output_shardings, tuple) and \
                len(compiled.output_shardings) == 2 and \
                isinstance(compiled.output_shardings[1], dict) \
                else (compiled.output_shardings, None)
            import jax
            for sh in jax.tree_util.tree_leaves(outs)[:4]:
                s = str(sh)
                out_sh.append("single" if "SingleDevice" in s else s[:120])
        except Exception:
            pass
        e.analysis = {"cost": cost, "memory": mem,
                      "out_shardings": out_sh,
                      # Pallas kernels in the COMPILED program (each
                      # lowers to a tpu_custom_call): the evidence that
                      # a kernel, not its composite, is what runs
                      "tpu_custom_calls":
                          compiled.as_text().count("tpu_custom_call")}
        # pod-scale serving (ISSUE 18): an entry that compiled against a
        # multi-device (sub)mesh folds in its collective traffic, split
        # per MESH AXIS — the tp/dp attribution the doctor reads.  Diagnostics only: any failure leaves the
        # cost/memory analysis intact and counts in the failure metric.
        shape = ((e.meta or {}).get("submesh") or {}).get("shape") or {}
        if any(int(n) > 1 for n in shape.values()):
            try:
                from ..utils import comm_stats as _comm
                e.analysis["collectives"] = _comm.analyze_compiled(
                    compiled,
                    axis_groups=_comm.axis_groups_from_shape(shape))
            except Exception:
                self._m_failures.labels(stage="collectives").inc()
        if not cost and not mem:
            # both analyses degraded (profiler counted each); entry
            # stays timing-only but records why
            e.analysis_error = e.analysis_error or \
                "cost_analysis/memory_analysis unavailable"
        return True

    @staticmethod
    def _entry_mesh(e: ExecEntry):
        """The multi-device (sub)mesh an entry compiled against, rebuilt
        from its meta, or None for a single-device entry."""
        sub = (e.meta or {}).get("submesh") or {}
        shape, dev_ids = sub.get("shape") or {}, sub.get("devices") or []
        if len(dev_ids) <= 1:
            return None
        import jax
        from jax.sharding import Mesh
        by_id = {d.id: d for d in jax.devices()}
        return Mesh(
            np.asarray([by_id[i] for i in dev_ids]).reshape(
                [int(n) for n in shape.values()]),
            tuple(shape.keys()))

    @staticmethod
    def _normalized_arg_shapes(e: ExecEntry, mesh):
        """Arg structs safe to AOT-lower.  A first call mixes
        mesh-committed operands (params, cache) with host-resident ones
        (the first token batch, lr, the step number), and ``lower()``
        rejects the mixed device sets it would accept at runtime: every
        leaf that does not already span the entry's mesh is committed
        as REPLICATED on it — which is where GSPMD puts those operands
        at runtime anyway."""
        if mesh is None:
            return e._arg_shapes
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        repl = NamedSharding(mesh, PartitionSpec())
        dev_set = {d.id for d in mesh.devices.flat}

        def fix(leaf):
            if not isinstance(leaf, jax.ShapeDtypeStruct):
                return leaf
            sh = leaf.sharding
            ids = {d.id for d in sh.device_set} if sh is not None \
                else set()
            if ids == dev_set:
                return leaf
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                        sharding=repl)
        return jax.tree_util.tree_map(fix, e._arg_shapes)

    def analyze_all(self, component: Optional[str] = None) -> int:
        """Analyze every (matching) entry; returns how many have
        analysis afterwards."""
        n = 0
        for e in self.entries(component):
            if self.analyze(e):
                n += 1
        return n

    def entries(self, component: Optional[str] = None) -> List[ExecEntry]:
        with self._lock:
            es = list(self._entries.values())
        if component is not None:
            es = [e for e in es if e.component == component]
        return es

    def clear(self):
        with self._lock:
            self._entries.clear()

    # ---- roofline snapshot --------------------------------------------
    def _entry_snapshot(self, e: ExecEntry, pf: Optional[float],
                        pb: Optional[float]) -> dict:
        mean_ms = (e.runtime_ms / e.calls) if e.calls else None
        d = {
            "component": e.component, "name": e.name, "kind": e.kind,
            "key": str(e.key), "calls": e.calls,
            "runtime_ms": round(e.runtime_ms, 3),
            "mean_ms": round(mean_ms, 4) if mean_ms is not None else None,
            "compile_ms": round(e.compile_ms, 2)
            if e.compile_ms is not None else None,
            "donate_argnums": list(e.donate_argnums),
            "in_shardings": e.in_shardings,
            "analyzed": e.analysis is not None,
        }
        if e.meta:
            d["meta"] = dict(e.meta)
        if e.analysis_error:
            d["analysis_error"] = e.analysis_error
        if e.analysis is None:
            return d
        cost = e.analysis.get("cost") or {}
        mem = e.analysis.get("memory") or {}
        d["flops"] = cost.get("flops")
        d["bytes_accessed"] = cost.get("bytes_accessed")
        for fld in ("argument_bytes", "output_bytes", "temp_bytes",
                    "alias_bytes", "peak_bytes"):
            if fld in mem:
                d[fld] = int(mem[fld])
        if e.analysis.get("out_shardings"):
            d["out_shardings"] = e.analysis["out_shardings"]
        if e.analysis.get("collectives"):
            d["collectives"] = e.analysis["collectives"]
        if "tpu_custom_calls" in e.analysis:
            d["tpu_custom_calls"] = e.analysis["tpu_custom_calls"]
        if pf is None:
            return d            # no peak for this device: no fractions
        flops = cost.get("flops") or 0.0
        nbytes = cost.get("bytes_accessed") or 0.0
        if mean_ms and mean_ms > 0:
            sec = mean_ms / 1e3
            if flops:
                ach_f = flops / sec
                d["achieved_flops_per_s"] = round(ach_f, 1)
                d["mfu"] = round(ach_f / pf, 6)
            if nbytes:
                ach_b = nbytes / sec
                d["achieved_hbm_gbps"] = round(ach_b / 1e9, 3)
                d["hbm_bw_frac"] = round(ach_b / pb, 6)
        if flops and nbytes:
            ai = flops / nbytes
            ridge = pf / pb
            d["arithmetic_intensity"] = round(ai, 3)
            d["ridge_ai"] = round(ridge, 3)
            d["bound"] = "compute" if ai >= ridge else "bandwidth"
            if mean_ms and mean_ms > 0:
                # the roof this executable can reach at ITS intensity
                roof = min(pf, ai * pb)
                d["roof_frac"] = round((flops / (mean_ms / 1e3)) / roof, 6)
        return d

    def snapshot(self, component: Optional[str] = None,
                 analyze: bool = False) -> dict:
        """JSON-safe observatory snapshot: per-executable records with
        roofline positions plus the MFU attribution (time share × gap
        to the 45% target).  ``analyze=True`` first runs the deferred
        XLA analyses (compiles — keep it off hot paths)."""
        if analyze:
            self.analyze_all(component)
        kind = device_kind()
        try:
            pf, pb = peak_flops(kind), peak_hbm_bytes_per_s(kind)
        except UnknownDevicePeak:
            pf = pb = None
        es = self.entries(component)
        rows = [self._entry_snapshot(e, pf, pb) for e in es]
        rows.sort(key=lambda r: -(r["runtime_ms"] or 0.0))
        total_rt = sum(r["runtime_ms"] for r in rows) or 0.0
        total_flops = 0.0
        for r in rows:
            if total_rt > 0:
                r["time_share"] = round(r["runtime_ms"] / total_rt, 4)
                mfu = r.get("mfu")
                if mfu is not None:
                    # this executable's charge against the gap to 45%:
                    # the wall-clock share it owns, scaled by how far
                    # below target it runs while owning it
                    r["mfu_weighted"] = round(r["time_share"] * mfu, 6)
                    r["gap_share"] = round(
                        r["time_share"] *
                        max(MFU_TARGET - mfu, 0.0) / MFU_TARGET, 4)
                    total_flops += (r.get("flops") or 0.0) * r["calls"]
        overall_mfu = (total_flops / (total_rt / 1e3) / pf) \
            if pf and total_rt > 0 and total_flops else None
        out = {
            "device_kind": kind or "host",
            "peak_flops": pf,
            "peak_hbm_gbps": round(pb / 1e9, 1) if pb else None,
            "peaks_known": pf is not None,
            "mfu_target": MFU_TARGET,
            "executables": rows,
            "overall": {
                "runtime_ms": round(total_rt, 3),
                "analyzed": sum(1 for r in rows if r["analyzed"]),
                "registered": len(rows),
                "mfu": round(overall_mfu, 6)
                if overall_mfu is not None else None,
            },
        }
        self._export_gauges(rows)
        return out

    def _export_gauges(self, rows: List[dict]):
        """Mirror the observatory into Prometheus gauges (scrape-time
        cost only; never called from a hot loop)."""
        g_rt = _metrics.gauge("exec_runtime_ms_total",
                              "cumulative steady-state wall ms",
                              labels=("component", "exec"))
        g_calls = _metrics.gauge("exec_calls_total", "steady-state calls",
                                 labels=("component", "exec"))
        g_flops = _metrics.gauge("exec_flops", "XLA cost-analysis flops",
                                 labels=("component", "exec"))
        g_peak = _metrics.gauge("exec_peak_bytes",
                                "arg+out+temp-alias bytes",
                                labels=("component", "exec"))
        g_mfu = _metrics.gauge("exec_mfu", "achieved/peak FLOPs",
                               labels=("component", "exec"))
        for r in rows:
            lbl = dict(component=r["component"], exec=r["name"])
            g_rt.labels(**lbl).set(r["runtime_ms"])
            g_calls.labels(**lbl).set(r["calls"])
            if r.get("flops") is not None:
                g_flops.labels(**lbl).set(r["flops"])
            if r.get("peak_bytes") is not None:
                g_peak.labels(**lbl).set(r["peak_bytes"])
            if r.get("mfu") is not None:
                g_mfu.labels(**lbl).set(r["mfu"])

    def profile(self, component: str) -> Optional[dict]:
        """Per-kind roofline digest for one component — what
        ``trainer.stats['exec_profile']`` / ``engine.stats
        ['exec_profile']`` carry.  Pure dict math over
        ALREADY-analyzed entries (None when nothing is analyzed yet):
        reading stats never compiles."""
        if not any(e.analysis is not None
                   for e in self.entries(component)):
            return None
        return profile_from_snapshot(self.snapshot(component))


def profile_from_snapshot(snap: dict) -> Optional[dict]:
    """Build the per-kind exec_profile digest the doctor rules read
    from a registry snapshot — live (``ExecRegistry.profile``) or
    offline (the report CLI reloading a snapshot file).  ONE
    implementation so the two can never drift: highest-runtime analyzed
    row per kind, plus the ``_overall``/``_peaks`` context."""
    prof: Dict[str, dict] = {}
    for r in snap.get("executables") or []:
        if not r.get("analyzed") or r.get("kind") is None:
            continue
        cur = prof.get(r["kind"])
        if cur is None or (r.get("runtime_ms") or 0) > \
                (cur.get("runtime_ms") or 0):
            prof[r["kind"]] = r
    if not prof:
        return None
    prof["_overall"] = snap.get("overall")
    prof["_peaks"] = {"device_kind": snap.get("device_kind"),
                      "peak_flops": snap.get("peak_flops"),
                      "peak_hbm_gbps": snap.get("peak_hbm_gbps"),
                      "peaks_known": snap.get("peaks_known")}
    return prof


def _default_name(key, kind: str) -> str:
    if isinstance(key, tuple):
        parts = [str(p) for p in key if not (isinstance(p, int) and
                                             p == 0)]
        return "/".join(parts) if parts else kind
    return str(key)


# ---------------------------------------------------------------------------
# HBM ledger
# ---------------------------------------------------------------------------
class HBMLedger:
    """Live device-memory accounting.  ``track(owner, category, name,
    nbytes)`` records one resident allocation (params, optimizer state,
    KV pool, draft cache) under a WEAKREF to its owner — a retired
    engine's pool drops out of the ledger when the engine is collected.
    ``snapshot()`` folds in the worst per-executable temp bytes the
    exec registry analyzed and reports headroom against device
    capacity."""

    def __init__(self):
        self._tracked: Dict[Tuple[str, str], dict] = {}
        self._lock = threading.Lock()

    def track(self, owner, category: str, name: str, nbytes: int,
              **meta):
        rec = {"category": category, "name": name, "bytes": int(nbytes),
               "meta": meta or None,
               "ref": weakref.ref(owner) if owner is not None else None}
        with self._lock:
            self._tracked[(category, name)] = rec

    def untrack(self, category: str, name: str):
        with self._lock:
            self._tracked.pop((category, name), None)

    def clear(self):
        with self._lock:
            self._tracked.clear()

    def _live(self) -> List[dict]:
        with self._lock:
            recs = list(self._tracked.items())
        out = []
        dead = []
        for key, r in recs:
            if r["ref"] is not None and r["ref"]() is None:
                dead.append(key)
                continue
            out.append(r)
        if dead:
            with self._lock:
                for key in dead:
                    self._tracked.pop(key, None)
        return out

    def snapshot(self, exec_registry: Optional[ExecRegistry] = None
                 ) -> dict:
        live = self._live()
        by_cat: Dict[str, int] = {}
        for r in live:
            by_cat[r["category"]] = by_cat.get(r["category"], 0) + \
                r["bytes"]
        live_bytes = sum(by_cat.values())
        reg = exec_registry if exec_registry is not None else registry()
        exec_temp = 0
        exec_peak_name = None
        for e in reg.entries():
            mem = (e.analysis or {}).get("memory") or {}
            t = int(mem.get("temp_bytes", 0) or 0)
            if t > exec_temp:
                exec_temp, exec_peak_name = t, f"{e.component}:{e.name}"
        cap = device_hbm_capacity()
        out = {
            "capacity_bytes": cap,
            "tracked_bytes": live_bytes,
            "by_category": by_cat,
            "tracked": [{"category": r["category"], "name": r["name"],
                         "bytes": r["bytes"]} for r in live],
            "exec_temp_bytes": exec_temp,
            "exec_temp_worst": exec_peak_name,
        }
        if cap:
            headroom = cap - live_bytes - exec_temp
            out["headroom_bytes"] = int(headroom)
            out["headroom_frac"] = round(headroom / cap, 4)
            out["oom_risk"] = headroom / cap < OOM_HEADROOM_MIN
        else:
            out["headroom_bytes"] = None
            out["headroom_frac"] = None
            out["oom_risk"] = None
        g = _metrics.gauge("hbm_tracked_bytes",
                           "ledger-resident device bytes",
                           labels=("category",))
        for cat, b in by_cat.items():
            g.labels(category=cat).set(b)
        if cap:
            _metrics.gauge("hbm_capacity_bytes",
                           "device memory capacity").set(cap)
            _metrics.gauge("hbm_headroom_bytes",
                           "capacity - tracked - worst exec temp").set(
                out["headroom_bytes"])
        return out


_REGISTRY = ExecRegistry()
_LEDGER = HBMLedger()


def registry() -> ExecRegistry:
    return _REGISTRY


def ledger() -> HBMLedger:
    return _LEDGER


def register(component: str, key, kind: str, **kw):
    return _REGISTRY.register(component, key, kind, **kw)


def note_runtime(component: str, key, dt_ms: float):
    _REGISTRY.note_runtime(component, key, dt_ms)


def analyze_all(component: Optional[str] = None) -> int:
    return _REGISTRY.analyze_all(component)


def profile(component: str) -> Optional[dict]:
    return _REGISTRY.profile(component)


def snapshot(component: Optional[str] = None, analyze: bool = False
             ) -> dict:
    return _REGISTRY.snapshot(component, analyze=analyze)


def track_bytes(owner, category: str, name: str, nbytes: int, **meta):
    _LEDGER.track(owner, category, name, nbytes, **meta)
