"""Unified telemetry layer (reference: the platform-layer profiler /
monitor registry — PAPER.md §1 layer 0).

One sink, four capabilities, every entry point feeds it:

- **metrics** — process-wide registry (counters/gauges/histograms with
  labels, lock-free hot path), Prometheus text exposition + round-trip
  parser, atomic JSONL snapshots.  Fed by SpmdTrainer / GPipeTrainer
  step loops, the serving engine's decode tick, the paged allocator,
  the router, checkpoint save/restore, the compile/trace and host-sync
  counters, and the load harness.
- **spans** — ONE primitive for a host span, ``span`` (a
  ``jax.profiler.TraceAnnotation``; ``profiler.RecordEvent`` is the same
  class): the trainer's and the engine's phases (``train_step`` >
  ``/h2d`` ``/launch``, ``train_step/read``; ``tick`` > ``/admit``
  ``/launch`` ``/read`` ``/commit``) land in the profiler's own trace,
  on the device trace's clock, and, when the buffer is armed, in the
  Chrome-trace/Perfetto export beside the per-request lifecycle.
- **capture** — ``PADDLE_TPU_PROFILE=start:stop`` windows a
  jax.profiler trace over a step/tick range with zero steady-state
  overhead: the operator's route to the picture the benchmark's
  ``--trace 1`` reads (step markers, host phases, scope paths under
  ``tf_op``).
- **slo** — fleet aggregation over engine replicas + a rolling SLO
  monitor (threshold breaches, regression vs the caller's baseline).
- **flightrec** — always-on bounded black box: recent step/tick ring +
  event log dumped as an atomic post-mortem bundle (JSON + Chrome
  trace) on unhandled exception, SIGTERM, rollback, fault kill, stall.
- **watchdog** — monitor thread fed per-step/per-tick heartbeats; a
  no-progress stall dumps all-thread stacks + a flightrec bundle.
  Plus fleet straggler detection (tick-time skew vs median).
- **doctor** — rule-based bottleneck attribution over the stats the
  entry points already emit: ranked ``[{bottleneck, evidence, knob}]``
  verdicts in ``trainer.stats['doctor']`` / ``engine.stats['doctor']``
  / loadgen reports.

Invariants (proven in tests/test_telemetry.py): telemetry-on adds zero
host syncs per decode tick and keeps the decode loop zero-recompile;
telemetry-off adds no per-step allocations.
"""
from . import doctor
from . import exec_registry
from . import flightrec
from . import metrics
from . import spans
from . import watchdog
from .capture import ProfileWindow, parse_profile_spec
from .doctor import diagnose
from .exec_registry import ExecRegistry, HBMLedger
from .flightrec import FlightRecorder
from .metrics import counter, gauge, histogram, parse_exposition, registry
from .slo import FleetAggregator, SLOMonitor
from .spans import (export_chrome_trace, span, step_span, tracer,
                    validate_chrome_trace)
from .watchdog import Watchdog, detect_stragglers

__all__ = [
    "metrics", "spans", "counter", "gauge", "histogram", "registry",
    "snapshot", "write_snapshot", "parse_exposition",
    "span", "step_span", "tracer", "export_chrome_trace",
    "validate_chrome_trace",
    "ProfileWindow", "parse_profile_spec",
    "FleetAggregator", "SLOMonitor",
    "flightrec", "FlightRecorder", "watchdog", "Watchdog",
    "detect_stragglers", "doctor", "diagnose",
    "exec_registry", "ExecRegistry", "HBMLedger",
]


def snapshot() -> dict:
    """THE one-call answer: every registered train/serve/fleet metric,
    the executable observatory (per-executable cost/roofline records —
    whatever analyses have run; reading never compiles), the HBM
    ledger, and tracer state — all JSON-safe."""
    return {
        "metrics": metrics.snapshot(),
        "executables": exec_registry.snapshot(),
        "hbm": exec_registry.ledger().snapshot(),
        "spans": {"buffered": len(spans.tracer()),
                  "dropped": spans.tracer().dropped,
                  "active": spans.tracer().active},
    }


def write_snapshot(path=None, extra=None):
    """Append one FULL snapshot line (metrics + executables + hbm) to
    the JSONL history file — same atomic-rename + line/size rotation as
    metrics.write_snapshot, which this wraps.  The report CLI
    (``python -m paddle_tpu.observability.report``) renders these files
    offline."""
    full = {"executables": exec_registry.snapshot(),
            "hbm": exec_registry.ledger().snapshot()}
    if extra:
        full.update(extra)
    return metrics.write_snapshot(path, extra=full)
