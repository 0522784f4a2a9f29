"""Profiler — trace annotations, trace capture, per-step timing.

Reference: paddle/fluid/platform/profiler.h:127 (`RecordEvent` RAII
markers), :210 (`EnableProfiler`/`DisableProfiler` state machine),
device_tracer.h:43 (CUPTI kernel timeline -> chrome trace), python
fluid/profiler.py:131,198,255 (profiler ctx manager, start/stop).

TPU-native: XLA already timestamps every HLO on-device; what the
framework owns is (1) host-side trace annotations that show up nested
inside the device timeline (RecordEvent: observability.spans.span, a
jax.profiler.TraceAnnotation), (2) capture control writing
TensorBoard/Perfetto traces (start_trace/stop_trace == EnableProfiler ->
chrome-trace file), and
(3) cheap per-step wall timing for training loops (hapi logs
`step_time_ms` through StepTimer) — the profiler.py summary-table role.
"""
from __future__ import annotations

import contextlib
import time
from typing import Optional

import jax

# The reference's RAII marker (platform/profiler.h:127) is the program's
# one span primitive under its reference name: a profiler annotation that
# nests inside a device capture and, when the span buffer is armed, also
# lands there for the Chrome-trace export.  Context manager or decorator;
# with no capture and the buffer off it costs the annotation's enter/exit.
from .observability.spans import span as RecordEvent

__all__ = ["RecordEvent", "record_event", "profiler", "start_profiler",
           "stop_profiler", "StepTimer", "memory_stats", "cost_stats"]

_active_trace_dir: Optional[str] = None


record_event = RecordEvent


def start_profiler(log_dir: str = "/tmp/paddle_tpu_profile",
                   tracer_option: Optional[str] = None):
    """reference fluid/profiler.py:198 start_profiler /
    platform EnableProfiler: begin a capture; artifacts are a
    TensorBoard/Perfetto trace under log_dir."""
    global _active_trace_dir
    if _active_trace_dir is not None:
        raise RuntimeError("profiler already started")
    jax.profiler.start_trace(log_dir)
    _active_trace_dir = log_dir
    return log_dir


def stop_profiler(sorted_key=None, profile_path: Optional[str] = None):
    """reference fluid/profiler.py:255 stop_profiler."""
    global _active_trace_dir
    if _active_trace_dir is None:
        return None
    jax.profiler.stop_trace()
    out, _active_trace_dir = _active_trace_dir, None
    return out


@contextlib.contextmanager
def profiler(log_dir: str = "/tmp/paddle_tpu_profile", state=None,
             tracer_option=None, profile_path=None):
    """reference fluid/profiler.py:131 profiler context manager."""
    start_profiler(log_dir, tracer_option)
    try:
        yield
    finally:
        stop_profiler(profile_path=profile_path)


class StepTimer:
    """Wall-clock step statistics (the summary-table half of the
    reference profiler). tick() after each step; read .last_ms /
    .mean_ms / .p50_ms."""

    def __init__(self, warmup: int = 1):
        self.warmup = warmup
        self.times_ms = []
        self._t0 = None
        self._seen = 0

    def start(self):
        self._t0 = time.perf_counter()

    def tick(self):
        now = time.perf_counter()
        if self._t0 is not None:
            self._seen += 1
            if self._seen > self.warmup:
                self.times_ms.append((now - self._t0) * 1e3)
        self._t0 = now

    @property
    def last_ms(self):
        return self.times_ms[-1] if self.times_ms else None

    @property
    def mean_ms(self):
        return sum(self.times_ms) / len(self.times_ms) \
            if self.times_ms else None

    @property
    def p50_ms(self):
        if not self.times_ms:
            return None
        s = sorted(self.times_ms)
        return s[len(s) // 2]

    def summary(self):
        return {"steps": len(self.times_ms), "mean_ms": self.mean_ms,
                "p50_ms": self.p50_ms, "last_ms": self.last_ms}


def _analysis_degraded(stage: str, exc=None) -> dict:
    """An executable whose XLA analysis is unavailable (jaxlib CPU
    deserialized executables return None or raise) degrades to {} —
    the exec registry keeps the entry timing-only — and the failure is
    counted so a fleet dashboard can see the blind spot."""
    try:
        from .observability import metrics as _metrics
        _metrics.counter(
            "exec_analysis_failures_total",
            "executable cost/memory analyses that degraded to "
            "timing-only", labels=("stage",)).labels(stage=stage).inc()
    except Exception:
        pass
    return {}


def memory_stats(compiled) -> dict:
    """Peak-memory evidence for a compiled executable (reference
    monitor.h STAT_ADD GPU-mem stats). Works on jax.jit(...).lower(...)
    .compile() results and SpmdTrainer.step_executable.  Backends where
    ``memory_analysis()`` returns None or raises (jaxlib CPU
    deserialized executables) yield {} instead of throwing, with an
    ``exec_analysis_failures_total`` count."""
    try:
        ma = compiled.memory_analysis()
    except Exception as e:
        return _analysis_degraded("memory_analysis", e)
    if ma is None:
        return _analysis_degraded("memory_analysis")
    try:
        return {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_bytes": ma.argument_size_in_bytes +
            ma.output_size_in_bytes + ma.temp_size_in_bytes -
            ma.alias_size_in_bytes,
        }
    except Exception as e:
        return _analysis_degraded("memory_analysis", e)


def cost_stats(compiled) -> dict:
    """FLOP/byte estimates from XLA's cost analysis.  Same degradation
    contract as memory_stats: None / raising backends yield {}."""
    try:
        ca = compiled.cost_analysis()
    except Exception as e:
        return _analysis_degraded("cost_analysis", e)
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict) or ca is None:
        return _analysis_degraded("cost_analysis")
    return {"flops": ca.get("flops", 0.0),
            "bytes_accessed": ca.get("bytes accessed", 0.0)}
