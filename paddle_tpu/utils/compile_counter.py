"""Process-wide XLA compile/trace counters.

PR 3 added the host-sync counter (distributed.async_dispatch) so tests
could PROVE "no per-step read-back" instead of hand-waving it; this is
the same discipline for compilation.  The serving engine's contract is
"the decode loop is recompile-free": after warmup, generating N tokens
must trigger ZERO new XLA compilations (a shape that changes per token —
the old concat-grown KV cache — would show up here as one compile per
generated token).

Counting uses ``jax.monitoring``, which jax fires around its own
compilation pipeline:

- ``/jax/core/compile/backend_compile_duration`` — one event per REAL
  XLA backend compile (persistent-cache deserializations do not fire it);
- ``/jax/core/compile/jaxpr_trace_duration`` — one event per jaxpr
  trace.  A persistent-cache hit still traces+lowers, so a decode loop
  whose shapes wobble is caught by the trace counter even when a warm
  on-disk cache hides the backend compile.

Listeners are registered lazily and exactly once; jax keeps them for the
process lifetime (there is no unregister-by-context), so the counters
are monotone — bracket a region with ``snapshot()`` and subtract.
"""
from __future__ import annotations

import contextlib
import threading

__all__ = ["install", "xla_compile_count", "xla_trace_count",
           "compile_counts", "CompileCountSnapshot", "snapshot",
           "assert_no_recompiles"]

_lock = threading.Lock()
_STATE = {"installed": False, "compiles": 0, "traces": 0}
_METRICS = {}                    # lazily-bound registry children

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


def _listener(key: str, duration: float, **kwargs) -> None:
    # registry mirror updated under the same lock: compiles can fire
    # from any thread, and += on a shared child is not atomic
    if key == _COMPILE_EVENT:
        with _lock:
            _STATE["compiles"] += 1
            _METRICS["compiles"].inc()
        # flight-recorder event log: "what compiled, when" is exactly
        # the post-mortem question a recompile-churn hang raises.
        # Compiles are rare after warmup, so this is a cold path.
        from ..observability import flightrec as _flightrec
        _flightrec.note_event("xla_compile",
                              n=_STATE["compiles"],
                              duration_s=round(float(duration), 4))
    elif key == _TRACE_EVENT:
        with _lock:
            _STATE["traces"] += 1
            _METRICS["traces"].inc()


def install() -> bool:
    """Register the monitoring listener (idempotent); the counters are
    live from here on."""
    with _lock:
        if _STATE["installed"]:
            return True
        # mirror into the unified metrics registry (observability/):
        # children bound before the listener can fire
        from ..observability import metrics as _obs_metrics
        _METRICS["compiles"] = _obs_metrics.counter(
            "xla_compiles_total", "XLA backend compiles")
        _METRICS["traces"] = _obs_metrics.counter(
            "jaxpr_traces_total", "jaxpr traces")
        from jax._src import monitoring
        monitoring.register_event_duration_secs_listener(_listener)
        _STATE["installed"] = True
        return True


def xla_compile_count() -> int:
    """Total XLA backend compiles observed in this process."""
    install()
    return _STATE["compiles"]


def xla_trace_count() -> int:
    """Total jaxpr traces observed in this process."""
    install()
    return _STATE["traces"]


def compile_counts() -> dict:
    install()
    with _lock:
        return {"xla_compiles": _STATE["compiles"],
                "jaxpr_traces": _STATE["traces"]}


class CompileCountSnapshot:
    """Bracketing helper: ``snap = snapshot(); ...; snap.new_compiles``."""

    def __init__(self):
        install()
        self._c0 = _STATE["compiles"]
        self._t0 = _STATE["traces"]

    @property
    def new_compiles(self) -> int:
        return _STATE["compiles"] - self._c0

    @property
    def new_traces(self) -> int:
        return _STATE["traces"] - self._t0


def snapshot() -> CompileCountSnapshot:
    return CompileCountSnapshot()


@contextlib.contextmanager
def assert_no_recompiles(what: str = "region", traces: bool = True):
    """Bracket a region that MUST be recompile-free (a warmed decode
    loop, a Poisson load-test window): raises AssertionError on exit if
    any XLA backend compile — or, with ``traces=True``, any jaxpr trace
    (which catches shape wobbles a warm on-disk cache would hide) —
    happened inside.  The assertion form of the snapshot()/subtract
    idiom, so tests and the serving smokes share one spelling."""
    snap = snapshot()
    yield snap
    if snap.new_compiles:
        raise AssertionError(
            f"{snap.new_compiles} XLA compile(s) inside {what} "
            f"(expected 0 — a shape or dtype wobbled)")
    if traces and snap.new_traces:
        raise AssertionError(
            f"{snap.new_traces} jaxpr trace(s) inside {what} "
            f"(expected 0 — something re-traced even if the backend "
            f"compile was cached)")
