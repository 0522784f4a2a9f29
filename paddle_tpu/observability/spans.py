"""Host spans: ONE primitive, on the profiler's clock.

``span`` (``profiler.RecordEvent`` is the same class under its reference
name, ``platform/profiler.h:127``) IS a ``jax.profiler.TraceAnnotation``:
entering it puts the span into the host plane of whatever profiler
session is running, on the clock of the device trace, so the program's
phases can be laid against the chip's busy intervals.  When the span
buffer below is armed the same enter/exit pair is also appended to it,
and exports as Chrome-trace JSON (``chrome://tracing`` / Perfetto's
``ui.perfetto.dev`` open it directly; the flight recorder's
``trace.json`` is built from it).  ``step_span`` is the same thing for
the two parents (``train_step``, ``tick``): a
``jax.profiler.StepTraceAnnotation``, which XProf groups by.

The names the trainer and the engine record (PERF.md section 3 has the
metric that reads each):

- ``train_step`` (``step``) > ``train_step/h2d``, ``train_step/launch``;
  ``train_step/read`` (``step``) is the host's read of the loss and may
  close after its parent.
- ``tick`` (``tick``, ``active``, ``kv_positions`` and, beside what the
  tick has to read, ``kv_positions_read``: what its kernel streams;
  ``state_bytes`` where the cache holds a recurrent state) > ``tick/admit``
  (> ``prefill`` with ``bucket``, ``prompt_tokens``), ``tick/launch``,
  ``tick/read``, ``tick/commit``.

Tracks of the buffer (Chrome-trace pid/tid):

- ``pid=1`` "host": the spans above.  ``tid`` is the emitting thread.
- ``pid=2`` "requests": one track PER REQUEST (``tid=rid``) holding its
  lifecycle, ``queued`` -> ``prefill`` -> ``decode``, plus instant
  events for preemptions.  These are built afterwards from timestamps
  the engine records anyway (``SpanTracer.complete``), not spans.

With no profiler session and the buffer off a span costs its
annotation's enter and exit and nothing else: no clock read, no dict,
no allocation beyond the object.  Recording is timestamp arithmetic and
``list.append``: no host syncs, no jax calls, so a traced decode loop
stays zero-recompile and one-sync-per-tick.

Knobs: ``PADDLE_TPU_SPANS=1`` arms the buffer at import;
``PADDLE_TPU_SPANS=<path>.json`` also names the default export path.
``PADDLE_TPU_PROFILE=<start>:<stop>`` (``capture.py``) runs a profiler
session over those steps or ticks.
"""
from __future__ import annotations

import functools
import json
import os
import threading
import time
from typing import List, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

__all__ = ["SpanTracer", "tracer", "span", "step_span",
           "export_chrome_trace", "validate_chrome_trace", "PID_HOST",
           "PID_REQUESTS"]

PID_HOST = 1
PID_REQUESTS = 2

_DEFAULT_CAPACITY = 250_000


class SpanTracer:
    """Bounded in-memory span buffer.  ``active`` is the hot-path gate:
    instrumentation reads it before building any event."""

    def __init__(self, capacity: int = _DEFAULT_CAPACITY):
        self.active = False
        self.capacity = int(capacity)
        self._events: List[dict] = []
        self.dropped = 0
        # one shared epoch so spans from every thread/component align;
        # perf_counter()/perf_counter_ns() share a clock
        self._t0_ns = time.perf_counter_ns()

    # ---- lifecycle ----------------------------------------------------
    def start(self):
        self.active = True
        return self

    def stop(self):
        self.active = False
        return self

    def clear(self):
        self._events = []
        self.dropped = 0

    def __len__(self):
        return len(self._events)

    # ---- time ---------------------------------------------------------
    def now_us(self) -> float:
        return (time.perf_counter_ns() - self._t0_ns) / 1e3

    def to_us(self, perf_counter_s: float) -> float:
        """Map a ``time.perf_counter()`` float (the repo's ubiquitous
        timestamp currency — Request.t_enqueue etc.) onto the trace
        clock."""
        return max(perf_counter_s * 1e6 - self._t0_ns / 1e3, 0.0)

    # ---- recording (host-side arithmetic only) ------------------------
    def _push(self, ev: dict):
        if len(self._events) >= self.capacity:
            self.dropped += 1
            return
        self._events.append(ev)     # list.append is GIL-atomic

    def complete(self, name: str, ts_us: float, dur_us: float,
                 pid: int = PID_HOST, tid: Optional[int] = None,
                 cat: str = "host", args: Optional[dict] = None):
        """One finished span ('X' event)."""
        ev = {"name": name, "ph": "X", "ts": round(ts_us, 3),
              "dur": round(max(dur_us, 0.0), 3), "pid": pid,
              "tid": tid if tid is not None else threading.get_ident()
              % 1_000_000, "cat": cat}
        if args:
            ev["args"] = args
        self._push(ev)

    def instant(self, name: str, pid: int = PID_HOST,
                tid: Optional[int] = None, cat: str = "host",
                args: Optional[dict] = None,
                ts_us: Optional[float] = None):
        ev = {"name": name, "ph": "i", "s": "t",
              "ts": round(self.now_us() if ts_us is None else ts_us, 3),
              "pid": pid,
              "tid": tid if tid is not None else threading.get_ident()
              % 1_000_000, "cat": cat}
        if args:
            ev["args"] = args
        self._push(ev)

    # ---- export -------------------------------------------------------
    def chrome_trace(self) -> dict:
        """The Chrome-trace document (Perfetto-compatible)."""
        meta = [
            {"name": "process_name", "ph": "M", "pid": PID_HOST, "tid": 0,
             "args": {"name": "paddle_tpu host"}},
            {"name": "process_name", "ph": "M", "pid": PID_REQUESTS,
             "tid": 0, "args": {"name": "requests"}},
        ]
        # label each request track by its rid
        rids = sorted({ev["tid"] for ev in self._events
                       if ev["pid"] == PID_REQUESTS})
        meta += [{"name": "thread_name", "ph": "M", "pid": PID_REQUESTS,
                  "tid": rid, "args": {"name": f"request {rid}"}}
                 for rid in rids]
        return {"traceEvents": meta + list(self._events),
                "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def export(self, path: str) -> str:
        """Write the Chrome-trace JSON atomically (fs.open_for_write)."""
        from ..framework.fs import open_for_write
        with open_for_write(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path


_TRACER = SpanTracer()
if os.environ.get("PADDLE_TPU_SPANS", "") not in ("", "0"):
    _TRACER.start()


def tracer() -> SpanTracer:
    return _TRACER


def default_export_path() -> Optional[str]:
    env = os.environ.get("PADDLE_TPU_SPANS", "")
    return env if env not in ("", "0", "1") else None


def _buffered(annotation, name: str, doc: str):
    """`annotation` (a profiler annotation class) with the buffer as its
    second sink.  nanobind classes take one base, so the two span
    classes are made from this one body and not from a mixin."""

    class cls(annotation):
        __slots__ = ("name", "cat", "args", "_t0")

        def __init__(self, name: str, cat: str = "host",
                     args: Optional[dict] = None, **more):
            if args:
                more = {**args, **more}
            super().__init__(name, **more)
            self.name = name
            self.cat = cat
            self.args = more
            self._t0 = None

        def __enter__(self):
            super().__enter__()
            if _TRACER.active:
                self._t0 = _TRACER.now_us()
            return self

        def __exit__(self, *exc):
            super().__exit__(*exc)
            if self._t0 is not None:
                _TRACER.complete(self.name, self._t0,
                                 _TRACER.now_us() - self._t0, cat=self.cat,
                                 args=self.args or None)
                self._t0 = None
            return False

        def note(self, **more):
            """Arguments known only after the span was entered (a tick's
            active slots): both sinks get them."""
            self.set_metadata(**more)
            self.args.update(more)

        def __call__(self, fn):
            """As a decorator: every call of `fn` is one such span."""
            @functools.wraps(fn)
            def wrapped(*a, **k):
                with type(self)(self.name, self.cat, self.args):
                    return fn(*a, **k)
            return wrapped

    cls.__name__ = cls.__qualname__ = name
    cls.__doc__ = doc
    return cls


span = _buffered(
    TraceAnnotation, "span",
    """One host span: ``with span("tick/read", tick=n): ...``.  Keyword
    arguments (or ``args``) travel with the span into the profiler's
    trace and into the buffer.  Also a decorator.""")
step_span = _buffered(
    StepTraceAnnotation, "step_span",
    """A ``span`` that the profiler's tools group by, for the parents
    ``train_step`` and ``tick``: pass ``step_num``.""")


def export_chrome_trace(path: Optional[str] = None) -> Optional[str]:
    """Export the global tracer's buffer; default path from
    ``PADDLE_TPU_SPANS=<path>``.  Returns the path or None when there is
    nowhere to write."""
    path = path or default_export_path()
    if not path:
        return None
    return _TRACER.export(path)


def validate_chrome_trace(doc) -> int:
    """Structural validation of a Chrome-trace document (the smoke's
    'the timeline actually loads' check): every event needs name/ph/pid
    /tid, 'X' events need numeric ts+dur.  Returns the event count;
    raises ValueError on the first malformed event."""
    if isinstance(doc, (str, bytes)):
        doc = json.loads(doc)
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        raise ValueError("traceEvents missing or not a list")
    for i, ev in enumerate(events):
        for k in ("name", "ph", "pid", "tid"):
            if k not in ev:
                raise ValueError(f"event {i} missing {k!r}: {ev}")
        if ev["ph"] == "X":
            if not isinstance(ev.get("ts"), (int, float)) or \
                    not isinstance(ev.get("dur"), (int, float)):
                raise ValueError(f"event {i} has non-numeric ts/dur: {ev}")
            if ev["dur"] < 0 or ev["ts"] < 0:
                raise ValueError(f"event {i} has negative ts/dur: {ev}")
    return len(events)
