"""Profile capture control: window a device trace over a step range.

The reference framework gates its profiler with an explicit
``EnableProfiler``/``DisableProfiler`` state machine (profiler.h:210);
the TPU-native equivalent is ``jax.profiler.start_trace``/``stop_trace``
writing a TensorBoard/Perfetto capture.  What neither gives you is
CONTROL tied to the training/serving clock: "capture steps 20..25" —
after warmup, long enough to see steady state, short enough to load in
a UI.

``ProfileWindow`` is that control.  ``PADDLE_TPU_PROFILE=start:stop``
(optionally ``start:stop:logdir``) arms a window; ``SpmdTrainer`` ticks
it per train step and ``InferenceEngine`` per decode tick, so the same
knob captures either.  When the env is unset ``from_env`` returns None
and the entry points hold a literal None — the steady-state cost of the
feature is one ``is not None`` check per step, no allocation, no call.

Host spans recorded while a capture is active land in its host plane
(``observability.spans.span`` is a ``jax.profiler.TraceAnnotation``),
beside the device's operations and on their clock; the chrome-trace
export of the span buffer is independent of captures and works with no
device profiler at all.
"""
from __future__ import annotations

import os
import warnings
from typing import Optional

__all__ = ["ProfileWindow", "parse_profile_spec"]

_DEFAULT_LOGDIR = "/tmp/paddle_tpu_profile"


def parse_profile_spec(spec: str):
    """``"start:stop[:logdir]"`` -> (start, stop, logdir).  Raises
    ValueError on nonsense (stop <= start, non-ints) — a mistyped env
    should fail loudly at startup, not silently never capture."""
    parts = spec.split(":", 2)
    if len(parts) < 2:
        raise ValueError(
            f"PADDLE_TPU_PROFILE must be 'start:stop[:logdir]', "
            f"got {spec!r}")
    start, stop = int(parts[0]), int(parts[1])
    if stop <= start or start < 0:
        raise ValueError(
            f"PADDLE_TPU_PROFILE window [{start}:{stop}) is empty or "
            f"negative")
    logdir = parts[2] if len(parts) > 2 and parts[2] else _DEFAULT_LOGDIR
    return start, stop, logdir


class ProfileWindow:
    """Capture device+host profile over steps [start, stop).

    ``on_step(n)`` is called with the step/tick counter AFTER the work
    of step n-1 (i.e. before step n runs): the trace starts when n ==
    start and stops when n >= stop.  One window per process lifetime —
    re-arming needs a new object (matching jax's one-trace-at-a-time
    profiler)."""

    def __init__(self, start: int, stop: int,
                 log_dir: str = _DEFAULT_LOGDIR, kind: str = "train"):
        self.start = int(start)
        self.stop = int(stop)
        self.log_dir = log_dir
        self.kind = kind
        self.active = False
        self.done = False
        self.trace_dir: Optional[str] = None

    @classmethod
    def from_env(cls, kind: str = "train",
                 env: str = "PADDLE_TPU_PROFILE"
                 ) -> Optional["ProfileWindow"]:
        spec = os.environ.get(env, "").strip()
        if not spec:
            return None
        start, stop, logdir = parse_profile_spec(spec)
        return cls(start, stop, log_dir=os.path.join(logdir, kind),
                   kind=kind)

    def on_step(self, step: int):
        """Advance the window clock.  Never raises: a broken profiler
        backend must not take the step loop down (warn once, disarm)."""
        if self.done:
            return
        if self.active:
            if step >= self.stop:
                self._stop()
        elif step >= self.start:
            if step >= self.stop:       # window already behind us
                self.done = True
                return
            self._start()

    def _start(self):
        from .. import profiler as _prof
        try:
            self.trace_dir = _prof.start_profiler(self.log_dir)
            self.active = True
        except Exception as e:          # pragma: no cover - backend dep
            warnings.warn(f"PADDLE_TPU_PROFILE capture failed to start "
                          f"({type(e).__name__}: {e}); disarmed")
            self.done = True

    def _stop(self):
        from .. import profiler as _prof
        try:
            _prof.stop_profiler()
        except Exception as e:          # pragma: no cover - backend dep
            warnings.warn(f"PADDLE_TPU_PROFILE capture failed to stop "
                          f"({type(e).__name__}: {e})")
        self.active = False
        self.done = True

    def close(self):
        """Force-stop an open capture (drain/teardown path)."""
        if self.active:
            self._stop()
