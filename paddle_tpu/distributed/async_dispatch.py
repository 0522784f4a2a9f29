"""Async dispatch plumbing: lazy step results + host-sync accounting.

The dispatch-bound regime (35% MFU with kernels that should
do better) comes from the HOST side of the step loop: calling
``float(loss)`` after every compiled step serializes dispatch against
device completion, so the host can never run ahead and queue work.  JAX's
async dispatch hides device latency only while nobody reads a value back.

This module is the read-back discipline:

- :class:`StepResult` wraps the device scalar a compiled step returns.
  It *is not* the number — it becomes the number (one blocking host
  transfer) only when somebody calls ``float()`` / formats / compares
  it.  ``hapi.Model.fit`` forces results only every ``log_freq``
  steps, so the steps in between are pure dispatch.
- :class:`LazyValue` defers an arbitrary zero-arg computation (metric
  ``accumulate()``) the same way.
- a process-wide **sync counter**: every forced read-back increments it,
  which is how tests prove "at most one blocking host sync per
  ``log_freq`` window" instead of hand-waving it.

Nothing here imports jax at module scope; wrapped values just need
``__float__`` (device arrays, Tensors, numpy scalars all qualify).
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Optional

__all__ = ["StepResult", "LazyValue", "host_sync_count",
           "record_host_sync", "reset_host_sync_count", "resolve"]

_lock = threading.Lock()
_SYNC_COUNT = 0
_SYNC_METRIC = None


def record_host_sync(n: int = 1) -> None:
    """Count a blocking host<-device read-back (or an explicit barrier).
    Mirrored into the unified metrics registry (host_syncs_total) under
    the same lock — the fleet loadgen drives replicas on threads, and
    an unsynchronized ``+=`` on the shared child would lose counts."""
    global _SYNC_COUNT, _SYNC_METRIC
    with _lock:
        _SYNC_COUNT += n
        if _SYNC_METRIC is None:
            from ..observability import metrics as _metrics
            _SYNC_METRIC = _metrics.counter(
                "host_syncs_total", "blocking host<-device read-backs")
        _SYNC_METRIC.inc(n)


def host_sync_count() -> int:
    return _SYNC_COUNT


def reset_host_sync_count() -> int:
    """Zero the counter, returning the old value (test bracketing)."""
    global _SYNC_COUNT
    with _lock:
        old, _SYNC_COUNT = _SYNC_COUNT, 0
    return old


class _Deferred:
    """Shared force-on-read machinery for StepResult/LazyValue."""

    _timings: Optional[dict]
    _resolved: bool
    _value: Any

    def _compute(self):  # pragma: no cover - overridden
        raise NotImplementedError

    def resolve(self):
        """Force the value (blocking host sync on first call; cached)."""
        if not self._resolved:
            t0 = time.perf_counter()
            self._value = self._compute()
            self._resolved = True
            record_host_sync()
            if self._timings is not None:
                self._timings["sync_ms"] = (
                    self._timings.get("sync_ms", 0.0)
                    + (time.perf_counter() - t0) * 1e3)
        return self._value

    # -- number protocol: anything that reads the value forces it -------
    def __float__(self):
        return float(self.resolve())

    def __int__(self):
        return int(self.resolve())

    def __bool__(self):
        return bool(self.resolve())

    def __format__(self, spec):
        v = self.resolve()
        try:
            return format(float(v), spec)
        except (TypeError, ValueError):
            return format(v, spec)

    def __repr__(self):
        if self._resolved:
            return f"{type(self).__name__}({self._value!r})"
        return f"{type(self).__name__}(<pending>)"

    def __str__(self):
        return str(self.resolve())

    def __array__(self, dtype=None):
        import numpy as np
        return np.asarray(self.resolve(), dtype=dtype)

    # NB: no __eq__/__hash__ overrides — identity semantics keep the
    # hash/eq contract intact and stop container membership tests from
    # silently forcing a per-step device sync.  Compare values
    # explicitly via float(result).
    def __lt__(self, other):
        return float(self) < other

    def __le__(self, other):
        return float(self) <= other

    def __gt__(self, other):
        return float(self) > other

    def __ge__(self, other):
        return float(self) >= other

    def __add__(self, other):
        return float(self) + other

    __radd__ = __add__

    def __sub__(self, other):
        return float(self) - other

    def __rsub__(self, other):
        return other - float(self)

    def __mul__(self, other):
        return float(self) * other

    __rmul__ = __mul__

    def __truediv__(self, other):
        return float(self) / other

    def __rtruediv__(self, other):
        return other / float(self)

    def __round__(self, ndigits=None):
        return round(float(self), ndigits)

    def __neg__(self):
        return -float(self)

    def __abs__(self):
        return abs(float(self))


class StepResult(_Deferred):
    """Lazy result of one compiled training/eval step.

    Wraps the on-device loss scalar.  Reading it (``float()``, format,
    comparison, ``numpy()``) blocks until the device produced the value —
    ONE host sync, counted — and caches the float.  Until then the host
    keeps dispatching ahead of the device.

    ``outputs`` carries the step's forward outputs (device arrays) when
    the caller requested them; they are never synced here.
    """

    __slots__ = ("_raw", "_value", "_resolved", "_timings", "outputs",
                 "_step")

    def __init__(self, loss, timings: Optional[dict] = None, outputs=None,
                 step: Optional[int] = None):
        self._raw = loss
        self._value = None
        self._resolved = False
        self._timings = timings
        self.outputs = outputs
        self._step = step                 # the train step that made it

    @property
    def loss(self):
        """The underlying device array (no sync)."""
        return self._raw

    @staticmethod
    def _unwrap(v):
        # Tensor -> its array.  Duck-typed `.data` is NOT safe here:
        # numpy values expose .data as a memoryview
        try:
            from ..core.tensor import Tensor
            if isinstance(v, Tensor):
                return v.data
        except Exception:  # pragma: no cover - core always importable
            pass
        return v

    def _compute(self):
        # the host's read of the loss: the span may close long after the
        # train_step that launched the work, and says which one that was
        from ..observability.spans import span
        data = self._unwrap(self._raw)
        with span("train_step/read", "train", step=self._step):
            try:
                return float(data)
            except (TypeError, ValueError):
                import numpy as np
                return float(np.asarray(data))

    def item(self):
        return self.resolve()

    def block_until_ready(self):
        """Barrier: wait for the device to finish this step (counted as a
        sync point; no host transfer)."""
        from ..observability.spans import span
        t0 = time.perf_counter()
        target = self._unwrap(self._raw)
        if hasattr(target, "block_until_ready"):
            with span("train_step/read", "train", step=self._step):
                target.block_until_ready()
        record_host_sync()
        if self._timings is not None:
            self._timings["sync_ms"] = (
                self._timings.get("sync_ms", 0.0)
                + (time.perf_counter() - t0) * 1e3)
        return self

    def __getattr__(self, name):
        # delegate array-ish attribute access (dtype, shape, astype, ...)
        # to the wrapped device value; never syncs by itself
        return getattr(object.__getattribute__(self, "_raw"), name)


class LazyValue(_Deferred):
    """Defer an arbitrary zero-arg computation (metric accumulate) until
    read; the first read is the (counted) host sync."""

    __slots__ = ("_fn", "_value", "_resolved", "_timings")

    def __init__(self, fn: Callable[[], Any], timings: Optional[dict] = None):
        self._fn = fn
        self._value = None
        self._resolved = False
        self._timings = timings

    def _compute(self):
        return self._fn()


def resolve(value):
    """Force a possibly-deferred value to its concrete form (floats stay
    floats, lists from multi-topk metrics stay lists)."""
    if isinstance(value, _Deferred):
        v = value.resolve()
        try:
            return float(v)
        except (TypeError, ValueError):
            return v
    return value
