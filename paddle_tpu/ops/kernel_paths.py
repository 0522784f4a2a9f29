"""Which implementation each kernel entry point took.

Every Pallas entry point in this package falls to an XLA composite on a
shape or backend its kernel does not serve.  That is right for users on
the CPU and wrong to do in silence on a chip: a "kernel" benchmark that
measured the composite looks healthy.  Each entry point therefore
records its choice here, once per TRACE (the choice is static per
executable), with the reason when it is the composite; callers — the
engine's stats, ``chip_smoke.py``, the tests — read ``counts()``.
"""
from __future__ import annotations

import threading

__all__ = ["note", "note_composite", "counts", "last_reason", "reset"]

_lock = threading.Lock()
_COUNTS: dict = {}
_REASONS: dict = {}


def note(op: str, path: str, reason: str = "") -> None:
    """Record that entry point ``op`` traced its ``path`` ('kernel' or
    'composite')."""
    with _lock:
        per_op = _COUNTS.setdefault(op, {"kernel": 0, "composite": 0})
        per_op[path] += 1
        if path == "composite":
            _REASONS[op] = reason


def note_composite(op: str, supported: bool) -> None:
    """The two reasons every shape-and-backend gate has: the kernel does
    not serve the shape, or there is no chip to run it on."""
    note(op, "composite", "backend is not tpu" if supported
         else "shape not served by the kernel")


def counts() -> dict:
    """``{op: {"kernel": n, "composite": m}}`` since the last reset."""
    with _lock:
        return {op: dict(v) for op, v in _COUNTS.items()}


def last_reason(op: str) -> str:
    """Why ``op`` last took its composite ('' if it never did)."""
    return _REASONS.get(op, "")


def reset() -> None:
    with _lock:
        _COUNTS.clear()
        _REASONS.clear()
