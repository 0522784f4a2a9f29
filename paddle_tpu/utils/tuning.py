"""Unified per-(device_kind, shape, dtype) tuning table.

PR 1 gave flash attention a persistent block-size autotune table
(`ops/flash_attention.py`: process cache + atomic-rename JSON, corrupt-
tolerant load).  Every tunable knob since has wanted the same thing —
quantized-matmul tile sizes, the MoE all-to-all chunk count, the
engine's prefill bucket list — and re-growing that machinery per op
would mean four slightly different cache files.  This module is the
generalization: ONE store, namespaced by op, with the flash pattern
kept exactly:

- **process cache first** — a sweep result recorded in this process is
  authoritative for the process lifetime;
- **on-disk JSON second** — ``PADDLE_TPU_TUNING_CACHE`` names the file
  ("0"/"off" disables persistence; default
  ``~/.cache/paddle_tpu/tuning.json``).  Writes go through
  ``framework.fs.open_for_write`` (fsync before atomic rename), so a
  crash can never commit a truncated table;
- **corrupt-tolerant load** — an unreadable/garbage table is treated as
  empty (the next sweep re-measures and rewrites it), never raised;
- **opt-in sweeps** — ``PADDLE_TPU_TUNING=sweep`` arms the on-device
  sweeps of ops that have one (quantized-matmul tiles today; flash
  keeps its own ``PADDLE_TPU_FLASH_AUTOTUNE=sweep`` knob for
  compatibility, recording its winners here too).

Key format on disk: ``"<op>|<part>|<part>|..."`` with parts stringified
(bools as 0/1).  Consumers:

- ``ops.flash_attention.get_block_sizes`` — op ``flash_blocks``, key
  ``(device_kind, seq, head_dim, causal)``;
- ``ops.quantized_matmul`` — op ``qmm_tiles``, key
  ``(device_kind, m_bucket, n, k, dtype)``;
- ``distributed.overlap.moe_a2a_chunks`` — op ``moe_a2a_chunks``, key
  ``(device_kind, tokens)``;
- ``inference.engine.default_prefill_buckets`` — op
  ``prefill_buckets``, key ``(device_kind, max_seq_len)``.
"""
from __future__ import annotations

import json
import math
import os
import threading
from typing import Any, Dict, Optional, Tuple

__all__ = ["lookup", "lookup_nearest", "record", "entries", "tuning_path",
           "device_kind", "normalize_kind", "sweep_enabled", "key_str",
           "reset_for_tests", "provenance", "all_entries", "META_OP"]

# provenance rides the same flat "<op>|<part>|..." disk encoding under a
# reserved op namespace: "__meta__|<orig_op>|<part>|..." -> {source, run,
# improvement}.  Old tables simply have no __meta__ keys; old readers
# see __meta__ as just another op they never look up.
META_OP = "__meta__"

_lock = threading.RLock()
# op -> {key_tuple_of_strs: value}; merged from disk once, sweeps win
_STATE: Dict[str, Any] = {"loaded": False, "cache": {}}


# ---------------------------------------------------------------------------
# device identity (shared with flash_attention, which predates this module)
# ---------------------------------------------------------------------------
def normalize_kind(kind: str) -> str:
    """Canonical short device kind ('TPU v5 lite' -> 'v5e', ...)."""
    k = (kind or "").lower()
    for alias, canon in (("v5 lite", "v5e"), ("v5litepod", "v5e"),
                         ("v5e", "v5e"), ("v5p", "v5p"),
                         ("v6 lite", "v6e"), ("v6e", "v6e"),
                         ("v4", "v4"), ("v3", "v3"), ("v2", "v2")):
        if alias in k:
            return canon
    return k


def device_kind() -> str:
    """Normalized kind of the local default device ('' when unknown)."""
    try:
        import jax
        return normalize_kind(getattr(jax.devices()[0], "device_kind", ""))
    except Exception:  # pragma: no cover
        return ""


def sweep_enabled() -> bool:
    """The generic opt-in sweep knob (flash keeps its legacy env)."""
    return os.environ.get("PADDLE_TPU_TUNING", "").strip() == "sweep"


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------
def tuning_path() -> Optional[str]:
    p = os.environ.get("PADDLE_TPU_TUNING_CACHE", "").strip()
    if p.lower() in ("0", "off", "false", "none"):
        return None
    # no path given: no table on disk — lookups use the tables in the
    # code, so what is compiled never depends on state around the
    # checkout
    return os.path.expanduser(p) if p else None


def key_str(op: str, parts) -> str:
    enc = [str(int(p)) if isinstance(p, bool) else str(p) for p in parts]
    return "|".join([op] + enc)


def _key_tuple(parts) -> Tuple[str, ...]:
    return tuple(str(int(p)) if isinstance(p, bool) else str(p)
                 for p in parts)


def _load_once() -> None:
    """Merge the on-disk table into the process cache (once); entries
    this process already recorded win over stale disk entries."""
    if _STATE["loaded"]:
        return
    _STATE["loaded"] = True
    path = tuning_path()
    if not path:
        return
    try:
        with open(path) as f:
            data = json.load(f)
        if not isinstance(data, dict):
            return
        for k, v in data.items():
            parts = str(k).split("|")
            if len(parts) < 2:
                continue
            op, key = parts[0], tuple(parts[1:])
            _STATE["cache"].setdefault(op, {}).setdefault(key, v)
    except (OSError, ValueError, TypeError):
        pass  # corrupt/unreadable table: sweep again, then rewrite it


def lookup(op: str, parts) -> Any:
    """The tuned value for (op, key) or None. Process cache first, then
    the on-disk table (loaded once per process)."""
    with _lock:
        _load_once()
        return _STATE["cache"].get(op, {}).get(_key_tuple(parts))


def lookup_nearest(op: str, parts, match_idx, near_idx,
                   max_dist: Optional[float] = None) -> Any:
    """The tuned value for (op, key), falling back to the NEAREST tabled
    shape when the exact key is missing — the flash autotuner's
    nearest-seq behaviour generalized (a sweep at seq 2048 should not
    leave seq 1920 untuned).

    Candidates must string-equal the query at every ``match_idx``
    position (device kind, dtype, causal flag, ...); distance is the
    summed ``|log(query/candidate)|`` ratio over the ``near_idx``
    positions (all numeric — shape dims), so "half the size" and "twice
    the size" are equally near.  Non-numeric candidates at a near
    position are skipped.  ``max_dist`` caps the accepted distance —
    callers whose tuned value changes behaviour materially (a remat
    policy, not a tile clamp) should bound how far an entry may travel.
    Returns the best value or None."""
    exact = lookup(op, parts)
    if exact is not None:
        return exact
    q = _key_tuple(parts)
    best, best_d = None, None
    with _lock:
        _load_once()
        table = dict(_STATE["cache"].get(op, {}))
    for key, val in table.items():
        if len(key) != len(q):
            continue
        if any(key[i] != q[i] for i in match_idx):
            continue
        try:
            d = 0.0
            for i in near_idx:
                a, b = float(q[i]), float(key[i])
                if a <= 0 or b <= 0:
                    d += 0.0 if a == b else float("inf")
                else:
                    d += abs(math.log(a / b))
        except ValueError:
            continue
        if max_dist is not None and d > max_dist:
            continue
        if best_d is None or d < best_d:
            best, best_d = val, d
    return best


def entries(op: str) -> Dict[Tuple[str, ...], Any]:
    """All known entries for one op (copy)."""
    with _lock:
        _load_once()
        return dict(_STATE["cache"].get(op, {}))


def record(op: str, parts, value, *, source: Optional[str] = None,
           run: Optional[str] = None,
           improvement: Optional[float] = None) -> None:
    """Record a tuned value: process cache immediately, on-disk table
    best-effort via atomic read-modify-write (fsync before rename).

    ``source``/``run``/``improvement`` stamp provenance (ISSUE 16):
    who committed the entry ('sweep' | 'autotune' | 'manual'), under
    which BENCH_RUN / autotune run id, and the measured improvement
    fraction over the incumbent it beat.  Provenance lands in the same
    atomic write as the value — a crash can never commit one without
    the other."""
    meta = None
    if source is not None or run is not None or improvement is not None:
        meta = {"source": source or "manual"}
        if run:
            meta["run"] = str(run)
        if improvement is not None:
            meta["improvement"] = round(float(improvement), 6)
    with _lock:
        _load_once()
        _STATE["cache"].setdefault(op, {})[_key_tuple(parts)] = value
        if meta is not None:
            _STATE["cache"].setdefault(META_OP, {})[
                (op,) + _key_tuple(parts)] = meta
        path = tuning_path()
        if not path:
            return
        try:
            data = {}
            try:
                with open(path) as f:
                    loaded = json.load(f)
                if isinstance(loaded, dict):
                    data = loaded
            except (OSError, ValueError):
                pass  # corrupt table: overwrite with what we know
            data[key_str(op, parts)] = value
            if meta is not None:
                data[key_str(META_OP, (op,) + _key_tuple(parts))] = meta
            from ..framework.fs import open_for_write
            with open_for_write(path, "w") as f:
                json.dump(data, f, indent=0, sort_keys=True)
        except OSError:
            pass


def provenance(op: str, parts) -> Optional[Dict[str, Any]]:
    """The provenance stamp recorded with (op, key), or None (pre-16
    entries and plain record() calls carry none)."""
    with _lock:
        _load_once()
        m = _STATE["cache"].get(META_OP, {}).get((op,) + _key_tuple(parts))
        return dict(m) if isinstance(m, dict) else None


def all_entries() -> Dict[str, Dict[Tuple[str, ...], Any]]:
    """Every op's entries (copy), provenance namespace excluded — the
    report CLI's feed."""
    with _lock:
        _load_once()
        return {op: dict(t) for op, t in _STATE["cache"].items()
                if op != META_OP}


def reset_for_tests() -> None:
    """Drop the process cache so the next lookup re-reads the file
    (tests re-point PADDLE_TPU_TUNING_CACHE at tmp paths)."""
    with _lock:
        _STATE["loaded"] = False
        _STATE["cache"] = {}
