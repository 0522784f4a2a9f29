"""Per-layer metrics of the Brumby serving cell.

Device time BY NAMED SCOPE a LAUNCHED TICK: ``readers/nemotron.py``'s
``own_time_by_scope`` sums an operation's own time under every scope its
path carries (``retention_step`` inside ``retention`` inside the decode
executable), and ``readers/account.py``'s ``slice_ticks`` counts the
traced slice's ``tick`` spans that launched a decode step.  (A serving
run's observations have no ``trace_steps``, which ``nemotron.
scope_device_ms`` divides by, and ``account.leaf_device_ms`` knows only
``account.VOCABULARY``.)  The prefills that fell into the slice are in
the slice's time and so in these numbers, as they are in the GPT cell's:
``retention_chunk`` is theirs alone, spread over the slice's ticks (0
where the slice holds no prefill).

What the decode step NEEDS, whatever implements it: to read and to write
once the recurrent state of every active slot.  The engine writes those
bytes on each ``tick`` span (``state_bytes``, from the cache's
``logical_slot_bytes``: the mathematics' own float32 ``[d (d + 1) / 2,
d + 1]`` a KV head and layer, not the layout's padded rows), so the cost
comes from shapes and spans and a padded or a second copy of the state
counts against the share.

A program without the scopes, the span argument or the counter (any
parent of PR 41) reads None: the metric is left out and nothing raises.
"""
from __future__ import annotations

import functools
import os

from .. import harness
from . import account
from .nemotron import _ops_of, newest_trace, own_time_by_scope

SCOPES = ("retention_step", "retention_chunk", "retention_proj", "mlp",
          "head", "retention", "embed", "sample")


@functools.lru_cache(maxsize=2)
def _scopes_of(path: str, mtime: float) -> dict:
    """One pass over a trace for all the metrics that read it."""
    return own_time_by_scope(_ops_of(path, mtime), SCOPES)


def _scope_ps(obs) -> dict:
    """{scope: own picoseconds in the newest trace}, {} with no trace."""
    if not obs.get("trace"):
        return {}
    path = newest_trace()
    if path is None:
        return {}
    return _scopes_of(path, os.path.getmtime(path))


def scope_tick_ms(obs, params):
    """Own device time of params["scope"] a launched tick of the slice.
    None where the trace has no retention scope at all (another
    program) or no launched tick."""
    ps = _scope_ps(obs)
    ticks = account.slice_ticks(obs)
    if not ticks or not (ps.get("retention_step") or
                         ps.get("retention_chunk")):
        return None
    return ps[params["scope"]] * 1e-9 / len(ticks)


def step_cost(ticks) -> dict:
    """What the slice's decode steps need: no operation worth counting
    beside the bytes (13 float32 operations a state element and token
    against 8 bytes), and the ``state_bytes`` their spans carry."""
    return {"flops": 0.0,
            "bytes": float(sum(int(t["state_bytes"]) for t in ticks
                               if "state_bytes" in t))}


def retention_step_roofline_pct(obs, params):
    """The least time the chip could take to read and write the state
    that the slice's ticks moved (their ``state_bytes`` over the HBM
    bandwidth) over the device time of ``retention_step`` in them.
    Prints the slice's table once a traced run: every scope's ms a
    launched tick, the prefills in the slice and the least time."""
    scopes = _scope_ps(obs)
    ps = scopes.get("retention_step")
    ticks = account.slice_ticks(obs)
    cost = step_cost(ticks)
    if not ps or not cost["bytes"]:
        return None
    least_s = cost["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    path = newest_trace()
    spans = account._timeline_of(path, os.path.getmtime(path))["spans"] \
        if path else []
    harness.say("scope_account.brumby", ticks=len(ticks),
                prefills=sum(name == "prefill" for _, _, name, _ in spans),
                ms_a_tick={k: round(v * 1e-9 / len(ticks), 4)
                           for k, v in scopes.items()},
                state_gb_a_tick=round(cost["bytes"] / len(ticks) / 1e9, 4),
                least_ms_a_tick=round(least_s * 1e3 / len(ticks), 4))
    return 100.0 * least_s / (ps * 1e-12)


def state_gib(obs, params):
    """Bytes of per-slot recurrent state the engine holds, from the
    program's gauge ``serve_recurrent_state_bytes``."""
    try:
        from paddle_tpu.observability import metrics
    except ImportError:
        return None
    series = metrics.snapshot().get("serve_recurrent_state_bytes", {}) \
        .get("series", [])
    held = sum(s["value"] for s in series)
    return held / 2 ** 30 if held else None
