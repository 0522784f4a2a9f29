"""Per-layer metrics of the Granite 4.0-H serving cell.

Device time BY NAMED SCOPE a LAUNCHED TICK, as ``readers/brumby.py``
reads it: ``readers/nemotron.py``'s ``own_time_by_scope`` sums an
operation's own time under every scope its path carries (``ssm_step``
inside ``mamba`` inside the decode executable), ``readers/account.py``'s
``slice_ticks`` counts the traced slice's ``tick`` spans that launched a
decode step.  The prefills that fell into the slice are in the slice's
time and so in these numbers: ``ssd_scan`` and ``ssm_state_write`` are
theirs alone (``ssm_chunk_device_ms``), spread over the slice's ticks (0
where the slice holds no prefill).

What the Mamba layers' decode step NEEDS, whatever implements it: to
read and to write once the float32 state of every active slot.  The
engine writes those bytes on each ``tick`` span (``state_bytes``, from
the cache's ``logical_slot_bytes``: 36 layers x 64 heads x 64 x 128 x 4
bytes a slot), so the cost comes from shapes and spans, and a padded or
a second copy of the state counts against the share.

A program without the scopes, the span argument or the gauge (any
parent of PR 48) reads None: the metric is left out and nothing raises.
"""
from __future__ import annotations

import functools
import os

from .. import harness
from . import account
# the same span argument (``state_bytes`` summed over the slice's ticks: no
# operation worth counting beside the bytes) and the same gauge
# (``serve_recurrent_state_bytes``: here state and windows, rows left out)
from .brumby import state_gib, step_cost  # noqa: F401
from .nemotron import _ops_of, newest_trace, own_time_by_scope

SCOPES = ("ssm_step", "ssd_scan", "ssm_state_write", "mamba_proj",
          "mamba_conv", "mamba_gate_norm", "decode_attn", "kv_write",
          "attn_proj", "attn_core", "mlp", "head", "mamba", "attn", "embed",
          "sample")
# the scopes only this program opens: a trace with none of them is
# another program's
OWN = ("ssm_step", "ssm_state_write")


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
    """Own device time of params["scopes"] (summed) a launched tick of
    the slice.  None where the trace has none of this program's own
    scopes or no launched tick."""
    ps = _scope_ps(obs)
    ticks = account.slice_ticks(obs)
    if not ticks or not any(ps.get(s) for s in OWN):
        return None
    return sum(ps[s] for s in params["scopes"]) * 1e-9 / len(ticks)


def ssm_step_roofline_pct(obs, params):
    """The least time the chip could take to read and write the state
    that the slice's ticks moved (their ``state_bytes`` over the HBM
    bandwidth) over the device time of ``ssm_step`` in them.  Prints the
    slice's table once a traced run: every scope's ms a launched tick,
    the prefills in the slice and the least time."""
    scopes = _scope_ps(obs)
    ps = scopes.get("ssm_step")
    ticks = account.slice_ticks(obs)
    cost = step_cost(ticks)
    if not ps or not cost["bytes"]:
        return None
    least_s = cost["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    path = newest_trace()
    spans = account._timeline_of(path, os.path.getmtime(path))["spans"] \
        if path else []
    harness.say("scope_account.granite", ticks=len(ticks),
                prefills=sum(name == "prefill" for _, _, name, _ in spans),
                ms_a_tick={k: round(v * 1e-9 / len(ticks), 4)
                           for k, v in scopes.items()},
                state_gb_a_tick=round(cost["bytes"] / len(ticks) / 1e9, 4),
                kv_positions_a_tick=round(sum(
                    int(t.get("kv_positions", 0)) for t in ticks)
                    / len(ticks), 1),
                least_ms_a_tick=round(least_s * 1e3 / len(ticks), 4))
    return 100.0 * least_s / (ps * 1e-12)
