"""Per-layer metrics read from the reduced profiler trace
(``benchmark/trace_reduce.py``).  No trace, no number."""
from __future__ import annotations

import re


def device_idle_pct(obs, params):
    trace = obs.get("trace")
    if not trace:
        return None
    return 100.0 * trace["idle_share"]


def module_device_ms(obs, params):
    """Device time of one XLA module per run of it: the module whose name
    matches params["module"] (a regular expression; the rule for each
    metric is in its file and in PERF.md).  Where several match, the one
    with the most time."""
    trace = obs.get("trace")
    if not trace:
        return None
    rule = re.compile(params["module"])
    found = [m for name, m in trace["modules"].items() if rule.search(name)]
    if not found:
        return None
    best = max(found, key=lambda m: m["total_s"])
    return 1e3 * best["total_s"] / best["count"]
