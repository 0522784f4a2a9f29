"""The whole step and the whole tick accounted for: device time by the
program's INNERMOST named scope, and every idle gap of the chip put down
to the program span the host was in.

Device side.  ``readers/nemotron.py`` finds an operation's path (the stat
``tf_op`` of its event's METADATA, e.g.
``jit(step)/fwd_bwd/transpose(jvp())/while/body/closed_call/checkpoint/
rematted_computation/attn/attn_core/...``) and sums own times under ONE
scope name wherever it stands in the path.  Here every operation goes to
exactly one row: the LAST word of ``VOCABULARY`` in its path (its leaf),
so the rows tile the traced time.  Two rows are no scope: ``(no scope)``,
operations that carry a path with no word of the vocabulary in it (the
program wrote them outside every scope: ``unscoped_pct`` reads this
row), and ``(no path)``, operations with no path at all, which XLA made
itself (layout copies, the completions of its asynchronous copies and
slices) and no scope of the program can reach (``xla_made_pct``).  A
fusion carries one path, its root's, so a fusion that straddles two
scopes counts whole under the root's.  The pass is read
from the path too: JAX writes a recomputed forward under
``rematted_computation`` and a backward under ``transpose(...)``; what
carries neither is the forward.

Host side.  ``paddle_tpu.observability.spans`` writes the trainer's and
the engine's phases into the profiler's own trace (``train_step`` >
``train_step/h2d`` ``/launch``, ``train_step/read``; ``tick`` >
``tick/admit`` > ``prefill``, ``tick/launch``, ``tick/read``,
``tick/commit``), on the device trace's clock.  ``gap_ms`` cuts each gap
between the chip's busy intervals at the edges of those spans and gives
every piece to the innermost span the engine's (or trainer's) thread was
in.

A program without the scopes or the spans (any parent of PR 38) reads
None: the metric is left out and nothing raises.
"""
from __future__ import annotations

import bisect
import functools
import os
import re
import statistics

from .. import harness
from .nemotron import _ops_of, newest_trace

# Every named scope of the program, outermost kinds first (PERF.md
# section 3 says where each is opened).
VOCABULARY = (
    # the trainer
    "fwd_bwd", "optimizer",
    # blocks: pre-norm, sublayer, residual add
    "attn", "mlp", "mamba", "moe", "kda", "mla", "dense_mlp",
    # inside the blocks
    "attn_proj", "attn_core", "decode_attn", "kv_write",
    "mamba_proj", "mamba_conv", "ssd_scan", "mamba_gate_norm",
    "moe_route", "expert_ffn", "shared_expert",
    "kda_groups", "kda_proj", "kda_conv", "kda_gates", "kda_scan",
    "mla_proj", "mla_attn",
    # the ends of the stack
    "embed", "head_ce", "head", "sample",
)
# a scope is a whole component of the path (``/attn/``, ``jvp(attn)``):
# the name of a parameter that holds the word (``...mlp.up_proj...``) is not
_WORD = re.compile(r"(?:^|[/(;])(" + "|".join(VOCABULARY) + r")(?=$|[/):;])")
REMAT_MARK = "rematted_computation"
BACKWARD_MARK = "transpose("
PASSES = ("fwd", "remat", "bwd")
UNSCOPED = "(no scope)"                 # a path, and no scope in it
PATHLESS = "(no path)"                  # XLA's own operations

# which phase of a gap each program span stands for; time inside a parent
# and outside its children is the engine's or the trainer's own
# bookkeeping, so it goes with the host's share
PHASE_OF = {
    "tick/read": "read", "tick/launch": "launch", "tick/admit": "host",
    "tick/commit": "host", "prefill": "host", "tick": "host",
    "train_step/read": "read", "train_step/launch": "launch",
    "train_step/h2d": "host", "train_step": "host",
}
PHASES = ("read", "host", "launch", "outside")
HOST_PLANE = "/host:CPU"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def leaf_of(path: str):
    """The innermost vocabulary scope of an operation's path, or None."""
    found = _WORD.findall(path)
    return found[-1] if found else None


def pass_of(path: str) -> str:
    if REMAT_MARK in path:
        return "remat"
    return "bwd" if BACKWARD_MARK in path else "fwd"


def own_times(ops):
    """[(path, own picoseconds)]: each operation's duration less that of
    the operations nested directly inside it (a ``while`` spans its
    body's), the rule of ``nemotron.own_time_by_scope``."""
    out, stack = [], []                         # [path, end, own]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            path, _, own = stack.pop()
            out.append((path, max(own, 0)))

    for path, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([path, start + dur, dur])
    close(float("inf"))
    return out


def account(ops) -> dict:
    """{leaf: {pass: picoseconds}} over all operations; those under no
    scope under ``UNSCOPED`` or, with no path at all, ``PATHLESS``."""
    table: dict = {}
    for path, own in own_times(ops):
        row = table.setdefault(
            leaf_of(path) or (UNSCOPED if path else PATHLESS),
            dict.fromkeys(PASSES, 0))
        row[pass_of(path)] += own
    return table


# ---------------------------------------------------------------------------
# the host's spans and the chip's busy intervals, on one clock
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=2)
def _timeline_of(path: str, mtime: float) -> dict:
    return timeline(path)


def timeline(path_or_bytes) -> dict:
    """What the gap metrics need of one trace: ``busy`` (the merged busy
    intervals of the first chip, ns), ``spans`` (the program's spans on
    the thread that holds the parents, as ``(start, end, name, stats)``)
    and ``modules`` (the first chip's XLA module runs, ``(name, start,
    duration)``)."""
    from jax.profiler import ProfileData
    from ..trace_reduce import MODULES_LINE, OPS_LINE, union
    data = (ProfileData.from_serialized_xspace(path_or_bytes)
            if isinstance(path_or_bytes, bytes)
            else ProfileData.from_file(path_or_bytes))
    busy, modules, threads = [], [], []
    seen_chip = False
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name) and not seen_chip:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    busy = union((ev.start_ns, ev.start_ns + ev.duration_ns)
                                 for ev in line.events)
                elif line.name == MODULES_LINE:
                    modules = [(ev.name, ev.start_ns, ev.duration_ns)
                               for ev in line.events]
            seen_chip = bool(busy)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                found = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                          dict(ev.stats)) for ev in line.events
                         if ev.name in PHASE_OF]
                if found:
                    threads.append(found)
    # the engine's thread: the one that holds the parents (a prefetcher's
    # train_step/h2d on another thread is not the step's phase)
    parents = lambda t: sum(name in ("tick", "train_step")
                            for _, _, name, _ in t)
    spans = max(threads, key=parents) if threads else []
    return {"busy": busy, "modules": modules, "spans": sorted(
        spans, key=lambda s: (s[0], -s[1]))}


def innermost_segments(spans) -> list:
    """[(start, end, name)] without overlap: at each instant the
    innermost of the spans, which nest (one thread) and come sorted by
    start, the longer first."""
    out, stack = [], []                         # (end, name)
    cursor = float("-inf")

    def advance(to):
        nonlocal cursor
        if stack and to > cursor:
            out.append((cursor, to, stack[-1][1]))
        cursor = max(cursor, to)

    for start, end, name, _ in spans:
        while stack and stack[-1][0] <= start:
            advance(stack[-1][0])
            stack.pop()
        advance(start)
        stack.append((end, name))
    while stack:
        advance(stack[-1][0])
        stack.pop()
    return out


def cut_gaps(busy, spans) -> dict:
    """{phase: ns} of the idle time between the busy intervals, each gap
    cut at the spans' edges; the four phases sum to the idle time."""
    segments = innermost_segments(spans)
    starts = [s for s, _, _ in segments]
    total = dict.fromkeys(PHASES, 0.0)
    for (_, a), (b, _) in zip(busy, busy[1:]):
        covered = 0.0
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(segments) and segments[i][0] < b:
            s, e, name = segments[i]
            cover = min(e, b) - max(s, a)
            if cover > 0:
                total[PHASE_OF[name]] += cover
                covered += cover
            i += 1
        total["outside"] += (b - a) - covered
    return total


def launched_ticks(spans) -> list:
    """The ``tick`` spans that launched a decode step: they carry
    ``kv_positions``."""
    return [stats for _, _, name, stats in spans
            if name == "tick" and "kv_positions" in stats]


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
def _trace_path(obs):
    return newest_trace() if obs.get("trace") else None


def slice_ticks(obs) -> list:
    """The stats of the traced slice's launched ``tick`` spans; [] where
    there is no trace or no such span."""
    path = _trace_path(obs)
    if path is None:
        return []
    return launched_ticks(
        _timeline_of(path, os.path.getmtime(path))["spans"])


def _runs(obs):
    """How many steps (training) or launched ticks (serving) the traced
    slice holds; None where a serving trace has no ``tick`` span."""
    if obs.get("kind") == "train":
        return max(int(obs.get("trace_steps", 1)), 1)
    return len(slice_ticks(obs)) or None


@functools.lru_cache(maxsize=2)
def _table_of(path: str, mtime: float) -> dict:
    return account(_ops_of(path, mtime))


def _account_of(obs):
    """(the newest trace's table, its runs), or (None, None)."""
    path = _trace_path(obs)
    runs = _runs(obs) if path else None
    if runs is None:
        return None, None
    return _table_of(path, os.path.getmtime(path)), runs


def leaf_device_ms(obs, params):
    """Own device time a step (or a launched tick) of the operations
    whose innermost scope is params["scope"]; with params["pass"] ==
    "remat" the recomputed forwards of ALL leaves (or of the one
    scope, if given)."""
    table, runs = _account_of(obs)
    if table is None:
        return None
    scope, which = params.get("scope"), params.get("pass", "all")
    rows = [table[scope]] if scope in table else \
        ([] if scope else list(table.values()))
    ps = sum(sum(row.values()) if which == "all" else row[which]
             for row in rows)
    return ps * 1e-9 / runs if ps else None


def _period_ms(modules):
    """Median start-to-start of the module that took most time: the
    step's or the tick's period inside the traced slice."""
    by_name: dict = {}
    for name, start, dur in modules:
        rec = by_name.setdefault(re.sub(r"\(\d+\)$", "", name), [0.0, []])
        rec[0] += dur
        rec[1].append(start)
    if not by_name:
        return None
    starts = sorted(max(by_name.values(), key=lambda r: r[0])[1])
    gaps = [b - a for a, b in zip(starts, starts[1:])]
    return statistics.median(gaps) * 1e-6 if gaps else None


def _has_scopes(table) -> bool:
    return bool(set(table) - {UNSCOPED, PATHLESS})


def xla_made_pct(obs, params):
    """Share of the traced device own time of the operations that carry
    no path: XLA's own, which no named scope reaches."""
    table, _ = _account_of(obs)
    if table is None or not _has_scopes(table):
        return None
    total = sum(sum(row.values()) for row in table.values())
    return 100.0 * sum(table.get(PATHLESS, {}).values()) / total


def unscoped_pct(obs, params):
    """Share of the traced device own time of the operations that carry a
    path and no vocabulary scope in it: what the program wrote outside
    every scope.  Prints the whole table once a traced run: every leaf's
    ms a step (or launched tick) by pass, both no-scope rows, the slice's
    period and the program's spans."""
    table, runs = _account_of(obs)
    if table is None or not _has_scopes(table):
        return None
    path = _trace_path(obs)
    line = _timeline_of(path, os.path.getmtime(path))
    per_run = lambda ps: round(ps * 1e-9 / runs, 4)
    total = sum(sum(row.values()) for row in table.values())
    durations: dict = {}
    for start, end, name, _ in line["spans"]:
        durations.setdefault(name, []).append((end - start) * 1e-6)
    harness.say(
        "scope_account", runs=runs, total_ms=per_run(total),
        leaves={leaf: {p: per_run(ps) for p, ps in row.items() if ps}
                for leaf, row in sorted(
                    table.items(), key=lambda kv: -sum(kv[1].values()))},
        slice_period_ms=_period_ms(line["modules"]),
        spans={name: {"count": len(ms), "median_ms": statistics.median(ms)}
               for name, ms in sorted(durations.items())})
    unscoped = sum(table.get(UNSCOPED, {}).values())
    return 100.0 * unscoped / total if total else None


def gap_ms(obs, params):
    """The chip's idle time a launched tick that fell to
    params["phase"]: ``read``, ``host`` (commit, admission, the engine's
    bookkeeping), ``launch`` or ``outside`` (the host in no span of the
    program: the caller's loop).  The four sum to the slice's idle time
    over its ticks."""
    path = _trace_path(obs)
    if path is None:
        return None
    line = _timeline_of(path, os.path.getmtime(path))
    runs = _runs(obs)
    if not line["spans"] or runs is None:
        return None
    return cut_gaps(line["busy"], line["spans"])[params["phase"]] * 1e-6 \
        / runs
