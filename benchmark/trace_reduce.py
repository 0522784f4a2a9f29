"""From a profiler trace (``.xplane.pb``) to numbers that need no name
from the program: device busy and idle time, time by XLA module, the
operations that took most time, and the longest idle gaps with what the
host was in meanwhile.

What one hand-read trace of a TPU v5e shows (PERF.md, section 3): each
chip is a plane ``/device:TPU:<n>``; its line ``XLA Modules`` has one
event per run of a compiled program, named ``<jit name>(<fingerprint>)``;
its line ``XLA Ops`` has one event per operation of those programs, which
nest (a ``while`` spans the operations of its body), so busy time is the
UNION of their intervals and never their sum.  Host threads are lines of
the plane ``/host:CPU``.

Times are seconds (floats); the trace's clock is nanoseconds.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE_PREFIX = "/host:"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path_or_bytes) -> list:
    """[{name, lines: [{name, events: [(name, start_s, dur_s)]}]}] read
    with nothing but JAX."""
    from jax.profiler import ProfileData
    data = (ProfileData.from_serialized_xspace(path_or_bytes)
            if isinstance(path_or_bytes, bytes)
            else ProfileData.from_file(path_or_bytes))
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals) -> list:
    """Sorted, merged [(start, end)]."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def self_times(events) -> dict:
    """{name: seconds} of each operation's OWN time: its duration less
    that of the operations nested directly inside it (a ``while`` spans
    its body's operations and would otherwise count them twice)."""
    own: dict = {}
    stack = []                       # [name, end, self]
    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, self_s = stack.pop()
            own[name] = own.get(name, 0.0) + max(self_s, 0.0)
    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            stack[-1][2] -= d
        stack.append([name, s + d, d])
    close(float("inf"))
    return own


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def op_label(text: str) -> str:
    """A device operation's event name is its whole HLO line; the label
    keeps what tells operations apart: the instruction's name without
    its instance number, the opcode (with a custom call's target) and the
    first result shape.  '%copy.148.remat = bf16[24,16,2048,128]{...}
    copy(...)' becomes 'copy copy bf16[24,16,2048,128]'."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text[:96]
    base = re.sub(r"(\.\d+|\.remat\d*|\.clone)+$", "", name.lstrip("%"))
    opcode = _OPCODE.search(" " + rest)
    shape = _SHAPE.search(rest)
    target = _TARGET.search(rest)
    parts = [base, opcode.group(1) if opcode else "?"]
    if target:
        parts[-1] += ":" + target.group(1)
    if shape:
        parts.append(shape.group(0))
    return " ".join(parts)[:96]


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _strip_fingerprint(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _host_events(planes):
    out = []
    for plane in planes:
        if plane["name"].startswith(HOST_PLANE_PREFIX):
            for line in plane["lines"]:
                out.extend((n, s, s + d) for n, s, d in line["events"]
                           if d > 0)
    return out


def _host_during(host_events, start, end) -> str:
    """The host event that covers most of [start, end], by name;
    'unattributed' where the trace has none there."""
    best, best_cover = "unattributed", 0.0
    for name, s, e in host_events:
        cover = min(e, end) - max(s, start)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def reduce(planes: list, top: int = 10) -> dict:
    """The device's side of a traced window.

    window: from the first to the last device event over all chips.
    busy_s / idle_share: union of the ``XLA Ops`` intervals, averaged
    over the chips that ran anything.  modules: {name: {count, total_s}}
    from ``XLA Modules`` (fingerprints stripped).  device_ops: the `top`
    groups of operations (op_label, with how many instructions share the
    label) by summed own time, as [label, seconds], from the first chip.
    idle_gaps: the `top` longest gaps between busy intervals of the first
    chip, as [what the host was in, seconds].
    """
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])
               and _line(p, OPS_LINE)]
    if not devices:
        return {}
    starts = [s for p in devices for _, s, _ in _line(p, OPS_LINE)]
    ends = [s + d for p in devices for _, s, d in _line(p, OPS_LINE)]
    w0, w1 = min(starts), max(ends)
    busy = []
    for p in devices:
        merged = union((s, s + d) for _, s, d in _line(p, OPS_LINE))
        busy.append(sum(e - s for s, e in merged))
    busy_s = sum(busy) / len(busy)

    first = devices[0]
    modules: dict = {}
    for name, _, d in _line(first, MODULES_LINE):
        rec = modules.setdefault(_strip_fingerprint(name),
                                 {"count": 0, "total_s": 0.0})
        rec["count"] += 1
        rec["total_s"] += d
    per_op: dict = {}
    for name, own in self_times(_line(first, OPS_LINE)).items():
        label = op_label(name)
        rec = per_op.setdefault(label, [0.0, 0])
        rec[0] += own
        rec[1] += 1
    device_ops = sorted(((f"{label} x{n}", t) for label, (t, n)
                         in per_op.items()), key=lambda kv: -kv[1])[:top]

    merged = union((s, s + d) for _, s, d in _line(first, OPS_LINE))
    host = _host_events(planes)
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(merged, merged[1:])), reverse=True)[:top]
    idle_gaps = [[_host_during(host, s, e), g] for g, s, e in gaps]
    window_s = w1 - w0
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else 0.0,
            "chips": len(devices), "modules": modules,
            "device_ops": [[n, s] for n, s in device_ops],
            "idle_gaps": idle_gaps}


def reduce_dir(log_dir: str, top: int = 10) -> dict:
    return reduce(load(find_xplane(log_dir)), top)
