"""Per-layer metrics of the Nemotron-H training cell.

Device time BY NAMED SCOPE.  The program wraps its new mechanisms in
``jax.named_scope`` (``ssd_scan``, ``mamba_conv``, ``moe_route``,
``expert_ffn``, ``shared_expert``).  One hand-read TPU v5e trace (PERF.md,
section 3) shows where that name ends up: not in the ``XLA Ops`` event's
own name (the HLO line) nor in its own stats, which is all that
``jax.profiler.ProfileData`` hands out, but in the stat ``tf_op`` of the
event's METADATA in the raw ``.xplane.pb``, as the operation's path
(``jit(step)/transpose(jvp(ssd_scan))/.../dot_general:``).  So this file
reads the few fields it needs from the protobuf wire format itself (no
dependency: importing TensorFlow's ``xplane_pb2`` costs 25 s), sums each
operation's OWN time (a ``while`` spans its body's operations) under the
scopes whose name its path carries, and divides by the traced steps.

Operations and bytes of the two rooflines are functions of the shapes
alone (``ssd_scan_cost``, ``expert_ffn_cost``), read from the cell's
configuration and traffic files, which the metric's file names.

Program counters come from ``paddle_tpu.distributed.moe.expert_totals()``
(process-wide, published when the driver reads ``trainer.stats``).  A
program that lacks the scope or the counter reads None: the metric is
left out and nothing raises.
"""
from __future__ import annotations

import functools
import glob
import os
import re

from .. import harness

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


# ---------------------------------------------------------------------------
# the protobuf wire format, as far as XSpace needs it
# ---------------------------------------------------------------------------
def _varint(buf, i):
    value, shift = 0, 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message: an int for a
    varint, a memoryview for bytes, strings and sub-messages."""
    buf = memoryview(buf)
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield number, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(view):
    key, value = 0, b""
    for number, _, v in fields(view):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def device_ops(xspace: bytes) -> list:
    """[(op path or '', start_ps, duration_ps)] of the first TPU plane's
    ``XLA Ops`` line.  XSpace.planes=1; XPlane: name=2 lines=3
    event_metadata=4 stat_metadata=5; XLine: name=2 events=4; XEvent:
    metadata_id=1 offset_ps=2 duration_ps=3; XEventMetadata: stats=5;
    XStat: metadata_id=1 str_value=5 ref_value=7; XStatMetadata: name=2."""
    for number, _, plane in fields(xspace):
        if number != 1:
            continue
        parts = {2: [], 3: [], 4: [], 5: []}
        for n, _, v in fields(plane):
            if n in parts:
                parts[n].append(v)
        if not parts[2] or not DEVICE_PLANE.match(_text(parts[2][0])):
            continue
        stat_names = {}
        for entry in parts[5]:
            key, meta = _map_entry(entry)
            stat_names[key] = next(
                (_text(v) for n, _, v in fields(meta) if n == 2), "")
        path_of = {}
        for entry in parts[4]:
            key, meta = _map_entry(entry)
            for n, _, stat in fields(meta):
                if n != 5:
                    continue
                got = {sn: sv for sn, _, sv in fields(stat)}
                if stat_names.get(got.get(1)) != "tf_op":
                    continue
                path_of[key] = _text(got[5]) if 5 in got \
                    else stat_names.get(got.get(7), "")
        for line in parts[3]:
            name, events = "", []
            for n, _, v in fields(line):
                if n == 2:
                    name = _text(v)
                elif n == 4:
                    events.append(v)
            if name != OPS_LINE:
                continue
            out = []
            for ev in events:
                got = {n: v for n, w, v in fields(ev) if w == 0}
                out.append((path_of.get(got.get(1), ""), got.get(2, 0),
                            got.get(3, 0)))
            return out
    return []


def own_time_by_scope(ops: list, scopes) -> dict:
    """{scope: picoseconds}: each operation's duration less that of the
    operations nested directly inside it, summed over the operations
    whose path carries the scope's name."""
    rules = {s: re.compile(r"(?<![A-Za-z0-9_])" + re.escape(s) +
                           r"(?![A-Za-z0-9_])") for s in scopes}
    total = dict.fromkeys(scopes, 0)
    stack = []                                  # [path, end, own]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            path, _, own = stack.pop()
            for scope, rule in rules.items():
                if rule.search(path):
                    total[scope] += max(own, 0)

    for path, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([path, start + dur, dur])
    close(float("inf"))
    return total


def newest_trace():
    found = glob.glob(os.path.join(harness.ROOT, ".bench_trace", "*",
                                   "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


@functools.lru_cache(maxsize=2)
def _ops_of(path: str, mtime: float) -> list:
    """One parse of a trace for all the metrics that read it."""
    with open(path, "rb") as f:
        return device_ops(f.read())


def scope_device_ms(obs, params):
    """Device time a step of the operations under params["scope"],
    forward, remat and backward together."""
    if not obs.get("trace"):
        return None
    path = newest_trace()
    if path is None:
        return None
    scope = params["scope"]
    ps = own_time_by_scope(_ops_of(path, os.path.getmtime(path)),
                           [scope])[scope]
    if not ps:
        return None
    return ps * 1e-9 / max(int(obs.get("trace_steps", 1)), 1)


# ---------------------------------------------------------------------------
# what the two kernels need, from the shapes alone
# ---------------------------------------------------------------------------
def _shapes(params):
    config = harness.load_json(harness.HERE, "configs",
                               params["config"] + ".json")
    mix = harness.load_json(harness.HERE, "traffic",
                            params["traffic"] + ".json")
    return config["model"]["kwargs"], int(mix["batch"]) * int(mix["seq_len"])


def ssd_scan_cost(m: dict, tokens: int) -> dict:
    """A step's chunked scans, forward and backward (the backward at
    twice the forward; the remat's second forward is not counted).
    Operations a position and layer, forward: the chunk's C B^T (2 Q N a
    group), its product with dt x (2 Q P a head), the chunk's state and
    the state's read-out (2 P N a head each).  Bytes: x and y (2 H P),
    B and C (2 G N) in bf16 and dt in float32, read once forward; the
    backward reads them and dy and writes four gradients."""
    q, n, g = m["chunk_size"], m["ssm_state_size"], m["n_groups"]
    h, p = m["mamba_num_heads"], m["mamba_head_dim"]
    layers = m["hybrid_override_pattern"].count("M")
    fwd = 2 * q * n * g + 2 * q * p * h + 4 * p * n * h
    io = 2 * (2 * h * p + 2 * g * n) + 4 * h
    return {"flops": 3.0 * fwd * tokens * layers,
            "bytes": 3.0 * io * tokens * layers}


def expert_ffn_cost(m: dict, tokens: int) -> dict:
    """A step's routed experts at the EXPECTED load (top_k x held / all
    pairs a token), forward and backward: two products a pair, each
    2 d f, times three.  Bytes: the held experts' two weight matrices in
    bf16, read for the forward, read again for dx and written as dw; a
    pair's rows in and out of both products."""
    lo, hi = m["held_experts"]
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    layers = m["hybrid_override_pattern"].count("E")
    pairs = tokens * m["num_experts_per_tok"] * (hi - lo) / \
        m["n_routed_experts"]
    weights = 2 * (hi - lo) * d * f * 2
    rows = pairs * (2 * d + 2 * f) * 2
    return {"flops": 3.0 * pairs * 4 * d * f * layers,
            "bytes": 3.0 * (weights + rows) * layers}


COSTS = {"ssd_scan": ssd_scan_cost, "expert_ffn": expert_ffn_cost}


def scope_roofline_pct(obs, params):
    """The least time the chip could take for the scope's work (the
    larger of operations over peak and bytes over bandwidth) over the
    device time it took."""
    ms = scope_device_ms(obs, params)
    if ms is None:
        return None
    cost = COSTS[params["scope"]](*_shapes(params))
    peaks = obs["peaks"]
    least_s = max(cost["flops"] / peaks["bf16_flops_per_s"],
                  cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)


# ---------------------------------------------------------------------------
# program counters
# ---------------------------------------------------------------------------
def expert_counter(obs, params):
    """params["field"] of the program's expert totals: local pairs a
    token, the busiest held expert's load over the mean."""
    try:
        from paddle_tpu.distributed import moe
    except ImportError:
        return None
    totals = getattr(moe, "expert_totals", lambda: {})()
    value = totals.get(params["field"])
    return None if value is None else float(value)
