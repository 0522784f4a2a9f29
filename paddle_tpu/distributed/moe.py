"""Mixture-of-Experts / expert parallelism — a from-scratch TPU design.

The reference snapshot has NO MoE and NO all-to-all collective (SURVEY.md
§2.5 marks expert parallelism "ABSENT — design fresh: ICI all-to-all"),
so unlike the rest of the framework there is no reference file to match;
BASELINE.json config #5 (ERNIE-MoE / switch-transformer) is the target
workload.

Design (GShard/Switch-transformer dispatch, expressed two ways):

1. COMPILED GSPMD path (the one SpmdTrainer uses): expert weights are
   stacked [E, ...] and sharded over the 'ep' mesh axis; tokens are
   grouped by batch row and dispatched into an [B, E, C, H] buffer with
   one-hot einsums. Resharding that buffer from token-sharded ('dp' on B)
   to expert-sharded ('ep' on E) is exactly the all-to-all over ICI —
   GSPMD inserts it from the sharding constraint, the same way it inserts
   the grad all-reduce over 'dp'.

2. MANUAL shard_map path: inside shard_map with the 'ep' axis bound the
   dispatch/exchange/combine is written with explicit
   ``lax.all_to_all`` (dispatch E->devices, expert FFN on local experts,
   all_to_all back). Both paths compute the same math; the manual one is
   the single-axis (dp==ep) formulation.

Gating, two routers inside the ONE layer class (``MoELayer``):

- the CAPACITY path (``capacity_factor`` a number; what ``GPTConfig``'s
  MoE blocks use): softmax top-k with a capacity factor; tokens beyond
  an expert's capacity C = ceil(cf * k * S / E) are DROPPED (their
  combine weight is zero and the residual connection carries them —
  Switch semantics), experts are gelu FFNs with biases, and it always
  holds all E experts.  The load-balance auxiliary loss is
  E * sum_e(frac_tokens_e * mean_prob_e) (Switch eq. 4), optionally plus
  a router z-loss; they reach the training loss through the
  collect_aux_losses() collector, which the compiled trainers open
  around the model call.
- the DROPLESS path (``capacity_factor=None``): sigmoid scores over
  all E experts (the router's product in float32 at the highest
  precision), top-k chosen by score plus a correction bias, the chosen scores normalised and scaled, NO token dropped, no
  auxiliary loss.  Its experts have no bias and are ``W_down act(x
  W_up)`` for ``activation`` gelu, relu or relu2, or GATED for
  ``"swiglu"``: ``W_down (silu(x W_gate) * (x W_up))``, a third stacked
  weight ``w_gate`` and a third grouped product over the same rows.  ``held_experts=(lo, hi)`` tells the layer which
  experts live on this chip: it routes over all E and computes its own
  experts' part of the result (``None`` holds all E, and the parts of
  all shares add up to that).  The (token, expert) pairs that fall on
  held experts are laid out in tiles of one expert each
  (``dropless_layout``) and go through ``ops.grouped_matmul``; pairs on
  absent experts are left out — the exchange that would carry them to
  other chips is not built here.  The layout is sized for the worst
  case (every pair on a held expert), but a share of the experts runs
  its passes over the first ``dropless_short_tiles`` tiles of it, the
  load that share should see twice over, and takes the whole of it,
  inside the step (``lax.cond`` on the tiles in use: no host sync, no
  second executable), only when the load does not fit.  Either way
  every pair has its row: nothing is dropped.
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import List, Optional

import jax
import jax.numpy as jnp

from ..core.autograd import apply
from ..core.tensor import Tensor
from ..nn import initializer as I
from ..nn.layer_base import Layer, ParamAttr
from .mesh import PartitionSpec, get_mesh, NamedSharding
from .mesh import axis_size as _axis_size
from .parallel_layers import mark_sharding, _in_shard_map

__all__ = ["MoELayer", "ExpertParallelFFN", "top_k_gating",
           "collect_aux_losses", "add_aux_loss", "moe_capacity",
           "collect_expert_stats", "record_expert_stats",
           "fold_expert_stats", "nearest_chunk_divisors",
           "route_top_k", "dropless_layout", "publish_expert_totals",
           "expert_totals", "reset_expert_totals"]


# ---------------------------------------------------------------------------
# Auxiliary-loss collection: MoE routers produce losses deep inside the
# network that must reach the optimizer's loss. The compiled trainers open
# a collector around the forward; eager users do the same explicitly.
# ---------------------------------------------------------------------------
_AUX_STACK: List[list] = []


@contextlib.contextmanager
def collect_aux_losses():
    """Collect auxiliary losses (router load-balance/z-loss) produced by
    layers during a forward pass. Yields a list the caller sums into the
    training loss."""
    bucket: list = []
    _AUX_STACK.append(bucket)
    try:
        yield bucket
    finally:
        _AUX_STACK.pop()


def add_aux_loss(loss):
    """Layers call this with a scalar Tensor; it lands in the innermost
    open collector (no-op when none is open, e.g. pure inference)."""
    if _AUX_STACK:
        _AUX_STACK[-1].append(loss)


def moe_capacity(tokens_per_group: int, num_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    """Expert capacity per token group (Switch: cf * k * S / E)."""
    return max(1, int(math.ceil(
        capacity_factor * top_k * tokens_per_group / num_experts)))


# ---------------------------------------------------------------------------
# Expert-balance stats: serving wants per-expert load and dropped-token
# (capacity-overflow) accounting without extra host syncs. The engine
# opens a collector inside its jitted step while TRACING; every MoE
# layer the trace hits records its traced kept-token load, and the fold
# rides out of the executable as one extra output fetched at the step's
# existing readback point.
# ---------------------------------------------------------------------------
_EXPERT_STATS_STACK: List[list] = []


@contextlib.contextmanager
def collect_expert_stats():
    """Collect per-layer expert-balance stats (kept-token load [E] +
    statically-known assigned count) emitted by MoE layers during a
    forward trace. Yields the list; fold with fold_expert_stats()."""
    bucket: list = []
    _EXPERT_STATS_STACK.append(bucket)
    try:
        yield bucket
    finally:
        _EXPERT_STATS_STACK.pop()


def record_expert_stats(load, assigned, tokens=None, into=None, short=None):
    """MoE layers call this with their per-expert KEPT-pair counts
    ``load [E]`` (what the expert computation really held — may be
    traced) and the number of (token, expert) assignments the router
    made to those experts (static ``top_k * B * S`` on the capacity
    path; traced on a share of the experts, where it depends on the
    routing); dropped = assigned - sum(load).

    Two sinks, one mechanism.  An open collector (the serving engine's
    trace) gets the record.  ``into``, a layer's own int32 buffer
    ``[E + 4]`` (load per held expert, pairs assigned, tokens seen, and
    `short [2]`: the tokens of the calls that had a short dropless buffer
    to take and of those that took it, traced, since the branch is taken
    on the device; zeros from a call with no branch),
    is added to in place: a buffer is state the compiled train step
    threads through and donates, so the counts accumulate on the device
    with no host sync until ``publish_expert_totals`` takes them."""
    if _EXPERT_STATS_STACK:
        _EXPERT_STATS_STACK[-1].append({"load": load, "assigned": assigned})
    if into is not None:
        row = jnp.concatenate([
            load.astype(jnp.int32),
            jnp.stack([jnp.asarray(assigned, jnp.int32),
                       jnp.asarray(tokens, jnp.int32)]),
            short.astype(jnp.int32)])
        into._data = into._data + row


# process-wide totals of the layers' buffers, as ops.kernel_paths keeps
# its counts: whoever holds the buffers (SpmdTrainer.stats) publishes,
# whoever reports (a benchmark reader) reads after the run
_EXPERT_TOTALS: dict = {}
EXPERT_STATS_BUFFER = "expert_stats"


def publish_expert_totals(buffers: dict):
    """TAKE every ``*.expert_stats`` buffer of `buffers` (one host
    read-back; call at a log boundary, never per step): what the buffers
    counted since they were last taken is added to the process-wide
    totals (Python integers, which do not overflow) and the buffers in
    `buffers` are set back to zero, so an int32 count only has to hold
    the tokens between two readings (2**31: 131,072 steps of 16,384).
    Returns ``expert_totals()``, or None where the model has no such
    buffer (then nothing is read)."""
    names = [n for n in buffers
             if n.rsplit(".", 1)[-1] == EXPERT_STATS_BUFFER]
    if not names:
        return None
    for name, row in zip(names, jax.device_get([buffers[n]
                                                for n in names])):
        total = _EXPERT_TOTALS.setdefault(name, [0] * len(row))
        for i, v in enumerate(row):
            total[i] += int(v)
        held = buffers[name]
        buffers[name] = jax.device_put(
            jnp.zeros(held.shape, held.dtype), held.sharding)
    return expert_totals()


def expert_totals() -> dict:
    """What was published since ``reset_expert_totals``: per layer the
    kept pairs of each held expert, the pairs the router assigned to
    them, the tokens seen, those of them whose call had a short buffer
    to take and those whose call took it; over all layers the local
    pairs a token, the busiest held expert's load over the mean load,
    the pairs dropped (assigned less kept) and the short buffer's share
    of the tokens that had one to take (1.0: the worst case never ran;
    None where no call had a branch)."""
    layers = {n: {"load": row[:-4], "assigned": row[-4], "tokens": row[-3],
                  "branch_tokens": row[-2], "short_tokens": row[-1]}
              for n, row in _EXPERT_TOTALS.items()}
    loads = [v for rec in layers.values() for v in rec["load"]]
    kept = sum(loads)
    tokens = sum(rec["tokens"] for rec in layers.values())
    assigned = sum(rec["assigned"] for rec in layers.values())
    branch = sum(rec["branch_tokens"] for rec in layers.values())
    short = sum(rec["short_tokens"] for rec in layers.values())
    return {"layers": layers, "pairs_dropped": assigned - kept,
            "local_pairs_per_token": kept / tokens if tokens else None,
            "short_buffer_share": short / branch if branch else None,
            "load_max_over_mean":
                max(loads) * len(loads) / kept if kept else None}


def reset_expert_totals() -> None:
    _EXPERT_TOTALS.clear()


def fold_expert_stats(bucket):
    """Sum a collector's per-layer records into ONE fixed-shape pytree
    ``{"load": [E] f32, "assigned": f32 scalar}`` suitable as an extra
    jit output; None when the trace hit no MoE layer (static per model
    config, so executable signatures stay stable)."""
    if not bucket:
        return None
    load = bucket[0]["load"].astype(jnp.float32)
    for rec in bucket[1:]:
        load = load + rec["load"].astype(jnp.float32)
    counts = [r["assigned"] for r in bucket]
    if all(isinstance(c, int) for c in counts):
        assigned = jnp.asarray(float(sum(counts)), jnp.float32)
    else:       # a share of the experts: the count depends on the routing
        assigned = sum(jnp.asarray(c, jnp.float32) for c in counts)
    return {"load": load, "assigned": assigned}


def nearest_chunk_divisors(n: int, k: int):
    """The valid a2a chunk counts nearest a requested k: the largest
    divisor of n that is <= k and the smallest that is >= k (for the
    divisibility error message — naming what WOULD work beats
    restating the constraint)."""
    k = max(1, min(int(k), int(n)))
    lower = next(d for d in range(k, 0, -1) if n % d == 0)
    higher = next(d for d in range(k, n + 1) if n % d == 0)
    return lower, higher


# ---------------------------------------------------------------------------
# Router math (pure jnp — used under both dispatch paths)
# ---------------------------------------------------------------------------
def top_k_gating(logits, top_k: int, capacity: int,
                 normalize_gates: bool = True):
    """Top-k gating with per-group capacity.

    logits: [B, S, E] router scores (a group = one batch row).
    Returns (dispatch [B,S,E,C] 0/1, combine [B,S,E,C] float, aux, zloss):
      - dispatch[b,s,e,c]=1 iff token s goes to expert e at capacity
        slot c;
      - combine = dispatch * renormalized gate prob;
      - aux = E * sum_e(load_frac_e * mean_prob_e) (Switch load-balance);
      - zloss = mean(logsumexp(logits)^2) (router logit drift control).
    """
    f32 = logits.astype(jnp.float32)
    probs = jax.nn.softmax(f32, axis=-1)                       # [B,S,E]
    n_experts = probs.shape[-1]

    masks, gates = [], []
    remaining = probs
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)                   # [B,S]
        m = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32)  # [B,S,E]
        masks.append(m)
        gates.append(jnp.sum(probs * m, axis=-1))              # [B,S]
        remaining = remaining * (1.0 - m)

    # load-balance aux from the top-1 assignment (Switch eq. 4)
    load_frac = jnp.mean(masks[0], axis=1)                     # [B,E]
    mean_prob = jnp.mean(probs, axis=1)                        # [B,E]
    aux = n_experts * jnp.mean(jnp.sum(load_frac * mean_prob, axis=-1))
    zloss = jnp.mean(jnp.square(jax.nn.logsumexp(f32, axis=-1)))

    if normalize_gates and top_k > 1:
        denom = sum(gates) + 1e-9
        gates = [g / denom for g in gates]

    dispatch = jnp.zeros(probs.shape + (capacity,), jnp.float32)
    combine = jnp.zeros_like(dispatch)
    # running per-expert fill count across the k choices
    offset = jnp.zeros(probs.shape[:1] + (1, n_experts), jnp.float32)
    for m, g in zip(masks, gates):
        pos_e = jnp.cumsum(m, axis=1) - m + offset             # [B,S,E]
        offset = offset + jnp.sum(m, axis=1, keepdims=True)
        pos = jnp.sum(pos_e * m, axis=-1)                      # [B,S]
        keep = (pos < capacity) & (jnp.sum(m, axis=-1) > 0)
        slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                              dtype=jnp.float32) * keep[..., None]
        d = m[..., :, None] * slot[..., None, :]       # [B,S,E,C]
        dispatch = dispatch + d
        combine = combine + d * g[..., None, None]
    return dispatch, combine, aux, zloss


# ---------------------------------------------------------------------------
# Dropless routing: scores over all experts, the pairs on held experts
# laid out expert by expert in whole tiles; between tokens and the
# buffer's rows by gathers both ways over the worst-case buffer, by rows
# (a gather out, a scatter-add back) over the short one
# ---------------------------------------------------------------------------
def route_top_k(logits, score_bias, top_k: int, normalize: bool = True,
                scaling: float = 1.0):
    """``logits [T, E]`` float32 -> ``(idx [T, k] int32, weight [T, k]
    float32)``: sigmoid scores, the top k by ``score + score_bias``,
    weighted by the chosen scores themselves (the bias only picks),
    divided by their sum when `normalize` and multiplied by `scaling`."""
    score = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(score + score_bias.astype(jnp.float32), top_k)
    weight = jnp.take_along_axis(score, idx, axis=-1)
    if normalize:
        weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), weight * scaling


# rows of a tile of the dropless buffer: one expert a tile, the MXU-sized
# block ops.grouped_matmul multiplies at a time
DROPLESS_TILE = 512
# the short dropless buffer holds this many times the load a share of the
# experts should see (tokens x top_k x held / all); a heavier load takes
# the worst-case buffer inside the step
DROPLESS_SHORT_LOAD = 2


def dropless_short_tiles(tokens: int, top_k: int, n_held: int,
                         n_experts: int, tile_m: int) -> int:
    """Tiles of the short buffer: ``DROPLESS_SHORT_LOAD`` times the
    expected pairs in whole tiles, and one more an expert for the part
    tile each may end in."""
    expected = tokens * top_k * n_held / n_experts
    return math.ceil(DROPLESS_SHORT_LOAD * expected / tile_m) + n_held


def dropless_layout(idx, lo: int, n_held: int, tile_m: int) -> dict:
    """Where every (token, expert) pair of ``idx [T, k]`` that falls on
    the held experts ``lo .. lo + n_held`` sits in a buffer of
    ``M = (ceil(T k / tile_m) + n_held) * tile_m`` rows: expert by
    expert, in token order, each expert in whole tiles and in at least
    one (so a tile has ONE expert and every expert has a tile — what
    ``ops.grouped_matmul`` asks).  M is the worst case (every pair on a
    held expert), so no pair is ever dropped.  The used tiles are a
    prefix of the buffer: a caller whose load fits in fewer tiles may cut
    ``src_token``, ``src_pair`` and ``tile_group`` to them and leave
    ``dest_row`` as it is (``MoELayer`` does, see
    ``dropless_short_tiles``).

    Returns int32 arrays: ``dest_row [T, k]`` (the pair's row; M for a
    pair on an absent expert), ``src_token [M]`` and ``src_pair [M]``
    (the row's token and flat pair; T and T k for an empty row),
    ``tile_group [M / tile_m]``, ``tiles_used [1]``, and the counters
    ``assigned`` (pairs the router put on held experts) and ``load
    [n_held]`` (rows the buffer really holds, by expert)."""
    t, k = idx.shape
    pairs = t * k
    n_tiles = -(-pairs // tile_m) + n_held
    m = n_tiles * tile_m
    local = idx.reshape(-1) - lo
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held)
    experts = jnp.arange(n_held, dtype=jnp.int32)
    hit = (key[:, None] == experts[None, :]).astype(jnp.int32)
    upto = jnp.cumsum(hit, axis=0)
    counts = upto[-1]                                        # [n_held]
    rank = jnp.sum((upto - hit) * hit, axis=1)               # among its expert
    tiles_per = jnp.maximum(1, -(-counts // tile_m))
    tile_end = jnp.cumsum(tiles_per)
    row_start = (tile_end - tiles_per) * tile_m
    pair_start = jnp.cumsum(counts) - counts
    safe = jnp.minimum(key, n_held - 1)
    dest_row = jnp.where(held, row_start[safe] + rank, m)
    # the other direction, by gathers alone: which pair sits in row r
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(n_tiles, dtype=jnp.int32),
                         side="right"), n_held - 1).astype(jnp.int32)
    rows = jnp.arange(m, dtype=jnp.int32)
    row_group = jnp.repeat(tile_group, tile_m)
    row_rank = rows - row_start[row_group]
    row_live = (row_rank < counts[row_group]) & \
        (rows < tile_end[-1] * tile_m)
    src_pair = jnp.where(
        row_live, order[jnp.minimum(pair_start[row_group] + row_rank,
                                    pairs - 1)], pairs)
    load = jnp.sum((row_group[:, None] == experts[None, :]) &
                   row_live[:, None], axis=0)
    return {"dest_row": dest_row.reshape(t, k).astype(jnp.int32),
            "src_pair": src_pair.astype(jnp.int32),
            "src_token": jnp.where(row_live, src_pair // k, t).astype(
                jnp.int32),
            "tile_group": tile_group,
            "tiles_used": tile_end[-1:].astype(jnp.int32),
            "assigned": jnp.sum(held), "load": load}


def _rows(table, index):
    """``table[index]``, reading zero where an index equals
    ``len(table)`` (an empty row, a pair on an absent expert)."""
    n = table.shape[0]
    got = table[jnp.minimum(index, n - 1)]
    return jnp.where((index < n).reshape(index.shape + (1,) * (table.ndim - 1)),
                     got, jnp.zeros_like(got))


def _sum_over_pairs(table, dest_row, weight=None):
    """``sum_k weight[:, k] * table[dest_row[:, k]]`` in float32, one
    gather of T rows a choice (never the [T, k, H] block at once)."""
    acc = 0.0
    for j in range(dest_row.shape[1]):
        got = _rows(table, dest_row[:, j]).astype(jnp.float32)
        acc = acc + (got if weight is None else got * weight[:, j, None])
    return acc


@jax.custom_vjp
def _dispatch(x, src_token, dest_row):
    """``x [T, H]`` -> the buffer ``[M, H]``; both directions gather."""
    return _rows(x, src_token)


def _dispatch_fwd(x, src_token, dest_row):
    return _rows(x, src_token), dest_row


def _dispatch_bwd(dest_row, d_buf):
    return _sum_over_pairs(d_buf, dest_row).astype(d_buf.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y_buf, weight, layout):
    """``y_buf [M, H]``, ``weight [T, k]`` -> ``y [T, H]``: each token's
    rows, weighted and summed in float32."""
    return _sum_over_pairs(y_buf, layout["dest_row"],
                           weight).astype(y_buf.dtype)


def _combine_fwd(y_buf, weight, layout):
    return _combine(y_buf, weight, layout), (y_buf, weight, layout)


def _combine_bwd(saved, dy):
    y_buf, weight, layout = saved
    row_weight = _rows(weight.reshape(-1), layout["src_pair"])
    d_buf = (_rows(dy, layout["src_token"]).astype(jnp.float32) *
             row_weight[:, None]).astype(y_buf.dtype)
    dy32 = dy.astype(jnp.float32)
    d_weight = jnp.stack(
        [jnp.sum(_rows(y_buf, layout["dest_row"][:, j]).astype(jnp.float32)
                 * dy32, axis=-1) for j in range(weight.shape[1])], axis=1)
    return d_buf, d_weight.astype(weight.dtype), None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _sum_by_token(rows, src_token, n_tokens, row_weight=None):
    """``rows [M, H]`` summed in float32 into the tokens they came from
    ``[n_tokens, H]``: one scatter-add of M rows (an empty row's token is
    ``n_tokens`` and falls off the end)."""
    rows = rows.astype(jnp.float32)
    if row_weight is not None:
        rows = rows * row_weight[:, None]
    return jax.ops.segment_sum(rows, src_token, num_segments=n_tokens)


@jax.custom_vjp
def _dispatch_rows(x, src_token, dest_row):
    """``_dispatch`` whose backward goes by the buffer's rows."""
    return _rows(x, src_token)


def _dispatch_rows_fwd(x, src_token, dest_row):
    return _rows(x, src_token), (src_token, dest_row)


def _dispatch_rows_bwd(saved, d_buf):
    src_token, dest_row = saved
    dx = _sum_by_token(d_buf, src_token, dest_row.shape[0])
    return dx.astype(d_buf.dtype), None, None


_dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def _combine_rows(y_buf, weight, layout):
    """``_combine`` by the buffer's rows, both ways: the forward one
    scatter-add of M weighted rows, ``d_weight`` a row's dot with the
    ``dy`` row that ``d_buf`` gathers anyway."""
    row_weight = _rows(weight.reshape(-1), layout["src_pair"])
    return _sum_by_token(y_buf, layout["src_token"], weight.shape[0],
                         row_weight).astype(y_buf.dtype)


def _combine_rows_fwd(y_buf, weight, layout):
    return _combine_rows(y_buf, weight, layout), (y_buf, weight, layout)


def _combine_rows_bwd(saved, dy):
    y_buf, weight, layout = saved
    row_weight = _rows(weight.reshape(-1), layout["src_pair"])
    dy_rows = _rows(dy, layout["src_token"]).astype(jnp.float32)
    d_buf = (dy_rows * row_weight[:, None]).astype(y_buf.dtype)
    d_row_weight = jnp.sum(y_buf.astype(jnp.float32) * dy_rows, axis=-1)
    d_weight = _rows(d_row_weight, layout["dest_row"])
    return d_buf, d_weight.astype(weight.dtype), None


_combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _cond_both_ways(short, full, fits, layout, operands):
    """``lax.cond(fits, short, full, layout, *operands)`` whose backward
    is a ``cond`` as well, of each branch's own backward with its forward
    run again inside it (what a layer under remat does anyway).  Left to
    differentiate the ``cond`` itself, every branch would write zeros in
    the shape of the other branch's residuals: the short branch would
    fill the worst-case buffers it is there to avoid."""
    return jax.lax.cond(fits, short, full, layout, *operands)


def _cond_both_ways_fwd(short, full, fits, layout, operands):
    return (_cond_both_ways(short, full, fits, layout, operands),
            (fits, layout, operands))


def _cond_both_ways_bwd(short, full, saved, dy):
    fits, layout, operands = saved

    def backward_of(branch):
        return lambda layout, operands, dy: jax.vjp(
            functools.partial(branch, layout), *operands)[1](dy)

    grads = jax.lax.cond(fits, backward_of(short), backward_of(full),
                         layout, operands, dy)
    return None, None, grads


_cond_both_ways.defvjp(_cond_both_ways_fwd, _cond_both_ways_bwd)


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------
class ExpertParallelFFN(Layer):
    """E stacked FFN experts, weights sharded over the 'ep' mesh axis.

    Parameters are the batched analogue of GPTMLP: w_up [E, H, F],
    w_down [E, F, H]; each expert e computes
    down(act(up(x_e))) on its capacity slice.  ``has_bias=False`` (the
    dropless path's experts) leaves b_up and b_down out.  A gated
    ``activation`` ("swiglu") adds ``w_gate [E, H, F]``:
    down(silu(gate(x_e)) * up(x_e)).
    """

    GATED = ("swiglu",)

    def __init__(self, num_experts: int, hidden_size: int, ffn_size: int,
                 weight_attr=None, down_weight_attr=None,
                 ep_axis: str = "ep", activation: str = "gelu",
                 has_bias: bool = True):
        super().__init__()
        self.num_experts = num_experts
        self.hidden_size = hidden_size
        self.ffn_size = ffn_size
        self.ep_axis = ep_axis
        self.activation = activation
        self.w_up = self.create_parameter(
            [num_experts, hidden_size, ffn_size], attr=weight_attr,
            default_initializer=I.Normal(0.0, 0.02))
        self.w_gate = self.create_parameter(
            [num_experts, hidden_size, ffn_size], attr=weight_attr,
            default_initializer=I.Normal(0.0, 0.02)) \
            if activation in self.GATED else None
        self.b_up = self.create_parameter(
            [num_experts, ffn_size], is_bias=True) if has_bias else None
        self.w_down = self.create_parameter(
            [num_experts, ffn_size, hidden_size],
            attr=down_weight_attr or weight_attr,
            default_initializer=I.Normal(0.0, 0.02))
        self.b_down = self.create_parameter(
            [num_experts, hidden_size], is_bias=True) if has_bias else None
        for p in (self.w_up, self.w_gate, self.b_up, self.w_down,
                  self.b_down):
            if p is not None:
                mark_sharding(p, PartitionSpec(ep_axis,
                                               *([None] * (p.ndim - 1))))

    def act(self, x, gate=None):
        """The activation of the up product `x` (a gated one also takes
        the gate product)."""
        if self.activation == "swiglu":
            return jax.nn.silu(gate) * x
        if self.activation == "gelu":
            return jax.nn.gelu(x, approximate=True)
        if self.activation == "relu":
            return jax.nn.relu(x)
        if self.activation == "relu2":
            return jnp.square(jax.nn.relu(x))
        raise ValueError(f"unknown activation {self.activation}")


class MoELayer(Layer):
    """MoE layer: router + experts + combine, a drop-in replacement for
    an MLP block: forward(x [B,S,H]) -> [B,S,H].  One class, two routers
    (the module docstring has both):

    - ``capacity_factor`` a number: the Switch/GShard CAPACITY path over
      all E experts (softmax top-k, tokens over capacity dropped, biased
      gelu experts, expert-parallel over 'ep').  Router aux losses are
      emitted via add_aux_loss() (scaled by aux_loss_coeff /
      z_loss_coeff) AND kept on self.last_aux_loss for inspection.
    - ``capacity_factor=None``: the DROPLESS path.  Sigmoid scores over
      all E experts, the top k chosen by score plus
      ``e_score_correction_bias``, weights the chosen scores, normalised
      (``normalize_gates``) and multiplied by ``routed_scaling``.  ``held_experts=(lo, hi)`` is this chip's share
      (None: all E); only those experts' weights exist here, and the
      result is their part alone.  Experts have no bias; ``activation``
      "swiglu" makes them gated (``experts.w_gate``).  A layer that
      holds a share runs over a buffer of ``dropless_short_tiles`` tiles
      (16,384 rows for 2 x 8192 tokens on 8 of 128 experts) while
      ``tiles_used`` fits in it, and over the worst case (102,400 rows)
      in the other branch of one ``lax.cond`` when it does not; where the
      worst case is no longer than that (all E held) there is no branch.
      The layer keeps an int32 buffer ``expert_stats`` (kept pairs by
      held expert, pairs assigned, tokens, tokens that had a short
      buffer to take, tokens that took it), fed through
      ``record_expert_stats``.
    """

    def __init__(self, hidden_size: int, ffn_size: int, num_experts: int,
                 top_k: int = 2, capacity_factor: Optional[float] = 1.25,
                 aux_loss_coeff: float = 0.01, z_loss_coeff: float = 0.0,
                 normalize_gates: bool = True, ep_axis: str = "ep",
                 weight_attr=None, down_weight_attr=None,
                 activation: str = "gelu",
                 a2a_chunks: Optional[int] = None,
                 routed_scaling: float = 1.0,
                 held_experts: Optional[tuple] = None):
        super().__init__()
        self.dropless = capacity_factor is None
        if not self.dropless and (held_experts is not None
                                  or routed_scaling != 1.0
                                  or activation in ExpertParallelFFN.GATED):
            raise ValueError(
                "routed_scaling, held_experts and a gated activation "
                "belong to the dropless path: pass capacity_factor=None "
                "with them")
        lo, hi = held_experts if held_experts is not None \
            else (0, num_experts)
        if not 0 <= lo < hi <= num_experts:
            raise ValueError(f"held_experts {held_experts} is no range "
                             f"of the {num_experts} experts")
        self.held = (int(lo), int(hi))
        self.routed_scaling = float(routed_scaling)
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.aux_loss_coeff = aux_loss_coeff
        self.z_loss_coeff = z_loss_coeff
        self.normalize_gates = normalize_gates
        self.ep_axis = ep_axis
        # chunked all-to-all (shard_map path): K > 1 splits dispatch/
        # combine so chunk j's exchange overlaps chunk j-1's expert FFN;
        # None resolves per-trace from PADDLE_TPU_MOE_A2A_CHUNKS /
        # PADDLE_TPU_OVERLAP (distributed.overlap.moe_a2a_chunks)
        self.a2a_chunks = a2a_chunks
        self.gate = self.create_parameter(
            [hidden_size, num_experts],
            attr=weight_attr, default_initializer=I.Normal(0.0, 0.02))
        # router stays replicated: every device scores its own tokens
        mark_sharding(self.gate, PartitionSpec(None, None))
        self.experts = ExpertParallelFFN(
            hi - lo, hidden_size, ffn_size, weight_attr=weight_attr,
            down_weight_attr=down_weight_attr, ep_axis=ep_axis,
            activation=activation, has_bias=not self.dropless)
        self.last_aux_loss: Optional[Tensor] = None
        if self.dropless:
            self.e_score_correction_bias = self.create_parameter(
                [num_experts], default_initializer=I.Constant(0.0))
            self.register_buffer(
                EXPERT_STATS_BUFFER,
                Tensor(jnp.zeros((hi - lo + 4,), jnp.int32)))

    # -- dropless formulation: this chip's experts, every token kept ---
    def _experts_over(self, n_tiles, layout, tokens, weight, w_up, w_down,
                      w_gate=None):
        """Dispatch, up product (and gate product, for gated experts),
        activation, down product and combine
        over the first `n_tiles` tiles of `layout`'s buffer (the used
        tiles are a prefix of it).  Over the whole buffer the passes that
        go by token are gathers, `top_k` of T rows each; over a shorter
        one they go by its rows (one scatter-add of them: on the chip 1.5
        ms where the six gathers take 4.7, PERF.md section 6, PR 34)."""
        from ..ops.grouped_matmul import grouped_matmul
        rows = n_tiles * DROPLESS_TILE
        by_rows = rows < layout["src_token"].shape[0]
        tiles = (layout["tile_group"][:n_tiles], layout["tiles_used"])
        src = {"dest_row": layout["dest_row"],
               "src_pair": layout["src_pair"][:rows],
               "src_token": layout["src_token"][:rows]}
        dispatch, combine = (_dispatch_rows, _combine_rows) if by_rows \
            else (_dispatch, _combine)
        x_buf = dispatch(tokens, src["src_token"], src["dest_row"])
        product = lambda rows, w: grouped_matmul(
            rows, w.astype(tokens.dtype), *tiles, tile_m=DROPLESS_TILE)
        gate = None if w_gate is None else product(x_buf, w_gate)
        y_buf = product(self.experts.act(product(x_buf, w_up), gate),
                        w_down)
        return combine(y_buf, weight, src)

    def _fn_dropless(self, x, gate, score_bias, w_up, w_down, *w_gate):
        b, s, h = x.shape
        tokens = x.reshape(b * s, h)
        lo, hi = self.held
        with jax.named_scope("moe_route"):
            # all float32 passes: at the backend's default a float32
            # product is one bf16 pass, and near-tied choices then flip
            logits = jnp.dot(tokens.astype(jnp.float32),
                             gate.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            idx, weight = route_top_k(
                logits, score_bias, self.top_k, self.normalize_gates,
                self.routed_scaling)
            layout = dropless_layout(idx, lo, hi - lo, DROPLESS_TILE)
        n_tiles = layout["tile_group"].shape[0]
        short_tiles = dropless_short_tiles(
            b * s, self.top_k, hi - lo, self.num_experts, DROPLESS_TILE)
        operands = (tokens, weight, w_up, w_down) + w_gate
        with jax.named_scope("expert_ffn"):
            full = functools.partial(self._experts_over, n_tiles)
            if short_tiles >= n_tiles:      # the worst case is no longer
                y = full(layout, *operands)
                short = jnp.zeros((2,), jnp.int32)
            else:
                fits = layout["tiles_used"][0] <= short_tiles
                y = _cond_both_ways(
                    functools.partial(self._experts_over, short_tiles),
                    full, fits, layout, operands)
                short = jnp.stack([b * s, jnp.where(fits, b * s, 0)])
        return y.reshape(b, s, h), layout["load"], layout["assigned"], short

    def _forward_dropless(self, x):
        gated = () if self.experts.w_gate is None else (self.experts.w_gate,)
        y, load, assigned, short = apply(
            self._fn_dropless, x, self.gate, self.e_score_correction_bias,
            self.experts.w_up, self.experts.w_down, *gated,
            name="moe_layer")
        arr = x.data if isinstance(x, Tensor) else jnp.asarray(x)
        record_expert_stats(
            load.data, assigned.data, tokens=arr.shape[0] * arr.shape[1],
            into=self._buffers[EXPERT_STATS_BUFFER], short=short.data)
        return y

    # -- dense/GSPMD formulation -------------------------------------
    def _fn_dense(self, x, gate, w_up, b_up, w_down, b_down):
        s = x.shape[1]
        cap = moe_capacity(s, self.num_experts, self.top_k,
                           self.capacity_factor)
        logits = jnp.einsum("bsh,he->bse", x.astype(jnp.float32), gate)
        dispatch, combine, aux, zloss = top_k_gating(
            logits, self.top_k, cap, self.normalize_gates)
        load = jnp.sum(dispatch, axis=(0, 1, 3))     # [E] kept tokens
        dispatch = dispatch.astype(x.dtype)
        combine = combine.astype(x.dtype)
        # token->expert buffer; resharding B('dp') -> E('ep') here IS the
        # all-to-all, inserted by GSPMD from the sharding constraint
        xe = jnp.einsum("bsec,bsh->bech", dispatch, x)   # [B,E,C,H]
        xe = self._constrain(xe, PartitionSpec("dp", self.ep_axis,
                                               None, None))
        h1 = self.experts.act(
            jnp.einsum("bech,ehf->becf", xe, w_up.astype(x.dtype))
            + b_up.astype(x.dtype)[None, :, None, :])
        ye = jnp.einsum("becf,efh->bech", h1, w_down.astype(x.dtype)) \
            + b_down.astype(x.dtype)[None, :, None, :]
        ye = self._constrain(ye, PartitionSpec("dp", self.ep_axis,
                                               None, None))
        y = jnp.einsum("bsec,bech->bsh", combine, ye)
        return y, aux, zloss, load

    # -- explicit all_to_all formulation (inside shard_map, dp==ep) --
    def _fn_shard_map(self, x, gate, w_up, b_up, w_down, b_down):
        axis = self.ep_axis
        world = _axis_size(axis)
        b, s, h = x.shape                       # local batch shard
        e_loc = w_up.shape[0]                   # local experts
        n_exp = e_loc * world
        cap = moe_capacity(s, n_exp, self.top_k, self.capacity_factor)
        logits = jnp.einsum("bsh,he->bse", x.astype(jnp.float32), gate)
        dispatch, combine, aux, zloss = top_k_gating(
            logits, self.top_k, cap, self.normalize_gates)
        aux = jax.lax.pmean(aux, axis)
        zloss = jax.lax.pmean(zloss, axis)
        dispatch = dispatch.astype(x.dtype)
        combine = combine.astype(x.dtype)
        xe = jnp.einsum("bsec,bsh->ebch", dispatch, x)   # [E,b,C,H]
        xe = xe.reshape(n_exp, b * cap, h)

        def expert_ffn(xg):
            """Local experts over a token-slot slice [E_loc, g, H] —
            pointwise per token, so chunking the slot dim is exact."""
            h1 = self.experts.act(
                jnp.einsum("egh,ehf->egf", xg, w_up.astype(x.dtype))
                + b_up.astype(x.dtype)[:, None, :])
            return jnp.einsum("egf,efh->egh", h1,
                              w_down.astype(x.dtype)) \
                + b_down.astype(x.dtype)[:, None, :]

        # chunked dispatch/combine (GShard-style a2a splitting): chunk
        # j+1's exchange has no dependence on chunk j's FFN, so the
        # async-collective scheduler can run them concurrently; K=1 is
        # the monolithic synchronous exchange.  Identical math either
        # way — the chunks partition the token-slot dim.
        if self.a2a_chunks is not None:
            # an explicit K that doesn't divide would be silently
            # rewritten — someone A/B-measuring chunk counts must not
            # get numbers for a different K than they asked for
            k = int(self.a2a_chunks)
            if k < 1 or (b * cap) % k:
                lo, hi = nearest_chunk_divisors(b * cap, k)
                raise ValueError(
                    f"a2a_chunks={k} must divide the per-device token "
                    f"slots b*capacity={b * cap} (b={b}, capacity="
                    f"{cap}); the nearest valid chunk counts are "
                    f"{lo} (below) and {hi} (above) — pick one, or "
                    f"leave a2a_chunks=None for the auto-clamped "
                    f"default")
        else:
            # env/default resolution clamps to the nearest divisor
            from .overlap import moe_a2a_chunks as _resolve_chunks
            k = _resolve_chunks(b * cap)
        csz = (b * cap) // k
        ye_chunks = []
        for j in range(k):
            xj = jax.lax.slice_in_dim(xe, j * csz, (j + 1) * csz, axis=1)
            # dispatch: each device keeps its expert rows of everyone's
            # tokens in this chunk
            xj = jax.lax.all_to_all(xj, axis, split_axis=0,
                                    concat_axis=1,
                                    tiled=True)      # [E_loc, W*csz, H]
            yj = expert_ffn(xj)
            # combine: return this chunk's expert outputs to the owners
            yj = jax.lax.all_to_all(yj, axis, split_axis=1,
                                    concat_axis=0,
                                    tiled=True)      # [E, csz, H]
            ye_chunks.append(yj)
        ye = ye_chunks[0] if k == 1 else jnp.concatenate(ye_chunks,
                                                         axis=1)
        ye = ye.reshape(n_exp, b, cap, h)
        y = jnp.einsum("bsec,ebch->bsh", combine, ye)
        return y, aux, zloss

    # -- serving formulation: ep-sharded experts, replicated tokens ---
    def _serve_ep_mesh(self):
        """The compile mesh when the expert-parallel SERVING dispatch
        can run for this trace, else None.  Conditions: inference (the
        training formulations own their paths), a compile mesh bound by
        the engine's trace guard carrying a real 'ep' axis, divisible
        experts, and not already inside a shard_map."""
        if self.training or _in_shard_map(self.ep_axis):
            return None
        from .mesh import get_compile_mesh
        mesh = get_compile_mesh()
        if (mesh is None or self.ep_axis not in mesh.axis_names
                or mesh.shape[self.ep_axis] <= 1):
            return None
        if self.num_experts % mesh.shape[self.ep_axis]:
            return None
        return mesh

    def _serve_chunks(self, c_loc: int) -> int:
        """a2a chunk count for the serving dispatch: an explicit
        a2a_chunks must divide the per-device capacity slice c_loc (the
        chunks partition it); None resolves from the overlap knob
        (PADDLE_TPU_MOE_A2A_CHUNKS) and clamps DOWN to the nearest
        divisor."""
        if self.a2a_chunks is not None:
            k = int(self.a2a_chunks)
            if k < 1 or c_loc % k:
                lo, hi = nearest_chunk_divisors(c_loc, k)
                raise ValueError(
                    f"a2a_chunks={k} must divide the per-device "
                    f"capacity slice {c_loc} of the serving expert "
                    f"dispatch; the nearest valid chunk counts are "
                    f"{lo} (below) and {hi} (above) — pick one, or "
                    f"leave a2a_chunks=None for the auto-clamped "
                    f"default")
            return k
        from .overlap import moe_a2a_chunks as _resolve_chunks
        k = max(1, min(_resolve_chunks(c_loc), c_loc))
        while c_loc % k:
            k -= 1
        return k

    def _fn_serve_ep(self, mesh, x, gate, w_up, b_up, w_down, b_down):
        """Expert-parallel SERVING dispatch (decode [B,1,H], verify
        [B,W,H], prefill [1,S,H]) under shard_map over the full serving
        mesh: tokens and the router stay replicated — every device
        computes the FULL gating, bitwise the ep=1 dense formulation,
        which is what keeps ep>1 token-identical — while expert weights
        arrive ep-sharded.  Each device owns a 1/ep slice of the
        capacity dim: chunked all-to-all sends its slice's tokens to
        the experts' owners (split E, concat C), the local expert FFN
        runs, the reverse all-to-all returns outputs, and a partial
        combine + psum over 'ep' rebuilds the replicated [B,S,H].  The
        capacity dim is zero-padded up front so the slices are equal —
        padded slots carry zero combine weight, so shapes are fixed
        (the zero-recompile contract survives) and the math is exact.
        """
        from .mesh import shard_map
        axis = self.ep_axis
        ep = int(mesh.shape[axis])
        b, s, h = x.shape
        n_exp = self.num_experts
        cap = moe_capacity(s, n_exp, self.top_k, self.capacity_factor)
        cap_pad = -(-cap // ep) * ep
        c_loc = cap_pad // ep
        n_chunks = self._serve_chunks(c_loc)
        csz = c_loc // n_chunks

        def body(xs, gate_r, wu, bu, wd, bd):
            logits = jnp.einsum("bsh,he->bse",
                                xs.astype(jnp.float32), gate_r)
            dispatch, combine, aux, zloss = top_k_gating(
                logits, self.top_k, cap, self.normalize_gates)
            load = jnp.sum(dispatch, axis=(0, 1, 3))   # [E] kept
            dispatch = dispatch.astype(xs.dtype)
            combine = combine.astype(xs.dtype)
            xe = jnp.einsum("bsec,bsh->ebch", dispatch, xs)  # [E,b,C,H]
            if cap_pad > cap:
                xe = jnp.pad(xe, ((0, 0), (0, 0),
                                  (0, cap_pad - cap), (0, 0)))
                combine = jnp.pad(combine, ((0, 0), (0, 0), (0, 0),
                                            (0, cap_pad - cap)))
            idx = jax.lax.axis_index(axis)
            x_loc = jax.lax.dynamic_slice_in_dim(
                xe, idx * c_loc, c_loc, axis=2)        # [E,b,c_loc,H]

            def expert_ffn(xg):
                """Local experts over a capacity-slice chunk
                [E_loc, b, g, H] — pointwise per token slot, so
                chunking the slice is exact."""
                h1 = self.experts.act(
                    jnp.einsum("ebgh,ehf->ebgf", xg,
                               wu.astype(xs.dtype))
                    + bu.astype(xs.dtype)[:, None, None, :])
                return jnp.einsum("ebgf,efh->ebgh", h1,
                                  wd.astype(xs.dtype)) \
                    + bd.astype(xs.dtype)[:, None, None, :]

            ye_chunks = []
            for j in range(n_chunks):
                xj = jax.lax.slice_in_dim(
                    x_loc, j * csz, (j + 1) * csz, axis=2)
                # dispatch: each device keeps its expert rows of every
                # peer's capacity slice for this chunk
                xj = jax.lax.all_to_all(
                    xj, axis, split_axis=0, concat_axis=2,
                    tiled=True)                  # [E_loc, b, csz*ep, H]
                yj = expert_ffn(xj)
                # combine: return the chunk's outputs to slice owners
                yj = jax.lax.all_to_all(
                    yj, axis, split_axis=2, concat_axis=0,
                    tiled=True)                  # [E, b, csz, H]
                ye_chunks.append(yj)
            ye = ye_chunks[0] if n_chunks == 1 else \
                jnp.concatenate(ye_chunks, axis=2)   # [E, b, c_loc, H]
            comb_loc = jax.lax.dynamic_slice_in_dim(
                combine, idx * c_loc, c_loc, axis=3)
            y = jnp.einsum("bsec,ebch->bsh", comb_loc, ye)
            y = jax.lax.psum(y, axis)
            return y, aux, zloss, load

        P = PartitionSpec
        sm = shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), P(axis), P(axis), P(axis), P(axis)),
            out_specs=(P(), P(), P(), P()),
            # gating/combine are replicated by construction (identical
            # inputs on every device) but flow through axis_index-
            # derived slices the static replication checker cannot see
            # through; the psum re-establishes the invariant
            check_vma=False)
        return sm(x, gate, w_up, b_up, w_down, b_down)

    def _constrain(self, arr, spec: PartitionSpec):
        """Best-effort sharding constraint: applied only under the
        COMPILE mesh a trainer publishes while tracing its step
        (mesh.compile_mesh_guard) — the ambient default mesh must not
        leak constraints into eager tape traces. Identity otherwise:
        GSPMD propagation from the sharded expert weights still finds
        the layout. Axes that don't divide the dim (ragged batches)
        drop to replicated, and shard_map manual mode is skipped."""
        from .mesh import get_compile_mesh
        mesh = get_compile_mesh()
        if mesh is None or not isinstance(arr, jax.core.Tracer):
            return arr
        if any(_in_shard_map(a) for a in mesh.axis_names):
            return arr
        names = [a if (a in mesh.axis_names and mesh.shape[a] > 1 and
                       arr.shape[i] % mesh.shape[a] == 0)
                 else None for i, a in enumerate(spec)]
        if not any(names):
            return arr
        return jax.lax.with_sharding_constraint(
            arr, NamedSharding(mesh, PartitionSpec(*names)))

    def forward(self, x):
        import functools
        if self.dropless:
            return self._forward_dropless(x)
        in_sm = _in_shard_map(self.ep_axis)
        serve_mesh = None if in_sm else self._serve_ep_mesh()
        if in_sm:
            fn = self._fn_shard_map
        elif serve_mesh is not None:
            # serving trace (engine compile-mesh guard) with a real
            # 'ep' axis: ep-sharded experts + explicit chunked a2a
            fn = functools.partial(self._fn_serve_ep, serve_mesh)
        else:
            if self.a2a_chunks not in (None, 1):
                # the GSPMD path's all-to-all is XLA-inserted (no
                # manual exchange to chunk); silently ignoring an
                # explicit K here would hand an A/B measurement the
                # monolithic numbers
                raise NotImplementedError(
                    f"a2a_chunks={self.a2a_chunks} only applies to the "
                    f"shard_map expert-parallel formulations (the '"
                    f"{self.ep_axis}' axis bound inside shard_map, or "
                    f"the serving dispatch on an ep>1 mesh); the GSPMD "
                    f"path's all-to-all is inserted by XLA and cannot "
                    f"be chunked from here — leave a2a_chunks=None")
            fn = self._fn_dense
        out = apply(
            fn, x, self.gate, self.experts.w_up, self.experts.b_up,
            self.experts.w_down, self.experts.b_down, name="moe_layer")
        if len(out) == 4:
            y, aux, zloss, load = out
            arr = x.data if isinstance(x, Tensor) else jnp.asarray(x)
            record_expert_stats(
                load.data if isinstance(load, Tensor) else load,
                self.top_k * arr.shape[0] * arr.shape[1])
        else:
            y, aux, zloss = out
        total_aux = aux * self.aux_loss_coeff
        if self.z_loss_coeff:
            total_aux = total_aux + zloss * self.z_loss_coeff
        # keep for inspection only when concrete — storing a trace-time
        # tracer would raise UnexpectedTracerError on later reads
        arr = total_aux.data if isinstance(total_aux, Tensor) else total_aux
        self.last_aux_loss = None if isinstance(arr, jax.core.Tracer) \
            else total_aux
        add_aux_loss(total_aux)
        return y
