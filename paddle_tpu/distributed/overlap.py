"""Latency-hiding collectives — the one knob and its defaults.

``PADDLE_TPU_OVERLAP`` governs every communication-overlap schedule in
the framework (default ON; set ``0`` to force every schedule back to its
synchronous counterpart for A/B runs):

- ZeRO-3 overlapped parameter all-gather (`distributed.zero3`, wired by
  SpmdTrainer when ``sharding_configs={'stage': 3}`` + scan-over-layers);
- the 1F1B pipeline schedule default (`distributed.pipeline`,
  ``schedule=None`` resolves here);
- chunked MoE all-to-all (`distributed.moe`, ``a2a_chunks=None``
  resolves here).

All of the schedules are numerics-preserving (they reorder communication,
not math); the dryrun and tests assert loss parity against the
synchronous paths, so the default can be ON.
"""
from __future__ import annotations

import os

__all__ = ["overlap_enabled", "pipeline_schedule_default",
           "moe_a2a_chunks", "autotune_a2a_sweep"]


def overlap_enabled() -> bool:
    """The master knob: PADDLE_TPU_OVERLAP (default on)."""
    return os.environ.get("PADDLE_TPU_OVERLAP", "1") != "0"


def pipeline_schedule_default() -> str:
    """Schedule used when GPipeTrainer(schedule=None):
    PADDLE_TPU_PIPELINE_SCHEDULE if set, else 'gpipe'.  1F1B is chosen
    per-constructor (schedule='1f1b') or via the env var — it computes
    the same losses but its explicit interleaved backward is a different
    compiled program, so flipping an existing trainer's schedule is an
    intentional act, not an ambient default.

    PADDLE_TPU_OVERLAP=0 overrides the env-var schedule back to 'gpipe'
    (the documented 'every schedule falls back to its synchronous
    counterpart' contract — an A/B flip of the one knob must actually
    change the program); an explicit constructor argument still wins
    over both."""
    if not overlap_enabled():
        return "gpipe"
    return os.environ.get("PADDLE_TPU_PIPELINE_SCHEDULE") or "gpipe"


def moe_a2a_chunks(tokens: int) -> int:
    """Chunk count for the MoE shard_map all-to-all when the layer was
    built with ``a2a_chunks=None``: PADDLE_TPU_MOE_A2A_CHUNKS if set,
    else the unified tuning table (utils.tuning, op "moe_a2a_chunks",
    key (device_kind, tokens) — recorded by a sweep or an operator),
    else 2 (so chunk j's exchange can overlap chunk j-1's expert FFN).
    PADDLE_TPU_OVERLAP=0 forces 1 (monolithic) EVEN IF the chunk env
    var is set — the kill switch must win over every env-selected
    schedule or an A/B of the one knob measures nothing (only an
    explicit MoELayer(a2a_chunks=...) argument overrides it).  Always
    clamped to a divisor of `tokens` (the per-expert token-slot count)
    — a ragged chunk would change shapes, and shape stability is the
    recompile-free contract."""
    if not overlap_enabled():
        return 1
    want = int(os.environ.get("PADDLE_TPU_MOE_A2A_CHUNKS", "0"))
    if not want:
        try:
            from ..utils import tuning as _tuning
            key = (_tuning.device_kind(), tokens)
            tuned = _tuning.lookup("moe_a2a_chunks", key)
            if tuned is None:
                # the sweep measures at the BENCH shape; a MoE layer's
                # b×capacity token count rarely equals it exactly —
                # nearest tabled count (same device, within ~4× either
                # way) still beats the blind default
                tuned = _tuning.lookup_nearest(
                    "moe_a2a_chunks", key, match_idx=(0,),
                    near_idx=(1,), max_dist=1.4)
            if tuned is not None:
                want = int(tuned)
        except (ValueError, TypeError):
            pass
    want = want or 2
    want = max(1, min(want, tokens if tokens > 0 else 1))
    while tokens % want:
        want -= 1
    return want


def autotune_a2a_sweep(tokens: int, hidden: int = 512, iters: int = 5):
    """On-device sweep of the MoE all-to-all chunk count: time a
    chunked token exchange (split → K sequential all_to_alls → concat,
    the dispatch shape distributed.moe uses) for K in (1, 2, 4, 8) over
    the local devices and record the winner in the unified tuning table
    (op "moe_a2a_chunks", key (device_kind, tokens)) so
    :func:`moe_a2a_chunks` serves it to every later process.  Needs >1
    device; returns the winning K or None."""
    import time

    import numpy as np
    import jax
    import jax.numpy as jnp

    from ..utils import tuning as _tuning
    from .mesh import shard_map as _shard_map

    devs = jax.devices()
    n = len(devs)
    if n < 2:
        return None
    # per-device token rows, rounded so every candidate K divides them
    t_loc = max(tokens // n, 8 * n)
    t_loc -= t_loc % (8 * n)
    mesh = jax.sharding.Mesh(np.array(devs), ("x",))
    spec = jax.sharding.PartitionSpec("x")
    x = jnp.zeros((n * t_loc, hidden), jnp.float32)

    def chunked(arr, k):
        def body(xs):                     # local shard [t_loc, hidden]
            parts = jnp.split(xs, k, axis=0)
            outs = [jax.lax.all_to_all(
                p.reshape(n, -1, hidden), "x", 0, 0, tiled=False)
                .reshape(-1, hidden) for p in parts]
            return jnp.concatenate(outs, axis=0)
        return _shard_map(body, mesh=mesh, in_specs=spec,
                          out_specs=spec)(arr)

    best, best_t = None, None
    for k in (1, 2, 4, 8):
        if t_loc % (k * n):
            continue
        try:
            fn = jax.jit(lambda a, k=k: chunked(a, k))
            jax.block_until_ready(fn(x))
            t0 = time.perf_counter()
            out = None
            for _ in range(iters):
                out = fn(x)
            jax.block_until_ready(out)
            t = (time.perf_counter() - t0) / iters
        except Exception:
            continue
        if best_t is None or t < best_t:
            best, best_t = k, t
    if best is not None:
        # record under the token count actually timed (t_loc was
        # rounded for divisibility), not the requested one
        _tuning.record("moe_a2a_chunks",
                       (_tuning.device_kind(), n * t_loc), best)
    return best


# No XLA flags are written for overlap: the installed TPU compiler runs
# its latency-hiding scheduler and async collectives by default, libtpu
# reads its own options from LIBTPU_INIT_ARGS (an `--xla_tpu_*` option in
# XLA_FLAGS that the compiler does not know aborts the process at
# start-up), and nothing here may guess the platform of a process that
# has not started.
