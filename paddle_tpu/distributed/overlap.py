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
           "moe_a2a_chunks"]


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
    want = int(os.environ.get("PADDLE_TPU_MOE_A2A_CHUNKS", "0")) or 2
    want = max(1, min(want, tokens if tokens > 0 else 1))
    while tokens % want:
        want -= 1
    return want


# No XLA flags are written for overlap: the installed TPU compiler runs
# its latency-hiding scheduler and async collectives by default, libtpu
# reads its own options from LIBTPU_INIT_ARGS (an `--xla_tpu_*` option in
# XLA_FLAGS that the compiler does not know aborts the process at
# start-up), and nothing here may guess the platform of a process that
# has not started.
