"""Knob-axis registry: the vocabulary shared by doctor actions and the
autotune controller.

A doctor verdict's structured ``action`` names a ``param`` — the config
axis to mutate.  This module maps that name to a :class:`KnobAxis`
carrying everything the controller needs to trial it: which benchmark
kinds it applies to, the default candidate values when the action does
not supply its own, the equivalent env knob, and the tuning-table op a
winner commits under.  One registry, so the doctor, the offline
controller, the live retuner and the report CLI all agree on what a
knob IS — nobody string-parses advice (ISSUE 16 satellite).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = ["KnobAxis", "AXES", "axis_for", "axis_for_action"]


class KnobAxis:
    """One tunable coordinate: name == the config/param key the measure
    harness understands.  ``candidates`` are the default trial values
    (a doctor action's non-empty candidate list overrides them);
    ``table_op`` is the unified-tuning-table namespace a winner commits
    under (None: env/config-only knob, nothing to persist)."""

    def __init__(self, name: str, kinds: Tuple[str, ...],
                 candidates: Sequence[Any] = (),
                 env: Optional[str] = None,
                 table_op: Optional[str] = None,
                 hot_apply: bool = False):
        self.name = name
        self.kinds = kinds
        self.candidates = list(candidates)
        self.env = env
        self.table_op = table_op
        # hot_apply: mutating this knob on a LIVE engine is a host-side
        # table/config change only — no retrace, no recompile — so the
        # live retuner may apply it without a restart
        self.hot_apply = hot_apply

    def trial_values(self, incumbent: Any,
                     suggested: Optional[Sequence[Any]] = None
                     ) -> List[Any]:
        """Candidate values to trial, the action's suggestion winning
        over the axis defaults, minus the incumbent value itself."""
        vals = list(suggested) if suggested else list(self.candidates)
        return [v for v in vals if v != incumbent]

    def __repr__(self):  # pragma: no cover - debug aid
        return f"KnobAxis({self.name!r}, kinds={self.kinds})"


# the registry: every axis ISSUE 16 names, keyed by param name.  Train
# axes mirror bench.py's bench_train() signature; serve axes mirror
# InferenceEngine construction knobs.
AXES: Dict[str, KnobAxis] = {a.name: a for a in [
    # -- train ----------------------------------------------------------
    KnobAxis("remat_policy", ("train",),
             candidates=["off", "dots_no_batch", "dots", "full"],
             table_op="remat_policy"),
    KnobAxis("quantize", ("train",),
             candidates=[None, "int8"], env="BENCH_QUANTIZE",
             table_op="qmm_tiles"),
    KnobAxis("use_flash", ("train",),
             candidates=[True, False], table_op="flash_blocks"),
    KnobAxis("scan", ("train",), candidates=[True, False]),
    KnobAxis("overlap", ("train",), candidates=[True, False],
             env="PADDLE_TPU_OVERLAP"),
    KnobAxis("moe_a2a_chunks", ("train",), candidates=[1, 2, 4, 8],
             env="PADDLE_TPU_MOE_A2A_CHUNKS",
             table_op="moe_a2a_chunks"),
    KnobAxis("prefetch_depth", ("train",), candidates=[0, 2, 4, 8],
             env="PADDLE_TPU_PREFETCH_DEPTH"),
    # -- serve ----------------------------------------------------------
    KnobAxis("spec_k", ("serve",), candidates=[0, 2, 4],
             env="PADDLE_TPU_SPEC_K"),
    KnobAxis("kv_dtype", ("serve",), candidates=["dense", "int8"],
             env="PADDLE_TPU_KV_DTYPE"),
    KnobAxis("prefill_buckets", ("serve",), candidates=[],
             env="PADDLE_TPU_PREFILL_BUCKETS",
             table_op="prefill_buckets", hot_apply=True),
    KnobAxis("qmm_tiles", ("train", "serve"), candidates=[],
             table_op="qmm_tiles"),
    KnobAxis("flash_blocks", ("train", "serve"), candidates=[],
             table_op="flash_blocks"),
    KnobAxis("batch_slots", ("serve",), candidates=[],
             env="PADDLE_TPU_DECODE_SLOTS"),
    KnobAxis("prefix_cache", ("serve",), candidates=[True],
             env="PADDLE_TPU_PREFIX_CACHE"),
    # chunked prefill (ISSUE 20): 0 disables; hot_apply via
    # InferenceEngine.set_prefill_chunk — a host-side flag flip (the
    # chunk executable for a NEW width compiles once, at apply time,
    # not in the steady-state serving loop)
    KnobAxis("prefill_chunk", ("serve",), candidates=[0, 32, 64, 128],
             env="PADDLE_TPU_CHUNKED_PREFILL", hot_apply=True),
]}


def axis_for(param: Optional[str]) -> Optional[KnobAxis]:
    """Registry lookup by param name (None/unknown -> None)."""
    if not param:
        return None
    return AXES.get(param)


def axis_for_action(action: Optional[dict]) -> Optional[KnobAxis]:
    """The axis a doctor verdict's structured action points at — None
    for behavioral advice (param None) or an unknown param (a future
    doctor rule must not crash an old controller)."""
    if not isinstance(action, dict):
        return None
    return axis_for(action.get("param"))
