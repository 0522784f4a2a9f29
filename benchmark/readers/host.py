"""Per-layer metrics read from the host's clock and from the program's
own counters and records.  Each reader takes the run's observations and
the ``params`` of its metric's file, and returns a number or None."""
from __future__ import annotations

import statistics

from ..harness import percentile as _pct


def train_step_ms(obs, params):
    if obs["kind"] != "train" or not obs["step_ms"]:
        return None
    return float(statistics.median(obs["step_ms"]))


def train_mfu_pct(obs, params):
    """tokens/s x operations per token over chips x the chip's peak."""
    if obs["kind"] != "train":
        return None
    peak = obs["peaks"]["bf16_flops_per_s"] * obs["chips"]
    return 100.0 * obs["tokens_per_s"] * obs["flops_per_token"] / peak


def gen_late_p95_ms(obs, params):
    return _pct(obs["window"]["late_ms"], 95) \
        if obs["kind"] == "serve" else None


def admit_to_first_ms(obs, params):
    return _pct(obs["window"]["admit_to_first_ms"], 50) \
        if obs["kind"] == "serve" else None


def ttft_p90_ms(obs, params):
    return _pct(obs["window"]["ttft_ms"], 90) \
        if obs["kind"] == "serve" else None


def itl_p99_ms(obs, params):
    return _pct(obs["window"]["itl_gaps_ms"], 99) \
        if obs["kind"] == "serve" else None


def decode_tick_ms(obs, params):
    if obs["kind"] != "serve" or not obs["engine_delta"]["decode_steps"]:
        return None
    d = obs["engine_delta"]
    return (d["decode_ms"] + d["sync_ms"]) / d["decode_steps"]


def slot_occupancy_pct(obs, params):
    if obs["kind"] != "serve" or not obs["engine_delta"]["decode_steps"]:
        return None
    d = obs["engine_delta"]
    return 100.0 * d["occupancy_sum"] / d["decode_steps"]


def kernel_fallbacks(obs, params):
    """How often the entry points named in params["ops"] traced their
    XLA composite instead of their kernel, over the run's executables."""
    ops = params["ops"]
    paths = obs["kernel_paths"]
    tables = list(paths.values()) if obs["kind"] == "serve" else [paths]
    seen = [t[op] for t in tables for op in ops if op in t]
    if not seen:
        return None
    return float(sum(c["composite"] for c in seen))


def peak_hbm_gib(obs, params):
    peak = obs["memory_peak_bytes"]
    return None if peak is None else peak / 2 ** 30
