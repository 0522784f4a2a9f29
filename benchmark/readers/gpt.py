"""What the two attention kernels of the GPT cells NEED, from the shapes
alone, and their shares of the chip's roofline.  Device times come from
``readers/account.py`` (the innermost named scope); the costs are
functions of the configuration's and the traffic's numbers, whatever
kernel does the work.
"""
from __future__ import annotations

from .. import harness
from . import account


def _model(params) -> dict:
    return harness.load_json(harness.HERE, "configs",
                             params["config"] + ".json")["model"]["kwargs"]


def attn_core_cost(m: dict, batch: int, seq_len: int) -> dict:
    """A step's causal flash attention over all layers: forward plus a
    backward at 2.5 times the forward (the backward forms the scores
    again: five products where the forward has two); the remat's second
    forward is in the measured time and not in the cost.  Operations: half
    the square of scores and of values, 2 a multiply-add.  Bytes: q, k, v
    and o in bf16 once forward; the backward reads them and do and writes
    three gradients."""
    h = m["num_heads"]
    d = m["hidden_size"] // h
    fwd = batch * h * 2.0 * seq_len * seq_len * d
    return {"flops": 3.5 * fwd * m["num_layers"],
            "bytes": 12.0 * batch * seq_len * h * d * 2 * m["num_layers"]}


def decode_attn_cost(m: dict, kv_positions: int) -> dict:
    """The decode ticks' attention over all layers: bytes of the k and v
    the ticks NEED, one read of every cached position of every active
    slot (``kv_positions``, summed over the ticks) at kv heads x head
    width in bf16, k and v; two products a position and head."""
    h = m["num_heads"]
    hkv = m.get("num_kv_heads") or h
    d = m["hidden_size"] // h
    return {"flops": 4.0 * kv_positions * h * d * m["num_layers"],
            "bytes": 2.0 * kv_positions * hkv * d * 2 * m["num_layers"]}


def _share(cost: dict, peaks: dict, device_s: float) -> float:
    least_s = max(cost["flops"] / peaks["bf16_flops_per_s"],
                  cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / device_s


def attn_core_roofline_pct(obs, params):
    """The least time the chip could take for a step's flash attention
    over the device time of the leaf ``attn_core`` (which also holds the
    head transposes and the remat's second forward)."""
    ms = account.leaf_device_ms(obs, {"scope": "attn_core"})
    if ms is None:
        return None
    mix = harness.load_json(harness.HERE, "traffic",
                            params["traffic"] + ".json")
    cost = attn_core_cost(_model(params), int(mix["batch"]),
                          int(mix["seq_len"]))
    return _share(cost, obs["peaks"], ms * 1e-3)


def decode_attn_roofline_pct(obs, params):
    """The least time the chip could take to read the k and v that the
    slice's ticks needed (their ``kv_positions``) over the device time of
    the leaf ``decode_attn`` in those ticks."""
    ms = account.leaf_device_ms(obs, {"scope": "decode_attn"})
    ticks = account.slice_ticks(obs)
    if ms is None or not ticks:
        return None
    cost = decode_attn_cost(_model(params),
                            sum(int(t["kv_positions"]) for t in ticks))
    return _share(cost, obs["peaks"], ms * 1e-3 * len(ticks))
