"""Per-layer metrics of the Kimi-Linear training cell: the work of its
three new mechanisms from the shapes alone, and their shares of the
chip's roofline.

Device time by named scope (``kda_scan``, ``mla_attn``, ``expert_ffn``)
and the program's expert counters are read by ``readers/nemotron.py``
(``scope_device_ms``, ``expert_counter``): this file adds what those
scopes NEED.  Each cost is a step's forward and backward (the backward at
twice the forward); the remat's second forward is in the measured time
and not in the cost.  They are functions of the configuration's and the
traffic's numbers, whatever implements the mechanism.
"""
from __future__ import annotations

from .. import harness
from . import nemotron


def _layers(m: dict) -> dict:
    """How many of the stack's layers are of each kind."""
    la = m["linear_attn_config"]
    stack = range(1, m["num_hidden_layers"] + 1)
    return {"kda": sum(i in la["kda_layers"] for i in stack),
            "mla": sum(i in la["full_attn_layers"] for i in stack),
            "moe": sum(i > m["first_k_dense_replace"] for i in stack)}


def kda_scan_cost(m: dict, batch: int, seq_len: int) -> dict:
    """The chunked delta rule of the KDA layers (chunk C, a head's state
    K x V).  Operations a position and head, forward: the decayed pair
    terms k.k and q.k (2 C K each), the solve (C C), W = T K+ (2 C K), U
    = T V (2 C V), U - W S, Q+ S and the state's update (2 K V each), the
    pairs' product with U (2 C V).  Bytes: q, k, v and o in bf16, the
    log-decay in float32 (it differs by channel: K numbers a position and
    head) and beta, read or written once forward; the backward reads them
    and do, and writes five gradients."""
    la = m["linear_attn_config"]
    h, k = la["num_heads"], la["head_dim"]
    v, c = k, m.get("kda_chunk_size", 64)
    fwd = h * (6 * c * k + c * c + 4 * c * v + 6 * k * v)
    io = h * (2 * (2 * k + 2 * v) + 4 * k + 4)
    scale = 3.0 * batch * seq_len * _layers(m)["kda"]
    return {"flops": scale * fwd, "bytes": scale * io}


def mla_attn_cost(m: dict, batch: int, seq_len: int) -> dict:
    """Causal attention of the latent-attention layers at its two widths:
    half the square of scores over qk_nope + qk_rope channels and of
    values over v_head_dim, 2 operations a multiply-add.  Bytes: q and k
    at the score width, v and o at the value width, in bf16."""
    h = m["num_attention_heads"]
    d, dv = m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"]
    scale = 3.0 * batch * _layers(m)["mla"]
    return {"flops": scale * h * seq_len * seq_len * (d + dv),
            "bytes": scale * seq_len * h * 2 * (2 * d + 2 * dv)}


def expert_ffn_cost(m: dict, batch: int, seq_len: int) -> dict:
    """The routed SwiGLU experts at the EXPECTED load (top_k x held / all
    pairs a token): three products a pair (gate, up, down), each 2 d f.
    Bytes: the held experts' three matrices in bf16, read for the
    forward, read again for dx and written as dw; a pair's rows in and
    out of the three products."""
    lo, hi = m["held_experts"]
    d, f = m["hidden_size"], m["moe_intermediate_size"]
    pairs = batch * seq_len * m["num_experts_per_token"] * (hi - lo) / \
        m["num_experts"]
    weights = 3 * (hi - lo) * d * f * 2
    rows = pairs * (3 * d + 3 * f) * 2
    layers = _layers(m)["moe"]
    return {"flops": 3.0 * pairs * 6 * d * f * layers,
            "bytes": 3.0 * (weights + rows) * layers}


COSTS = {"kda_scan": kda_scan_cost, "mla_attn": mla_attn_cost,
         "expert_ffn": expert_ffn_cost}


def cost_of(params: dict) -> dict:
    """The cost of params["scope"] at the sizes of the configuration and
    traffic files that the metric's file names."""
    config = harness.load_json(harness.HERE, "configs",
                               params["config"] + ".json")
    mix = harness.load_json(harness.HERE, "traffic",
                            params["traffic"] + ".json")
    return COSTS[params["scope"]](config["model"]["kwargs"],
                                  int(mix["batch"]), int(mix["seq_len"]))


def scope_roofline_pct(obs, params):
    """The least time the chip could take for the scope's work (the
    larger of operations over the bf16 peak and bytes over the HBM
    bandwidth) over the device time the scope took.  None where the
    trace has no such scope."""
    ms = nemotron.scope_device_ms(obs, params)
    if ms is None:
        return None
    cost, peaks = cost_of(params), obs["peaks"]
    least_s = max(cost["flops"] / peaks["bf16_flops_per_s"],
                  cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
