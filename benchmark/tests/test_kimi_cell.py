"""The Kimi-Linear training cell's files and readers, and whole runs of
it on the CPU (the rehearsal's sizes): a sound run is correct; a run
whose KDA scan passes no state from chunk to chunk, leaves the delta
correction out or takes one decay a head, whose shared expert is left
out, or whose keys lose their shared channels, is not; the fp8 reference
in the program's place is not.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_kimi_cell.py -q
"""
import io
import json
import math
import os
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import calibrate  # noqa: E402
import calibrate_faults_kimi as faults  # noqa: E402
from benchmark import harness, run as bench_run  # noqa: E402
from benchmark.readers import kimi as readers  # noqa: E402
from benchmark.readers import nemotron as trace_readers  # noqa: E402
from benchmark.references import kimi_linear as reference  # noqa: E402

CELL = faults.CELL
SPEC = harness.load_spec()
PROBE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "scope_probe.xplane.pb")
# the catalog row's config (model-configs/architectures.jsonl), the
# numbers a cut may not touch
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_size": 2304,
    "intermediate_size": 9216, "kv_lora_rank": 512,
    "moe_intermediate_size": 1024, "moe_layer_freq": 1,
    "num_attention_heads": 32, "num_expert_group": 1,
    "num_experts_per_token": 8, "num_key_value_heads": 32,
    "num_shared_experts": 1, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "topk_group": 1, "v_head_dim": 128,
    "model_max_length": 1048576, "num_nextn_predict_layers": 0}


def last_line(seconds="1.5"):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(["--rehearse", "--workload", CELL, "--seed",
                             "2147483659", "--seconds", seconds])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), \
        out.getvalue()


def test_the_cell_states_its_cut():
    parts = harness.load_cell(SPEC, CELL)
    config, mix = parts["config"], parts["mix"]
    kw = config["model"]["kwargs"]
    entry = {c["name"]: c for c in SPEC["configs"]}[parts["cell"]["config"]]
    assert config["source"].startswith(entry["source"])
    assert "kimi_linear" in config["source"] and len(config["source"]) <= 200
    assert config["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert config["published"]["num_hidden_layers"] == 27
    assert config["published"]["num_experts"] == 256
    assert config["published"]["vocab_size"] == 163840
    # the file's top level keeps the published keys, cut where it says
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    la = config["linear_attn_config"]
    assert la == kw["linear_attn_config"]
    assert (la["num_heads"], la["head_dim"],
            la["short_conv_kernel_size"]) == (32, 128, 4)
    assert la["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert sorted(la["kda_layers"] + la["full_attn_layers"]) == \
        list(range(1, 28))
    assert config["num_hidden_layers"] == kw["num_hidden_layers"] == 5
    assert config["num_experts"] == \
        kw["held_experts"][1] - kw["held_experts"][0] == 8
    assert kw["num_experts"] == 256           # the router keeps its width
    assert config["vocab_size"] == kw["vocab_size"] == 163840 // 8
    for key in ("hidden_size", "intermediate_size", "kv_lora_rank",
                "moe_intermediate_size", "num_attention_heads",
                "num_experts_per_token", "num_shared_experts",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                "routed_scaling_factor", "first_k_dense_replace",
                "rms_norm_eps"):
        assert kw[key] == config[key], key
    for key in ("kda_gate_ranks", "kda_decay", "kda_qk_norm", "A_log",
                "dt_bias", "conv1d_weight", "mla_positions", "optimizer",
                "embedding_and_out_projections"):
        assert key in config["assumed"]
    assert "32 chips" in config["deployment"]
    assert (mix["batch"], mix["seq_len"]) == (2, 8192)
    total = sum(math.prod(s) for s in reference.param_spec(kw).values())
    assert total == 602_434_432                   # 602.4 M, 9.64 GB
    limits = config["check"]["limits"]
    assert set(limits) == {"loss_rel_gap", "grad_norm_gap",
                           "delta_norm_gap"}
    assert "my chip runs, PR 35" in config["check"]["why"]


def test_cell_reports_the_declared_metrics():
    layer = {m["name"] for m in
             harness.metrics_for(SPEC, "per_layer", CELL)}
    assert {"train_step_ms", "train_mfu_pct", "step_device_ms.train",
            "device_idle_pct.train", "peak_hbm_gib.train",
            "kernel_fallbacks.train", "kda_scan_device_ms.kimi",
            "kda_scan_roofline_pct.kimi", "mla_attn_device_ms.kimi",
            "mla_attn_roofline_pct.kimi", "expert_ffn_device_ms.kimi",
            "expert_ffn_roofline_pct.kimi", "local_pairs_per_token.kimi",
            "expert_load_max_over_mean.kimi", "short_buffer_share.kimi",
            "kernel_fallbacks.kimi"} == layer
    e2e = {m["name"] for m in harness.metrics_for(SPEC, "end_to_end", CELL)}
    assert e2e == {"train_tokens_per_s", "setup_s"}
    for m in harness.metrics_for(SPEC, "per_layer", CELL):
        desc = harness.load_json(harness.HERE, "layer_metrics",
                                 m["name"] + ".json")
        assert callable(harness.resolve(desc["reader"])), m["name"]
        if m["name"].endswith("_roofline_pct.kimi"):
            assert desc["params"]["scope"] in readers.COSTS
            assert readers.cost_of(desc["params"])["flops"] > 0


def test_costs_against_hand_counts():
    """Operations and bytes at the cell's shapes and at a small one,
    counted by hand."""
    kw = harness.load_cell(SPEC, CELL)["config"]["model"]["kwargs"]
    # the cell: 2 x 8192 positions; 4 KDA layers, 1 MLA, 4 MoE
    assert readers._layers(kw) == {"kda": 4, "mla": 1, "moe": 4}
    kda = readers.kda_scan_cost(kw, 2, 8192)
    # a position and head, forward: 6 x 64 x 128 + 64 x 64 + 4 x 64 x 128
    # + 6 x 128 x 128 = 184,320 operations; 2 x 512 + 4 x 128 + 4 = 1,540
    # bytes; 32 heads, 16,384 positions, 4 layers, forward and backward
    assert kda["flops"] == 3 * 184_320 * 32 * 16_384 * 4
    assert kda["bytes"] == 3 * 1_540 * 32 * 16_384 * 4
    assert kda["bytes"] / 819e9 == pytest.approx(11.8e-3, rel=0.01)
    assert kda["bytes"] / 819e9 > kda["flops"] / 197e12      # bytes bound
    mla = readers.mla_attn_cost(kw, 2, 8192)
    assert mla["flops"] == 3 * 2 * 32 * 8192 * 8192 * (192 + 128)
    assert mla["bytes"] == 3 * 2 * 8192 * 32 * 2 * (384 + 256)
    assert mla["flops"] / 197e12 == pytest.approx(20.9e-3, rel=0.01)
    ffn = readers.expert_ffn_cost(kw, 2, 8192)
    # 16,384 x 8 x 8 / 256 = 4,096 pairs a layer; 6 x 2304 x 1024 a pair
    assert ffn["flops"] == 3 * 4_096 * 6 * 2304 * 1024 * 4
    assert ffn["bytes"] == 3 * 4 * (3 * 8 * 2304 * 1024 * 2 +
                                    4_096 * 3 * (2304 + 1024) * 2)
    assert ffn["flops"] / 197e12 == pytest.approx(3.53e-3, rel=0.01)
    assert ffn["flops"] / 197e12 > ffn["bytes"] / 819e9
    # a small stack: 3 layers (KDA, KDA, MLA), the first dense
    small = {"linear_attn_config": {"kda_layers": [1, 2], "head_dim": 16,
                                    "full_attn_layers": [3], "num_heads": 2,
                                    "short_conv_kernel_size": 4},
             "kda_chunk_size": 32, "num_hidden_layers": 3,
             "first_k_dense_replace": 1, "num_attention_heads": 4,
             "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
             "hidden_size": 64, "moe_intermediate_size": 32,
             "num_experts": 16, "num_experts_per_token": 4,
             "held_experts": [4, 8]}
    assert readers._layers(small) == {"kda": 2, "mla": 1, "moe": 2}
    kda = readers.kda_scan_cost(small, 1, 128)
    assert kda["flops"] == 3 * 128 * 2 * 2 * (
        6 * 32 * 16 + 32 * 32 + 4 * 32 * 16 + 6 * 16 * 16)
    assert kda["bytes"] == 3 * 128 * 2 * 2 * (2 * 64 + 4 * 16 + 4)
    mla = readers.mla_attn_cost(small, 1, 128)
    assert mla["flops"] == 3 * 4 * 128 * 128 * 40
    ffn = readers.expert_ffn_cost(small, 1, 128)
    assert ffn["flops"] == 3 * (128 * 4 * 4 / 16) * 6 * 64 * 32 * 2
    # and the whole step's operations a token for the MFU
    assert reference.train_flops_per_token(kw, 8192) == pytest.approx(
        2.31e9, rel=0.01)


def test_readers_return_nothing_where_there_is_nothing(monkeypatch):
    params = {"scope": "kda_scan", "config": "kimi-linear-ep32-train",
              "traffic": "pretrain_seq8192"}
    assert readers.scope_roofline_pct({"trace": None}, params) is None
    monkeypatch.setattr(trace_readers, "newest_trace", lambda: None)
    assert readers.scope_roofline_pct({"trace": {"busy_s": 1}},
                                      params) is None
    # a trace without the scope (the parent's program): the probe has
    # expert_ffn and ssd_scan and no kda_scan
    monkeypatch.setattr(trace_readers, "newest_trace", lambda: PROBE)
    obs = {"trace": {"busy_s": 1}, "trace_steps": 2,
           "peaks": harness.peaks_for("TPU v5 lite")}
    assert readers.scope_roofline_pct(obs, params) is None
    ms = trace_readers.scope_device_ms(obs, {"scope": "expert_ffn"})
    assert ms == pytest.approx(21_835_002e-9 / 2)
    ffn = readers.cost_of({**params, "scope": "expert_ffn"})
    assert readers.scope_roofline_pct(
        obs, {**params, "scope": "expert_ffn"}) == pytest.approx(
            100 * ffn["flops"] / 197e12 / (ms * 1e-3))


def test_sound_run_is_correct_and_counts_its_pairs():
    from paddle_tpu.distributed import moe
    moe.reset_expert_totals()
    line, out = last_line()
    assert line["correct"] is True and line["rehearsal"] is True, out
    assert line["metrics"] == {}
    totals = moe.expert_totals()
    assert totals["pairs_dropped"] == 0 and len(totals["layers"]) == 3
    # 4 of 16 experts a token, 4 held: 1 pair a token expected
    assert 0.7 < totals["local_pairs_per_token"] < 1.3
    assert totals["short_buffer_share"] in (0.0, 1.0)


@pytest.mark.parametrize("broken", faults.FAULTS)
def test_broken_run_is_not_correct(monkeypatch, broken):
    monkeypatch.setattr(*faults.fault(broken))
    line, out = last_line()
    assert line["correct"] is False, out


def test_fp8_reference_fails_the_check():
    parts = harness.load_cell(SPEC, CELL, rehearse=True)
    jax, devices = harness.start_jax(1, rehearse=True)
    ctx = {"jax": jax, "devices": devices, "config": parts["config"],
           "mix": parts["mix"], "seconds": 2.0, "trace": False,
           "workload": CELL, "control_precisions": ["fp8"]}
    limits = parts["config"]["check"]["limits"]
    pairs = (("loss_gap", "loss_rel_gap"), ("grad_gap", "grad_norm_gap"),
             ("delta_gap", "delta_norm_gap"))
    seeds = [2200000000 + 7919 * i for i in range(3)]
    for row in calibrate.calibrate_train(ctx, seeds, len(seeds)):
        for reading, limit in pairs:
            assert row["program"][reading] <= limits[limit], row
        failed = [r for r, l in pairs if row["control_fp8"][r] > limits[l]]
        assert failed, row
