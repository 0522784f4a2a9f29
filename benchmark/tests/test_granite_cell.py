"""The Granite 4.0-H serving cell's files and readers, and whole runs of
it on the CPU (the rehearsal's sizes: five layers MMAMM, so that both
kinds of cache and the handover from prefill to decode run): a sound run
is correct; a run whose state or window is zeroed at the handover, whose
scan passes no state between chunks, whose residual or attention
multiplier is another, whose D skip or gate is dropped or whose
attention reads one row short is not; the fp8 reference in the program's
place is not.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_granite_cell.py -q
"""
import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import calibrate  # noqa: E402
import calibrate_faults_granite as faults  # noqa: E402
from benchmark import harness, run as bench_run  # noqa: E402
from benchmark.readers import account, granite as readers  # noqa: E402
from benchmark.references import granite_hybrid as reference  # noqa: E402

CELL = faults.CELL
SPEC = harness.load_spec()
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# the catalog row's config (model-configs/architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": PERIOD * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}
OWN_METRICS = {
    "ssm_step_device_ms.granite", "ssm_step_roofline_pct.granite",
    "ssm_chunk_device_ms.granite", "decode_attn_device_ms.granite",
    "mamba_proj_device_ms.granite", "mlp_device_ms.granite",
    "head_device_ms.granite", "kernel_fallbacks.granite",
    "state_gib.granite"}


def last_line(seconds="1.5"):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = bench_run.main(["--rehearse", "--workload", CELL, "--seed",
                             "2147483659", "--seconds", seconds])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1]), \
        out.getvalue()


def test_the_cell_reduces_nothing():
    parts = harness.load_cell(SPEC, CELL)
    config, mix = parts["config"], parts["mix"]
    kw = config["model"]["kwargs"]
    entry = {c["name"]: c for c in SPEC["configs"]}[parts["cell"]["config"]]
    assert config["source"] == entry["source"] and \
        len(config["source"]) <= 200
    assert config["reduced"] == entry["reduced"] == []
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    for key in ("vocab_size", "hidden_size", "shared_intermediate_size",
                "num_hidden_layers", "layer_types", "rms_norm_eps",
                "embedding_multiplier", "residual_multiplier",
                "attention_multiplier", "logits_scaling",
                "num_attention_heads", "num_key_value_heads",
                "mamba_n_heads", "mamba_d_head", "mamba_d_state",
                "mamba_n_groups", "mamba_d_conv", "mamba_chunk_size"):
        assert kw[key] == config[key], key
    assert kw["max_seq_len"] == config["max_position_embeddings"]
    for key in ("state_dtype", "dt_clamp", "A_log", "D_and_norms", "dt_bias",
                "conv1d", "embedding", "projections_into_the_stream",
                "gated_norm_groups", "attention_positions", "weight_layout",
                "unused_keys", "capacity"):
        assert key in config["assumed"], key
    assert "one TPU v5e chip is the whole deployment" in config["deployment"]
    assert reference.num_params(kw) == 3_191_396_096
    assert reference.state_bytes_per_slot(kw) == 36 * 2_097_152 == 75_497_472
    for text in ("3,191,396,096", "75,497,472"):
        assert text in config["deployment"], text
    engine = config["driver"]["engine"]
    assert (engine["batch_slots"], engine["max_seq_len"],
            engine["kv_layout"], engine["prefill_buckets"]) == \
        (64, 5120, "dense", [128, 256, 512, 1024])
    assert set(engine) == {"batch_slots", "max_seq_len", "kv_layout",
                           "prefill_buckets"}       # no new option
    assert mix["clients"] == engine["batch_slots"] == mix["block"] == 64
    assert (mix["plan_requests"], mix["lead_in_s"], mix["grace_s"],
            mix["trace_s"]) == (640, 6.0, 1.0, 2.0)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 256,
                                    "sigma": 0.8, "min": 32, "max": 1024}
    assert mix["output_tokens"] == {"dist": "lognormal", "median": 2048,
                                    "sigma": 0.6, "min": 512, "max": 4096}
    # the longest request ends as its slot fills
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] == \
        engine["max_seq_len"] == config["check"]["pad_to"]
    assert set(config["check"]["limits"]) == {"logit_deficit",
                                              "distinct_share"}
    assert "my chip runs, PR 48" in config["check"]["why"]
    assert set(config["rehearse"]["model"]["kwargs"]["layer_types"]) == \
        {"mamba", "attention"}


def test_declaration_keeps_its_form():
    """What the driver refuses before any run: names, units and lines of
    text within their limits (every `why` and `source` at most 200), each
    entry with just its keys, the file ending as the parent's."""
    import re
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
    unit = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")

    def line(text):
        return 1 <= len(text) <= 200 and text.isprintable()

    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}, c
        assert name.match(c["name"]) and line(c["why"]) and \
            line(c["source"]) and len(c["reduced"]) <= 16, c["name"]
        assert all(name.match(k) for k in c["reduced"])
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w
        assert name.match(w["name"]) and name.match(w["traffic"]) and \
            line(w["why"]) and w["chips"] in (1, 4), w["name"]
    assert len(SPEC["configs"]) == len(SPEC["workloads"]) == 6
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}, m
        assert name.match(m["name"]) and unit.match(m["unit"]) and \
            line(m["layer"]) and m["better"] in ("lower", "higher"), m
        assert set(m.get("workloads", ())) <= cells, m["name"]
    for m in SPEC["per_layer"]:
        if m["name"] in OWN_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "itl_p95_ms"
    with open(os.path.join(ROOT, "BENCHMARK.json"), "rb") as f:
        raw = f.read()
    assert len(raw) <= 64 * 1024 and raw.endswith(b"]\n}\n")


def test_cell_reports_the_declared_metrics():
    layer = {m["name"] for m in
             harness.metrics_for(SPEC, "per_layer", CELL)}
    assert OWN_METRICS | {
        "decode_tick_ms.serve", "tick_device_ms.serve",
        "device_idle_pct.serve", "peak_hbm_gib.serve",
        "slot_occupancy.serve", "itl_p99_ms.serve", "ttft_p90_ms.serve",
        "admit_to_first_ms.serve", "gen_late_p95_ms.serve",
        "gap_read_ms.serve", "gap_host_ms.serve", "gap_launch_ms.serve",
        "gap_outside_ms.serve"} <= layer
    # what needs account.VOCABULARY to know the new scopes, or reads the
    # GPT cell's shapes, is not this cell's
    assert not layer & {"decode_attn_device_ms.serve",
                        "decode_attn_roofline_pct.serve",
                        "kv_write_device_ms.serve", "kernel_fallbacks.serve",
                        "unscoped_device_pct.serve",
                        "xla_made_device_pct.serve"}
    e2e = {m["name"] for m in harness.metrics_for(SPEC, "end_to_end", CELL)}
    assert {"serve_tokens_per_s", "itl_p95_ms", "setup_s"} <= e2e
    for m in harness.metrics_for(SPEC, "per_layer", CELL):
        desc = harness.load_json(harness.HERE, "layer_metrics",
                                 m["name"] + ".json")
        assert callable(harness.resolve(desc["reader"]))


def test_readers_find_nothing_without_their_sources():
    """The parent's side of a traced run: no trace, no scope, no span
    argument: every reader of the cell's own metrics says None and none
    raises."""
    obs = {"kind": "serve", "trace": None, "kernel_paths": {},
           "peaks": {"hbm_bytes_per_s": 1.0}}
    for name in sorted(OWN_METRICS - {"state_gib.granite"}):
        desc = harness.load_json(harness.HERE, "layer_metrics",
                                 name + ".json")
        assert harness.resolve(desc["reader"])(
            obs, desc.get("params", {})) is None, name
    assert readers.step_cost([{"kv_positions": 4}])["bytes"] == 0.0
    # another program's trace: scopes, but none of this program's own
    foreign = {"mlp": 5e9, "head": 1e9, "ssm_step": 0, "ssm_state_write": 0}
    assert not any(foreign.get(s) for s in readers.OWN)


def test_roofline_divides_the_spans_bytes_by_the_scopes_time(monkeypatch):
    ticks = [{"kv_positions": 9, "state_bytes": 819_000_000},
             {"kv_positions": 9, "state_bytes": 819_000_000}]
    monkeypatch.setattr(account, "slice_ticks", lambda obs: ticks)
    scopes = dict.fromkeys(readers.SCOPES, 0)
    scopes.update(ssm_step=4e9, ssd_scan=1e9, ssm_state_write=1e9,
                  mamba_proj=2e9, mlp=6e9, head=1e9)       # picoseconds
    monkeypatch.setattr(readers, "_scope_ps", lambda obs: scopes)
    obs = {"kind": "serve", "trace": {}, "peaks": {"hbm_bytes_per_s": 819e9}}
    # 2 x 0.819 GB at 819 GB/s is 2 ms at least; the scope took 4 ms
    assert readers.ssm_step_roofline_pct(obs, {}) == pytest.approx(50.0)
    assert readers.scope_tick_ms(obs, {"scopes": ["mlp"]}) == \
        pytest.approx(3.0)
    assert readers.scope_tick_ms(
        obs, {"scopes": ["ssd_scan", "ssm_state_write"]}) == \
        pytest.approx(1.0)
    assert readers.scope_tick_ms(obs, {"scopes": ["decode_attn"]}) == 0.0
    # a trace with none of this program's own scopes is another program's
    monkeypatch.setattr(readers, "_scope_ps",
                        lambda obs: {**scopes, "ssm_step": 0,
                                     "ssm_state_write": 0})
    assert readers.scope_tick_ms(obs, {"scopes": ["mlp"]}) is None
    assert readers.ssm_step_roofline_pct(obs, {}) is None


def test_sound_run_is_correct_and_counts_its_state():
    line, out = last_line()
    assert line["correct"] is True and line["failed"] == 0, out
    assert line["rehearsal"] is True and line["metrics"] == {}
    held = readers.state_gib({}, {})
    kw = harness.load_cell(SPEC, CELL, rehearse=True)["config"]["model"][
        "kwargs"]
    state = reference.state_bytes_per_slot(kw)
    window = kw["layer_types"].count("mamba") * (kw["mamba_d_conv"] - 1) * \
        (kw["mamba_n_heads"] * kw["mamba_d_head"] +
         2 * kw["mamba_n_groups"] * kw["mamba_d_state"]) * 4
    assert held * 2 ** 30 >= 3 * (state + window)   # this process's engines
    row = [json.loads(l) for l in out.splitlines()
           if '"served_logit_deficit_max"' in l][-1]
    assert row["ok"] and row["value"] <= row["limit"] / 10, row


@pytest.mark.parametrize("name", faults.FAULTS)
def test_planted_fault_is_not_correct(name):
    undo = faults.planted(name)
    try:
        line, out = last_line()
    finally:
        undo()
    assert line["correct"] is False, out
    row = [json.loads(l) for l in out.splitlines()
           if '"served_logit_deficit_max"' in l][-1]
    # an attention layer that misses one row of some thirty moves the
    # logits least; every other fault reads tens of limits
    factor = 2 if name == "one_row_short" else 10
    assert row["ok"] is False and row["value"] > factor * row["limit"], row


def test_fp8_reference_fails_the_check():
    """The precision step below the configuration's, in the program's
    place."""
    parts = harness.load_cell(SPEC, CELL, rehearse=True)
    jax, devices = harness.start_jax(1, rehearse=True)
    ctx = {"jax": jax, "devices": devices, "config": parts["config"],
           "mix": parts["mix"], "seconds": 2.0, "trace": False,
           "workload": CELL, "control_precisions": ["fp8"]}
    limit = parts["config"]["check"]["limits"]["logit_deficit"]
    seeds = [2200000000 + 7919 * i for i in range(2)]
    for row in calibrate.calibrate_serve(ctx, seeds, len(seeds)):
        assert row["failed"] == 0 and row["tokens"] > 50, row
        assert row["program_deficit"] <= limit < row["control_fp8"], row
