"""The Brumby serving cell's files and readers, and whole runs of it on
the CPU (the rehearsal's sizes): a sound run is correct; a run whose
state is zeroed at the handover from prefill to decode, whose gate is
left out, whose weights are of degree 1, whose normaliser is dropped or
whose keys carry no rotary is not; the fp8 reference in the program's
place is not.  The sixth fault, the state kept in bf16, needs more
tokens than a rehearsal's sequence holds (an increment is lost only
under a state some 256 times its size): here the test shows that the
rounding reaches the served path, the chip's reading is in PERF.md.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_brumby_cell.py -q
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
import calibrate_faults_brumby as faults  # noqa: E402
from benchmark import harness, run as bench_run  # noqa: E402
from benchmark.readers import account, brumby as readers  # noqa: E402
from benchmark.references import brumby as reference  # noqa: E402

CELL = faults.CELL
SPEC = harness.load_spec()
# the catalog row's config (model-configs/architectures.jsonl)
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_key_value_heads": 8, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


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
    assert config["source"] == entry["source"] and \
        len(config["source"]) <= 200
    assert config["reduced"] == entry["reduced"] == ["num_hidden_layers"]
    assert config["published"]["num_hidden_layers"] == 40
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["num_hidden_layers"] == kw["num_hidden_layers"] == 8
    for key in ("head_dim", "hidden_size", "intermediate_size",
                "num_attention_heads", "num_key_value_heads",
                "rms_norm_eps", "rope_theta", "vocab_size"):
        assert kw[key] == config[key], key
    assert kw["max_seq_len"] == config["max_position_embeddings"]
    for key in ("degree", "gate", "state_per_kv_head", "normaliser",
                "scale", "rope_and_qk_norm", "bias", "state_dtype", "chunk",
                "phi_layout", "gate_bias_shift", "init"):
        assert key in config["assumed"], key
    assert "five TPU v5e chips" in config["deployment"]
    assert reference.num_params(kw) == 4_198_652_992
    assert reference.state_bytes_per_slot(kw) == 8 * 34_080_768
    engine = config["driver"]["engine"]
    assert (engine["batch_slots"], engine["max_seq_len"],
            engine["kv_layout"]) == (16, 32768, "dense")
    assert mix["clients"] == engine["batch_slots"] == mix["block"]
    longest = mix["prompt_tokens"]["max"] + 3131   # the block's longest
    assert config["check"]["pad_to"] >= longest
    assert set(config["check"]["limits"]) == {"logit_deficit",
                                              "distinct_share"}
    assert "my chip runs, PR 41" in config["check"]["why"]


def test_declaration_keeps_its_form():
    """What the driver refuses before any run (a `why` of 204 characters
    cost this PR one check): names, units and lines of text within their
    limits, each entry with just its keys, the file ending in a newline."""
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
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w
        assert name.match(w["name"]) and name.match(w["traffic"]) and \
            line(w["why"]) and w["chips"] in (1, 4), w["name"]
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}, m
        assert name.match(m["name"]) and unit.match(m["unit"]) and \
            line(m["layer"]) and m["better"] in ("lower", "higher"), m
    with open(os.path.join(ROOT, "BENCHMARK.json"), "rb") as f:
        raw = f.read()
    assert len(raw) <= 64 * 1024 and raw.endswith(b"]\n}\n")


def test_cell_reports_the_declared_metrics():
    layer = {m["name"] for m in
             harness.metrics_for(SPEC, "per_layer", CELL)}
    assert {"retention_step_device_ms.brumby",
            "retention_chunk_device_ms.brumby",
            "retention_step_roofline_pct.brumby",
            "retention_proj_device_ms.brumby", "mlp_device_ms.brumby",
            "head_device_ms.brumby", "kernel_fallbacks.brumby",
            "state_gib.brumby", "decode_tick_ms.serve",
            "tick_device_ms.serve", "device_idle_pct.serve",
            "peak_hbm_gib.serve", "slot_occupancy.serve", "itl_p99_ms.serve",
            "ttft_p90_ms.serve", "admit_to_first_ms.serve",
            "gen_late_p95_ms.serve", "gap_read_ms.serve",
            "gap_host_ms.serve", "gap_launch_ms.serve",
            "gap_outside_ms.serve"} <= layer
    # what reads keys and values, or needs account.VOCABULARY to know the
    # new scopes, is not this cell's
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
    argument, no gauge: every reader says None and none raises."""
    obs = {"kind": "serve", "trace": None, "peaks": {"hbm_bytes_per_s": 1.0}}
    for scope in readers.SCOPES:
        assert readers.scope_tick_ms(obs, {"scope": scope}) is None
    assert readers.retention_step_roofline_pct(obs, {}) is None
    assert readers.step_cost([{"kv_positions": 4}])["bytes"] == 0.0


def test_roofline_divides_the_spans_bytes_by_the_scopes_time(monkeypatch):
    ticks = [{"kv_positions": 0, "state_bytes": 819_000_000},
             {"kv_positions": 0, "state_bytes": 819_000_000}]
    monkeypatch.setattr(account, "slice_ticks", lambda obs: ticks)
    monkeypatch.setattr(readers, "_scope_ps", lambda obs: {
        "retention_step": 4e9, "retention_chunk": 0, "retention_proj": 2e9,
        "mlp": 6e9, "head": 1e9})             # picoseconds
    obs = {"kind": "serve", "trace": {}, "peaks": {"hbm_bytes_per_s": 819e9}}
    # 2 x 0.819 GB at 819 GB/s is 2 ms at least; the scope took 4 ms
    assert readers.retention_step_roofline_pct(obs, {}) == pytest.approx(50.0)
    assert readers.scope_tick_ms(obs, {"scope": "mlp"}) == pytest.approx(3.0)
    assert readers.scope_tick_ms(obs, {"scope": "retention_chunk"}) == 0.0


def test_sound_run_is_correct_and_counts_its_state():
    line, out = last_line()
    assert line["correct"] is True and line["failed"] == 0, out
    assert line["rehearsal"] is True and line["metrics"] == {}
    held = readers.state_gib({}, {})
    kw = harness.load_cell(SPEC, CELL, rehearse=True)["config"]["model"][
        "kwargs"]
    from paddle_tpu.ops.power_retention import state_rows
    d = kw["head_dim"]
    per_slot = kw["num_hidden_layers"] * kw["num_key_value_heads"] * \
        (state_rows(d) * d + d * d) * 4
    assert held * 2 ** 30 >= 3 * per_slot       # every engine of this process


@pytest.mark.parametrize("name", [f for f in faults.FAULTS
                                  if f != "bf16_state"])
def test_planted_fault_is_not_correct(name):
    faults.note_config(harness.load_cell(SPEC, CELL, rehearse=True)["config"])
    undo = faults.planted(name)
    try:
        line, out = last_line()
    finally:
        undo()
    assert line["correct"] is False, out
    row = [json.loads(l) for l in out.splitlines()
           if '"served_logit_deficit_max"' in l][-1]
    assert row["ok"] is False and row["value"] > 10 * row["limit"], row


def test_bf16_state_fault_reaches_the_served_state():
    """Planted in the view's ``read``, so in the decode tick and in the
    prefill alike: every state element a served engine holds is a bf16
    number.  Whether ``correct`` turns false is the chip's to say (PERF.md
    section 2): a rehearsal's sequences are too short for bf16 to lose
    an increment."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import weights as W
    from benchmark.drivers import serve
    parts = harness.load_cell(SPEC, CELL, rehearse=True)
    config = parts["config"]
    harness.start_jax(1, rehearse=True)
    flat = W.make_weights(5, reference.param_spec(config["model"]["kwargs"]),
                          config["init"], config["dtype"])
    states = {}
    for planted in (False, True):
        undo = faults.planted("bf16_state") if planted else (lambda: None)
        try:
            engine = serve.build_engine(config, flat)
            engine.add_request(np.arange(1, 20, dtype=np.int32),
                               max_new_tokens=12, eos_id=None)
            engine.run()
            states[planted] = [np.asarray(x) for x in
                               jax.tree_util.tree_leaves(engine.cache.layers)]
        finally:
            undo()
    rounded = lambda x: np.asarray(
        jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    assert all(np.array_equal(x, rounded(x)) for x in states[True])
    assert not all(np.array_equal(x, rounded(x)) for x in states[False])


def test_fp8_reference_fails_the_check():
    """The precision step below bf16, in the program's place."""
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
