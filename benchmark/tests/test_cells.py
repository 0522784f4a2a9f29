"""Every cell's files load, and every metric a cell reports is
declared: the data the harness is driven by hangs together."""
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, trafficgen  # noqa: E402

SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_contract_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    for entry in (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"]
                  + SPEC["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
    for m in SPEC["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert "bound" not in m
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 4)


def test_every_cell_loads_and_reports_what_is_declared():
    cells = {w["name"] for w in SPEC["workloads"]}
    used = set()
    for cell in SPEC["workloads"]:
        parts = harness.load_cell(SPEC, cell["name"])
        used.add(cell["config"])
        config, mix = parts["config"], parts["mix"]
        assert config["driver"]["kind"] in ("train", "serve")
        assert mix["kind"] in ("train_steps", "closed_loop", "open_loop")
        assert len(config["source"]) <= 200
        harness.resolve(config["reference"] + ":param_spec")
        e2e = harness.metrics_for(SPEC, "end_to_end", cell["name"])
        layer = harness.metrics_for(SPEC, "per_layer", cell["name"])
        assert {"setup_s"} < {m["name"] for m in e2e}
        assert layer
        reported = {m["name"] for m in e2e}
        for m in layer:
            # a layer metric moves a metric that its cell reports
            assert m["moves"] in reported, (cell["name"], m["name"])
            desc = harness.load_json(harness.HERE, "layer_metrics",
                                     m["name"] + ".json")
            assert callable(harness.resolve(desc["reader"]))
        # the rehearsal's overrides load too
        harness.load_cell(SPEC, cell["name"], rehearse=True)
    assert used == {c["name"] for c in SPEC["configs"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells


def test_paths_hold_the_files():
    for c in SPEC["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in SPEC["paths"]))
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert json.load(open(os.path.join(ROOT, c["file"])))["reduced"] \
            == c["reduced"]


def test_every_seed_offers_the_same_work():
    mixes = [f[:-5] for f in os.listdir(os.path.join(harness.HERE, "traffic"))]
    assert {w["traffic"] for w in SPEC["workloads"]} <= set(mixes)
    for name in mixes:
        mix = trafficgen.load_mix(name)
        if mix["kind"] == "train_steps":
            continue
        a = trafficgen.requests(mix, 50304, 1, 40.0)
        b = trafficgen.requests(mix, 50304, 2_500_000_011, 40.0)
        block = int(mix["block"])
        size = lambda reqs: (sorted(len(r["prompt"]) for r in reqs[:block]),
                             sorted(r["max_new"] for r in reqs[:block]))
        assert size(a) == size(b)
        assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
        if mix["kind"] == "open_loop":
            assert abs(a[block - 1]["due"] - b[block - 1]["due"]) < 1e-9
            again = trafficgen.requests(mix, 50304, 1, 40.0)
            assert [r["due"] for r in a] == [r["due"] for r in again]


def test_gamma_arrivals_keep_the_rate_and_the_burstiness():
    import numpy as np
    gaps = trafficgen._gap_quantiles(
        {"arrival": "gamma", "cv": 3.0, "rate_rps": 2.0}, 4800)
    assert abs(gaps.mean() - 0.5) < 1e-9
    assert 2.5 < gaps.std() / gaps.mean() < 3.5
    expo = trafficgen._gap_quantiles({"rate_rps": 2.0}, 4800)
    assert 0.9 < expo.std() / expo.mean() < 1.1
