"""What every cell's run shares: finding a cell's files by the names in
``BENCHMARK.json``, the device check, the compile cache, building the
program's model from a configuration file, and the per-layer readers.
"""
from __future__ import annotations

import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoResult(Exception):
    """The run cannot give a result (no chip, no program): exit non-zero
    and print no result line."""


def say(what: str, **fields) -> None:
    print(json.dumps({"bench": what, **fields}), flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_spec() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def load_cell(spec: dict, workload: str, rehearse: bool = False) -> dict:
    """The cell, its configuration file and its traffic file."""
    from . import trafficgen
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = dict(cells[workload])
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = load_json(ROOT, entry["file"])
    if rehearse:
        for key, over in config.get("rehearse", {}).items():
            config[key] = {**config.get(key, {}), **over} \
                if isinstance(over, dict) else over
    return {"cell": cell, "config": config,
            "mix": trafficgen.load_mix(cell["traffic"], rehearse)}


def metrics_for(spec: dict, group: str, workload: str) -> list:
    """The metrics of `group` that this cell reports."""
    return [m for m in spec[group]
            if "workloads" not in m or workload in m["workloads"]]


def resolve(path: str):
    """'package.module:attr' -> the object."""
    module, _, attr = path.partition(":")
    return getattr(importlib.import_module(module), attr)


def start_jax(chips: int, rehearse: bool):
    """Import JAX, point its persistent compile cache inside the
    checkout (or where JAX_COMPILATION_CACHE_DIR says), and refuse to
    measure without the chips the cell asks for."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if not rehearse and platform != "tpu":
        raise NoResult(f"JAX found no TPU (platform {platform!r}): nothing "
                       f"is measured on a CPU; --rehearse runs tiny sizes")
    if len(devices) < chips:
        raise NoResult(f"the cell needs {chips} chip(s), JAX reports "
                       f"{len(devices)}")
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = os.path.join(ROOT, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        import paddle_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        raise NoResult(f"the program is not in this checkout: {e}")
    if rehearse:
        from paddle_tpu.ops import set_interpret_mode
        set_interpret_mode(True)
    return jax, devices[:chips]


def peaks_for(device_kind: str) -> dict:
    table = load_json(HERE, "peaks.json")
    if device_kind not in table or device_kind.startswith("_"):
        raise NoResult(f"device kind {device_kind!r} is not in "
                       f"benchmark/peaks.json")
    return table[device_kind]


def device_record(devices, peak_bytes) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak_bytes}


def memory_peak(devices):
    """Peak bytes in use on the fullest chip (None where the backend does
    not report it, as the CPU's)."""
    peaks = []
    for d in devices:
        ms = d.memory_stats()
        if ms and "peak_bytes_in_use" in ms:
            peaks.append(int(ms["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def build_model(config: dict, weights: dict):
    """The program's model from the configuration's import path and
    keyword arguments, holding the benchmark's weights (by name)."""
    cls = resolve(config["model"]["class"])
    cfg_cls = resolve(config["model"]["config_class"])
    model = cls(cfg_cls(**config["model"]["kwargs"]))
    params = dict(model.named_parameters())
    if set(params) != set(weights):
        odd = sorted(set(params) ^ set(weights))[:6]
        raise RuntimeError(f"the model's parameters and the reference's "
                           f"spec differ, e.g. {odd}")
    for name, p in params.items():
        if tuple(p.data.shape) != tuple(weights[name].shape):
            raise RuntimeError(f"{name}: model {p.data.shape}, reference "
                               f"{weights[name].shape}")
        p.data = weights[name]
    return model


class Check:
    """The numbers `correct` rests on, each printed beside its limit."""

    def __init__(self):
        self.rows = []

    def at_most(self, name: str, value, limit) -> None:
        ok = value is not None and value == value and value <= limit
        self.rows.append({"check": name, "value": value, "limit": limit,
                          "rule": "<=", "ok": bool(ok)})

    def at_least(self, name: str, value, limit) -> None:
        ok = value is not None and value == value and value >= limit
        self.rows.append({"check": name, "value": value, "limit": limit,
                          "rule": ">=", "ok": bool(ok)})

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def report(self) -> None:
        for row in self.rows:
            say("check", **row)


class Tracer:
    """jax.profiler around a short slice, host events on, Python's
    tracer off (it slows the host it is there to watch)."""

    def __init__(self, jax, workload: str):
        self.jax = jax
        self.dir = os.path.join(ROOT, ".bench_trace", workload)

    def start(self) -> None:
        import shutil
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> dict:
        from . import trace_reduce
        self.jax.profiler.stop_trace()
        return trace_reduce.reduce_dir(self.dir)


def read_layer_metrics(spec: dict, workload: str, obs: dict) -> dict:
    """Each per-layer metric of the cell through its own reader
    (``benchmark/layer_metrics/<name>.json`` names it).  A reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for metric in metrics_for(spec, "per_layer", workload):
        desc = load_json(HERE, "layer_metrics", metric["name"] + ".json")
        value = resolve(desc["reader"])(obs, desc.get("params", {}))
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def percentile(values, q: float):
    """None for an empty sample."""
    import numpy as np
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
