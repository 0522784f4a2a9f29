#!/usr/bin/env python3
"""Run ONE cell of BENCHMARK.json once, in this process alone:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

It finds the cell's configuration, traffic mix and per-layer readers by
the names in ``BENCHMARK.json``, makes weights and inputs from ``--seed``,
warms up the cell's own shapes (set-up), measures for ``--seconds``, checks
what the timed path produced against the configuration's plain reference,
and prints one JSON object as its last line: the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics (and the device's side of a short
traced slice) with ``--trace 1``.

Without the chips the cell asks for it exits non-zero and prints no
result.  ``--rehearse`` runs tiny sizes on the CPU with interpreted
kernels: it says so, and never prints a metric.
"""
import time
T_PROCESS_START = time.perf_counter()

import argparse          # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                         "BENCHMARK.json's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes, kernels interpreted; never a "
                         "result")
    return ap.parse_args(argv)


def main(argv=None, hooks=None) -> int:
    """`hooks` lets a test swap a part of the timed path for a broken one
    (benchmark/tests); a run from the command line has none."""
    args = parse_args(argv)
    from benchmark import harness
    spec = harness.load_spec()
    parts = harness.load_cell(spec, args.workload, args.rehearse)
    cell, config = parts["cell"], parts["config"]
    seconds = args.seconds if args.seconds is not None \
        else float(spec["run_seconds"])
    try:
        jax, devices = harness.start_jax(cell["chips"], args.rehearse)
        peaks = None if args.rehearse else \
            harness.peaks_for(devices[0].device_kind)
    except harness.NoResult as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 2
    harness.say("start", workload=args.workload, seed=args.seed,
                seconds=seconds, trace=args.trace, rehearsal=args.rehearse,
                platform=devices[0].platform, kind=devices[0].device_kind,
                compile_cache_dir=jax.config.jax_compilation_cache_dir)

    import importlib
    driver = importlib.import_module(
        "benchmark.drivers." + config["driver"]["kind"])
    ctx = {"jax": jax, "devices": devices, "workload": args.workload,
           "seed": args.seed, "seconds": seconds, "trace": bool(args.trace),
           "config": config, "mix": parts["mix"], "peaks": peaks,
           "t_process_start": T_PROCESS_START, **(hooks or {})}
    result = driver.run(ctx)
    result["check"].report()

    line = {"correct": result["check"].correct,
            "attempted": result["attempted"], "failed": result["failed"]}
    device = harness.device_record(devices, result["memory_peak_bytes"])
    if args.rehearse:
        # a rehearsal proves the control flow, not the chip: no metric
        line.update(rehearsal=True, metrics={}, device=device)
        print(json.dumps(line), flush=True)
        return 0
    if args.trace:
        line["metrics"] = harness.read_layer_metrics(
            spec, args.workload, result["obs"])
        trace = result["trace"] or {}
        device["busy_s"] = trace.get("busy_s")
        device["window_s"] = trace.get("window_s")
        line["breakdown"] = {"device_ops": trace.get("device_ops", []),
                             "idle_gaps": trace.get("idle_gaps", [])}
        harness.say("trace_modules", modules=trace.get("modules"))
    else:
        wanted = harness.metrics_for(spec, "end_to_end", args.workload)
        line["metrics"] = {
            m["name"]: {"value": result["end_to_end"][m["name"]],
                        "unit": m["unit"]}
            for m in wanted if m["name"] in result["end_to_end"]}
    line["device"] = device
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
