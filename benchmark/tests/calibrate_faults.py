#!/usr/bin/env python3
"""Read, on the chip, at the Nemotron-H training cell's own size and
init, what ``correct`` compares when the program is BROKEN: the two
faults ``test_nemotron_cell.py`` plants on the CPU (the shared expert
left out; the scan passing no state from chunk to chunk), beside a sound
run and the fp8 reference on the same seeds.  The limits in
``configs/nemotron3-nano-ep16-train.json`` have to fail each of them by
one of the cell's limits (PERF.md gives the readings); the benchmark's
own runs never run this.

    python3 benchmark/tests/calibrate_faults.py [--seeds 2] [--fault-seeds 1] \
        [--first-seed N] [--rehearse]
"""
import argparse
import gc
import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import calibrate  # noqa: E402

CELL = "train_nemotron3_ep16_seq8192"


def planted(fault):
    """Plant `fault` in the program; returns what undoes it."""
    from test_nemotron_cell import (no_shared_expert,
                                    scan_without_carried_state)
    if fault == "shared_expert":
        from paddle_tpu.models import nemotron_h as mod
        name, broken = "_fn", staticmethod(no_shared_expert)
        sound = staticmethod(mod.NemotronHMLP._fn)
        mod = mod.NemotronHMLP
    else:
        mod = importlib.import_module("paddle_tpu.ops.ssd_scan")
        name, sound = "_chunked", mod._chunked
        broken = scan_without_carried_state(sound)
    setattr(mod, name, broken)
    return lambda: setattr(mod, name, sound)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--fault-seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=2_200_039_595)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import harness, trafficgen, weights as W
    from benchmark.drivers import train as T
    parts = harness.load_cell(harness.load_spec(), CELL, args.rehearse)
    try:
        jax, devices = harness.start_jax(1, args.rehearse)
    except harness.NoResult as e:
        print(f"calibrate_faults: {e}", file=sys.stderr)
        return 2
    config, mix = parts["config"], parts["mix"]
    kw = config["model"]["kwargs"]
    ref = importlib.import_module(config["reference"])
    spec = ref.param_spec(kw)

    def program(make, batches, want):
        trainer = T.build_trainer(jax, devices, config, make())
        got = T.program_readings(jax, trainer, config, make, batches)
        T.free_trainer(jax, trainer)
        del trainer
        gc.collect()
        return calibrate.gaps(T, got, want)

    for n in range(args.seeds):
        seed = args.first_seed + 7919 * n
        make = lambda dtype="float32": W.make_weights(
            seed, spec, config["init"], dtype)
        batches = trafficgen.train_batches(mix, kw["vocab_size"], seed)
        want = T.reference_readings(ref, config, make(), batches)
        low = T.reference_readings(ref, config, make(), batches,
                                   precision="fp8")
        row = {"seed": seed, "control_fp8": calibrate.gaps(T, low, want),
               "program": program(make, batches, want)}
        print(json.dumps(row), flush=True)
        for fault in ("shared_expert", "scan_state") \
                if n < args.fault_seeds else ():
            undo = planted(fault)
            try:
                row = {"seed": seed, "fault": fault,
                       "program": program(make, batches, want)}
            finally:
                undo()
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
