#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, the numbers that
``correct`` compares: from sound runs of the program over many seeds, and
from the control (the plain reference computed in fp8 or int8, the
precision step below the configuration's bf16).  The limits in the configurations'
files are set from these two readings (PERF.md gives them); the
benchmark's own runs never run this.

    python3 benchmark/tests/calibrate.py --workload <cell> --seeds 12 \
        --control-seeds 3 [--seconds 8] [--rehearse]

One process, many seeds: set-up is paid once per seed, compilation once.
Serving seeds each get a short window at the cell's own load, long enough
to finish the mix's longest requests.
"""
import argparse
import gc
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def calibrate_train(ctx, seeds, control_seeds):
    from benchmark import trafficgen, weights as W
    from benchmark.drivers import train as T
    jax, config, mix = ctx["jax"], ctx["config"], ctx["mix"]
    kw = config["model"]["kwargs"]
    ref = importlib.import_module(config["reference"])
    spec = ref.param_spec(kw)
    rows = []
    for n, seed in enumerate(seeds):
        make = lambda dtype="float32": W.make_weights(
            seed, spec, config["init"], dtype)
        batches = trafficgen.train_batches(mix, kw["vocab_size"], seed)
        t0 = time.perf_counter()
        want = T.reference_readings(ref, config, make(), batches)
        row = {"seed": seed, "reference_s": time.perf_counter() - t0}
        if n < control_seeds:
            for prec in ctx["control_precisions"]:
                low = T.reference_readings(ref, config, make(), batches,
                                           precision=prec)
                row["control_" + prec] = gaps(T, low, want)
        trainer = T.build_trainer(jax, ctx["devices"], config, make())
        got = T.program_readings(jax, trainer, config, make, batches)
        row["program"] = gaps(T, got, want)
        row["losses"] = got["losses"]
        T.free_trainer(jax, trainer)
        del trainer
        gc.collect()
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def gaps(T, got, want):
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(got["losses"], want["losses"]))
    g, gl = T.worst_leaf_gap(got["grad_norms"], want["grad_norms"])
    d, dl = T.worst_leaf_gap(got["delta_norms"], want["delta_norms"])
    import statistics
    per_leaf = sorted(T.leaf_gaps(got["grad_norms"],
                                  want["grad_norms"]).values())
    return {"loss_gap": loss, "grad_gap": g, "grad_leaf": gl,
            "delta_gap": d, "delta_leaf": dl,
            "grad_gap_median_leaf": statistics.median(per_leaf),
            "grad_gap_p90_leaf": per_leaf[int(0.9 * len(per_leaf))],
            "grad_gap_whole": T.whole_norm_gap(got["grad_norms"],
                                               want["grad_norms"]),
            "delta_gap_whole": T.whole_norm_gap(got["delta_norms"],
                                                want["delta_norms"])}


def calibrate_serve(ctx, seeds, control_seeds):
    from benchmark import trafficgen, weights as W
    from benchmark.drivers import serve as S
    config, mix = ctx["config"], ctx["mix"]
    kw = config["model"]["kwargs"]
    chk = config["check"]
    ref = importlib.import_module(config["reference"])
    spec = ref.param_spec(kw)
    horizon = mix["lead_in_s"] + ctx["seconds"] + 3.0
    rows = []
    for n, seed in enumerate(seeds):
        plan = trafficgen.requests(mix, kw["vocab_size"], seed, horizon)
        flat = W.make_weights(seed, spec, config["init"], config["dtype"])
        engine = S.build_engine(config, flat)
        run = S.drive({**ctx, "seed": seed}, engine, mix, plan, None)
        win = S.reduce_window(run)
        S.release(engine)
        del engine
        gc.collect()
        load = run["load"]
        stacked = ref.stack(flat, kw)
        picks = S.sample_for_check(load, seed, int(chk["sample_requests"]))
        row = {"seed": seed, "requests": len(picks), "failed": win["failed"],
               "finished": win["finished"], "tokens": 0,
               "program_deficit": 0.0, "exact": 0}
        controls = ctx["control_precisions"] if n < control_seeds else []
        for prec in controls:
            row["control_" + prec] = 0.0
        distinct = set()
        for rid in picks:
            prompt = load.plan[load.sent[rid]["index"]]["prompt"]
            toks = load.records[rid]["tokens_out"]
            d, lg = S.logit_deficits(ref, kw, stacked, prompt, toks,
                                     int(chk["pad_to"]))
            row["program_deficit"] = max(row["program_deficit"],
                                         float(d.max()))
            row["exact"] += int((d == 0).sum())
            row["tokens"] += len(toks)
            distinct.update(int(t) for t in toks)
            for prec in controls:
                c, _ = S.logit_deficits(ref, kw, stacked, prompt, toks,
                                        int(chk["pad_to"]), prec,
                                        against=lg)
                row["control_" + prec] = max(row["control_" + prec],
                                             float(c.max()))
                row[f"control_{prec}_moved"] = \
                    row.get(f"control_{prec}_moved", 0) + int((c > 0).sum())
        row["distinct_share"] = len(distinct) / max(row["tokens"], 1)
        del stacked, flat
        gc.collect()
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_000)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control-precisions", default="fp8")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import harness
    spec = harness.load_spec()
    parts = harness.load_cell(spec, args.workload, args.rehearse)
    try:
        jax, devices = harness.start_jax(parts["cell"]["chips"],
                                         args.rehearse)
    except harness.NoResult as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    ctx = {"jax": jax, "devices": devices, "config": parts["config"],
           "mix": parts["mix"], "seconds": args.seconds, "trace": False,
           "workload": args.workload,
           "control_precisions": args.control_precisions.split(",")}
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    kind = parts["config"]["driver"]["kind"]
    rows = (calibrate_train if kind == "train" else calibrate_serve)(
        ctx, seeds, args.control_seeds)
    keys = ("loss_gap", "grad_gap", "delta_gap", "grad_gap_median_leaf",
            "grad_gap_p90_leaf", "grad_gap_whole", "delta_gap_whole") \
        if kind == "train" else ("program_deficit",)
    summary = {"workload": args.workload, "seeds": len(rows)}
    for k in keys:
        sound = [r["program"][k] if kind == "train" else r[k] for r in rows]
        summary[f"sound_max_{k}"] = max(sound)
    for prec in ctx["control_precisions"]:
        name = "control_" + prec
        for k in keys if kind == "train" else ("deficit",):
            ctl = [r[name][k] if kind == "train" else r[name]
                   for r in rows if name in r]
            summary[f"{name}_min_{k}"] = min(ctl) if ctl else None
    print(json.dumps({"calibration": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
