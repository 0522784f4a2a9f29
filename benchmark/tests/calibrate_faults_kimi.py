#!/usr/bin/env python3
"""``calibrate_faults.py`` for the Kimi-Linear training cell: read, on
the chip, at the cell's own size and init, what ``correct`` compares when
the program is BROKEN in one of the five ways ``test_kimi_cell.py``
plants on the CPU (``FAULTS`` below), beside a sound run and the fp8
reference on the same seeds.  The limits in
``configs/kimi-linear-ep32-train.json`` have to fail each of them by one
of the cell's limits (PERF.md gives the readings); the benchmark's own
runs never run this.

    python3 benchmark/tests/calibrate_faults_kimi.py [--seeds 2] \
        [--fault-seeds 1] [--faults a,b] [--first-seed N] [--rehearse]
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

CELL = "train_kimi_linear_ep32_seq8192"


def _scan_without_carried_state(real):
    """Every chunk taken for a sequence of its own: nothing is passed
    from chunk to chunk."""
    def broken(q, k, v, g, beta, c):
        cut = lambda t: t.reshape((-1, c) + t.shape[2:])
        o = real(cut(q), cut(k), cut(v), cut(g), cut(beta), c)
        return o.reshape(v.shape)
    return broken


def _one_decay_a_head(real):
    """The head's mean log-decay on every key channel: the scalar-decay
    gated delta rule."""
    def broken(q, k, v, g, beta, c):
        import jax.numpy as jnp
        mean = jnp.mean(g, axis=-1, keepdims=True)
        return real(q, k, v, jnp.broadcast_to(mean, g.shape), beta, c)
    return broken


def _no_delta_correction(m_kk, beta, k_plus, v):
    """``S_t = Diag(alpha) S + beta k v^T``: A = 0, so T = Diag(beta), and
    nothing is taken back from what the state already holds (W = 0)."""
    import jax.numpy as jnp
    return jnp.zeros_like(k_plus), (beta[..., None] * v).astype(v.dtype)


def _no_shared_expert(self, x):
    return self.routed(x)


def _no_k_pe(real):
    """The keys' 64 shared channels left at zero."""
    def broken(self, x, *weights):
        import jax.numpy as jnp
        q, k, v = real(self, x, *weights)
        nope = self.cfg.qk_nope_head_dim
        return q, jnp.concatenate(
            [k[..., :nope], jnp.zeros_like(k[..., nope:])], -1), v
    return broken


def fault(name):
    """(object, attribute, broken value) of the fault `name`."""
    scan = importlib.import_module("paddle_tpu.ops.kda_scan")
    from paddle_tpu.models import kimi_linear as model
    return {
        "scan_state": lambda: (scan, "_chunked",
                               _scan_without_carried_state(scan._chunked)),
        "delta_correction": lambda: (scan, "_wy", _no_delta_correction),
        "scalar_decay": lambda: (scan, "_chunked",
                                 _one_decay_a_head(scan._chunked)),
        "shared_expert": lambda: (model.KimiMoE, "forward",
                                  _no_shared_expert),
        "k_pe": lambda: (model.MLAttention, "_qkv",
                         _no_k_pe(model.MLAttention._qkv)),
    }[name]()


FAULTS = ("scan_state", "delta_correction", "scalar_decay", "shared_expert",
          "k_pe")


def planted(name):
    """Plant the fault; returns what undoes it."""
    obj, attr, broken = fault(name)
    sound = obj.__dict__[attr]
    setattr(obj, attr, broken)
    return lambda: setattr(obj, attr, sound)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--fault-seeds", type=int, default=1)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--controls", type=int, default=None,
                    help="seeds that also read the fp8 reference "
                         "(default: all)")
    ap.add_argument("--first-seed", type=int, default=2_200_039_595)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import harness, trafficgen, weights as W
    from benchmark.drivers import train as T
    parts = harness.load_cell(harness.load_spec(), CELL, args.rehearse)
    try:
        jax, devices = harness.start_jax(1, args.rehearse)
    except harness.NoResult as e:
        print(f"calibrate_faults_kimi: {e}", file=sys.stderr)
        return 2
    config, mix = parts["config"], parts["mix"]
    kw = config["model"]["kwargs"]
    ref = importlib.import_module(config["reference"])
    spec = ref.param_spec(kw)
    controls = args.seeds if args.controls is None else args.controls

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
        row = {"seed": seed}
        if n < controls:
            low = T.reference_readings(ref, config, make(), batches,
                                       precision="fp8")
            row["control_fp8"] = calibrate.gaps(T, low, want)
        row["program"] = program(make, batches, want)
        print(json.dumps(row), flush=True)
        for name in args.faults.split(",") if n < args.fault_seeds else ():
            undo = planted(name)
            try:
                row = {"seed": seed, "fault": name,
                       "program": program(make, batches, want)}
            finally:
                undo()
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
