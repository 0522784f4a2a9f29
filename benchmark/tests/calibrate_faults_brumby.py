#!/usr/bin/env python3
"""``calibrate_faults.py`` for the Brumby serving cell: read, on the chip,
at the cell's own size and init, what ``correct`` compares when the
program is BROKEN in one of the six ways ``test_brumby_cell.py`` plants
on the CPU (``FAULTS`` below), beside a sound run and the fp8 reference
on the same seed.  The limits in ``configs/brumby-14b-l8-serve.json``
have to fail each of them (PERF.md gives the readings); the benchmark's
own runs never run this.

Each fault is planted where BOTH paths run it: in the model's
projections, in the cache's view, in ``ops.power_retention``'s shared
pieces, and for the one that lives inside the step's arithmetic
(``degree_one``) in the kernel's entry point as well as in XLA's form, so
that the chip's path is the broken one (PERF.md section 7 d).

    python3 benchmark/tests/calibrate_faults_brumby.py [--seeds 1] \
        [--faults a,b] [--first-seed N] [--seconds 20] [--rehearse]
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import calibrate  # noqa: E402

CELL = "serve_brumby_l8_longout_closed"


def _modules():
    import importlib
    return (importlib.import_module("paddle_tpu.ops.power_retention"),
            importlib.import_module("paddle_tpu.ops.power_retention_kernel"),
            importlib.import_module("paddle_tpu.models.recurrent_cache"),
            importlib.import_module("paddle_tpu.models.brumby"))


def _state_zeroed_at_the_handover(real):
    """The prefill's state is dropped: the slot decodes from zero."""
    def broken(self, i, slot, view):
        import jax
        import jax.numpy as jnp
        from dataclasses import replace
        zero = jax.tree_util.tree_map(jnp.zeros_like, view.state)
        return real(self, i, slot, replace(view, state=zero))
    return broken


def _projection(real, change):
    def broken(self, x, positions):
        return change(self, *real(self, x, positions))
    return broken


def _no_gate(self, q, k, v, log_g):
    import jax.numpy as jnp
    return q, k, v, jnp.zeros_like(log_g)


def _no_rotary_on_keys(model):
    """``rope`` that leaves the keys (the tensors with the KV heads'
    count) as they are."""
    real = model.rope

    def broken(x, positions, theta):
        return x if x.shape[2] in _KV_HEADS else real(x, positions, theta)
    return broken


_KV_HEADS = set()   # the head count that marks a tensor as keys


def _degree_one_phi(real):
    """``phi(u) . phi(w) == u . w``: the key itself in the first rows."""
    def broken(u):
        import jax.numpy as jnp
        full = real(u)
        d = u.shape[-1]
        return jnp.concatenate(
            [u.astype(full.dtype),
             jnp.zeros(full.shape[:-1] + (full.shape[-1] - d,), full.dtype)],
            axis=-1)
    return broken


def _degree_one_kernel(pr):
    def broken(q, k, v, log_g, state, eps):
        return pr.step_reference(q, k, v, log_g, state, eps)
    return broken


def _no_normaliser(num, den, eps):
    return num


def broken_read(self, q, real):
    """The state rounded to bf16 after every update."""
    import jax
    import jax.numpy as jnp
    from dataclasses import replace
    y, view = real(self, q)
    state = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16).astype(x.dtype), view.state)
    return y, replace(view, state=state)


def faults(name):
    """[(object, attribute, broken value)] of the fault `name`."""
    pr, kernel, cache, model = _modules()
    view, mixer = cache.RetentionLayerView, model.BrumbyRetention
    return {
        "handover": lambda: [(
            cache.RecurrentStateCache, "with_slot",
            _state_zeroed_at_the_handover(
                cache.RecurrentStateCache.with_slot))],
        "no_gate": lambda: [(mixer, "_project",
                             _projection(mixer._project, _no_gate))],
        "degree_one": lambda: [(pr, "phi", _degree_one_phi(pr.phi)),
                               (kernel, "step", _degree_one_kernel(pr))],
        "no_normaliser": lambda: [(pr, "normalise", _no_normaliser)],
        "no_key_rotary": lambda: [(model, "rope",
                                   _no_rotary_on_keys(model))],
        "bf16_state": lambda: [(view, "read",
                                lambda self, q, real=view.read:
                                broken_read(self, q, real))],
    }[name]()


FAULTS = ("handover", "no_gate", "degree_one", "no_normaliser",
          "no_key_rotary", "bf16_state")


def planted(name):
    """Plant the fault; returns what undoes it."""
    undo = []
    for obj, attr, broken in faults(name):
        sound = obj.__dict__[attr]
        setattr(obj, attr, broken)
        undo.append((obj, attr, sound))
    return lambda: [setattr(*u) for u in undo]


def note_config(config):
    """The KV head count that ``no_key_rotary`` tells keys by."""
    _KV_HEADS.clear()
    _KV_HEADS.add(config["model"]["kwargs"]["num_key_value_heads"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--first-seed", type=int, default=2_200_047_457)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control-precisions", default="fp8")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import harness
    parts = harness.load_cell(harness.load_spec(), CELL, args.rehearse)
    try:
        jax, devices = harness.start_jax(1, args.rehearse)
    except harness.NoResult as e:
        print(f"calibrate_faults_brumby: {e}", file=sys.stderr)
        return 2
    note_config(parts["config"])
    ctx = {"jax": jax, "devices": devices, "config": parts["config"],
           "mix": parts["mix"], "seconds": args.seconds, "trace": False,
           "workload": CELL,
           "control_precisions": args.control_precisions.split(",")}
    for n in range(args.seeds):
        seed = args.first_seed + 7919 * n
        calibrate.calibrate_serve(ctx, [seed], 1)          # sound + control
        for name in [f for f in args.faults.split(",") if f]:
            undo = planted(name)
            try:
                print(json.dumps({"fault": name}), flush=True)
                calibrate.calibrate_serve(ctx, [seed], 0)
            finally:
                undo()
    return 0


if __name__ == "__main__":
    sys.exit(main())
