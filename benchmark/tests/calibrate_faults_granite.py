#!/usr/bin/env python3
"""``calibrate_faults.py`` for the Granite 4.0-H serving cell: read, on
the chip, at the cell's own size and init, what ``correct`` compares when
the program is BROKEN in one of the eight ways ``test_granite_cell.py``
plants on the CPU (``FAULTS`` below), beside a sound run and the fp8
reference on the same seed.  The limits in
``configs/granite-4.0-h-micro-serve.json`` have to fail each of them
(PERF.md gives the readings); the benchmark's own runs never run this.

Each fault is planted where the chip's path runs it: in the cache's
``with_slot`` (the handover from prefill to decode), in the chunked
scan's ``_chunked_from``, in the model's layers as they are built, in
the mixer's shared function, and for the attention that reads one row
short in the rows' ``write_attend`` (a scatter and the plain decode
kernel where the sound step's kernel stores the token itself).

    python3 benchmark/tests/calibrate_faults_granite.py [--seeds 1] \
        [--faults a,b] [--first-seed N] [--seconds 40] [--rehearse]
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

CELL = "serve_granite4h_micro_longout_c64"


def _modules():
    import importlib
    return tuple(importlib.import_module("paddle_tpu." + name) for name in (
        "ops.ssd_scan", "models.recurrent_cache", "models.nemotron_h",
        "models.granite_hybrid", "models.gpt"))


def _handover(real, field, mamba_view):
    """The prefill's state (or window) is dropped: the slot decodes from
    zeros."""
    def broken(self, i, slot, view):
        import jax.numpy as jnp
        from dataclasses import replace
        if isinstance(view, mamba_view):
            view = replace(view, **{field: jnp.zeros_like(
                getattr(view, field))})
        return real(self, i, slot, view)
    return broken


def _no_state_between_chunks(real):
    """Every chunk starts from zero; the state handed on is the last
    chunk's own."""
    def broken(x, dt, a_neg, b_mat, c_mat, q, state):
        import jax.numpy as jnp
        ys, last = [], None
        for lo in range(0, x.shape[1], q):
            cut = lambda t: t[:, lo:lo + q]
            y, last = real(cut(x), cut(dt), a_neg, cut(b_mat), cut(c_mat),
                           q, None)
            ys.append(y)
        return jnp.concatenate(ys, axis=1), last
    return broken


def _built_with(real, **fields):
    """The layer as built, then the named attributes overwritten."""
    def broken(self, cfg, *args):
        real(self, cfg, *args)
        for name, value in fields.items():
            setattr(self, name, value)
    return broken


def _no_skip(real):
    def broken(x, w_in, conv_w, conv_b, dt_bias, a_log, d_skip, *rest):
        import jax.numpy as jnp
        return real(x, w_in, conv_w, conv_b, dt_bias, a_log,
                    jnp.zeros_like(d_skip), *rest)
    return broken


def _one_row_short(real):
    """The decode step's attention without the newest row: it reads the
    ``lengths`` rows written before this token."""
    def broken(self, q, k, v, lengths):
        import jax
        import jax.numpy as jnp
        from paddle_tpu import ops
        if q.shape[1] != 1:
            return real(self, q, k, v, lengths)
        with jax.named_scope("kv_write"):
            kv = self.write(k, v, lengths)
        with jax.named_scope("decode_attn"):
            out = ops.decode_attention(
                q[:, 0].astype(kv.k.dtype), kv.k, kv.v,
                jnp.maximum(lengths.astype(jnp.int32), 1))
        return out[:, None], kv
    return broken


def faults(name):
    """[(object, attribute, broken value)] of the fault `name`."""
    scan, cache, mixer, model, rows = _modules()
    hybrid = cache.HybridStateCache
    return {
        "state_handover": lambda: [(hybrid, "with_slot", _handover(
            hybrid.with_slot, "state", cache.MambaLayerView))],
        "window_handover": lambda: [(hybrid, "with_slot", _handover(
            hybrid.with_slot, "window", cache.MambaLayerView))],
        "no_state_between_chunks": lambda: [(
            scan, "_chunked_from",
            _no_state_between_chunks(scan._chunked_from))],
        "residual_one": lambda: [(
            model.GraniteHybridLayer, "__init__", _built_with(
                model.GraniteHybridLayer.__init__, residual=1.0))],
        "attention_scale": lambda: [(
            model.GraniteAttention, "__init__", _built_with(
                model.GraniteAttention.__init__, q_scale=1.0))],
        "no_skip": lambda: [(model, "mamba2_mixer",
                             _no_skip(model.mamba2_mixer))],
        "no_gate": lambda: [(mixer, "_gated", lambda y, z: y)],
        "one_row_short": lambda: [(
            rows.DenseKVLayer, "write_attend",
            _one_row_short(rows.DenseKVLayer.write_attend))],
    }[name]()


FAULTS = ("state_handover", "window_handover", "no_state_between_chunks",
          "residual_one", "attention_scale", "no_skip", "no_gate",
          "one_row_short")


def planted(name):
    """Plant the fault; returns what undoes it."""
    undo = []
    for obj, attr, broken in faults(name):
        sound = obj.__dict__[attr]
        setattr(obj, attr, broken)
        undo.append((obj, attr, sound))
    return lambda: [setattr(*u) for u in undo]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--first-seed", type=int, default=2_200_047_457)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--control-precisions", default="fp8")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import harness
    parts = harness.load_cell(harness.load_spec(), CELL, args.rehearse)
    try:
        jax, devices = harness.start_jax(1, args.rehearse)
    except harness.NoResult as e:
        print(f"calibrate_faults_granite: {e}", file=sys.stderr)
        return 2
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
