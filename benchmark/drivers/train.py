"""Training cells: ``SpmdTrainer.train_step`` over the batches of a
``train_steps`` mix, each step ended by the host's read of its loss.

Order of a run: ONE trainer is built from the seeded weights and driven
through its first three steps by the window's own call and feed; what the
check compares is read from its state there (set-up), and the same object
goes on into the window.  After the window the trainer is freed and the
plain reference follows the same three steps from the same weights (so
the peak memory stays the program's, and its time is not set-up).
"""
from __future__ import annotations

import gc
import importlib
import statistics
import time

import numpy as np

from .. import harness, trafficgen, weights as weights_mod
from ..harness import say

CHECK_STEPS = 3


def _leaf_norms(jax, tree: dict) -> dict:
    import jax.numpy as jnp
    norms = jax.jit(lambda t: {
        k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
        for k, v in t.items()})(tree)
    return {k: float(v) for k, v in jax.device_get(norms).items()}


def leaf_gaps(got: dict, want: dict) -> dict:
    """|got - want| of every leaf's norm, against the reference's norm of
    that leaf or of the median leaf, whichever is larger (some gradients
    are all but zero)."""
    floor = statistics.median(want.values())
    return {name: abs(got[name] - ref) / max(ref, floor)
            for name, ref in want.items()}


def worst_leaf_gap(got: dict, want: dict) -> tuple:
    """The largest leaf gap and its leaf."""
    worst, where = 0.0, None
    for name, gap in leaf_gaps(got, want).items():
        if not gap <= worst:          # NaN wins
            worst, where = gap, name
    return worst, where


def whole_norm_gap(got: dict, want: dict) -> float:
    """The gap of the norm over all leaves together."""
    total = lambda d: sum(v * v for v in d.values()) ** 0.5
    return abs(total(got) - total(want)) / total(want)


def reference_readings(ref_mod, config, flat, batches, precision="float32"):
    return ref_mod.train_steps(config["model"]["kwargs"], flat,
                               batches[:CHECK_STEPS],
                               config["driver"]["optimizer"]["kwargs"],
                               precision=precision)


def build_trainer(jax, devices, config: dict, flat: dict):
    """The compiled step with its state: model, optimizer, criterion,
    strategy and mesh as the configuration's file names them."""
    from paddle_tpu.distributed import SpmdTrainer, create_mesh
    from paddle_tpu.distributed.fleet import DistributedStrategy
    drv = config["driver"]
    model = harness.build_model(config, flat)
    opt = harness.resolve(drv["optimizer"]["class"])(
        parameters=model.parameters(), **drv["optimizer"]["kwargs"])
    crit = harness.resolve(drv["criterion"])()
    st = DistributedStrategy()
    for key, value in drv["strategy"].items():
        setattr(st, key, value)
    mesh = create_mesh(drv["mesh"], devices=devices)
    return SpmdTrainer(model, opt, lambda o, l: crit(o, l), mesh=mesh,
                       strategy=st)


def free_trainer(jax, trainer) -> None:
    """Delete a finished trainer's device state (copied from
    chip_smoke.py): the reference needs the memory."""
    for leaf in jax.tree_util.tree_leaves(
            (trainer.params, trainer.opt_state, trainer.buffers)):
        if not leaf.is_deleted():
            leaf.delete()
    trainer.model = None
    gc.collect()


def step(trainer, batch) -> float:
    """The window's call: one step, ended by the host's read of its loss."""
    return float(trainer.train_step(batch[0], batch[1]))


def program_readings(jax, trainer, config, flat_f32_fn, batches,
                     step_fn=step) -> dict:
    """Drive the trainer through the check's steps; the same three
    numbers as the reference gives."""
    b1 = config["driver"]["optimizer"]["kwargs"].get("beta1", 0.9)
    losses = [step_fn(trainer, batches[0])]
    moment = {n: s["moment1"] for n, s in trainer.opt_state.items()}
    grad_norms = {k: v / (1.0 - b1)
                  for k, v in _leaf_norms(jax, moment).items()}
    for i in range(1, CHECK_STEPS):
        losses.append(step_fn(trainer, batches[i]))
    start = flat_f32_fn()
    import jax.numpy as jnp
    delta = jax.jit(lambda p, s: {
        k: p[k].astype(jnp.float32) - s[k] for k in s})(
            dict(trainer.params), start)
    delta_norms = _leaf_norms(jax, delta)
    del start, delta
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta_norms}


def compare(check: harness.Check, got: dict, want: dict, limits: dict):
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(got["losses"], want["losses"]))
    grad_gap, grad_leaf = worst_leaf_gap(got["grad_norms"],
                                         want["grad_norms"])
    delta_gap, delta_leaf = worst_leaf_gap(got["delta_norms"],
                                           want["delta_norms"])
    check.at_most("loss_rel_gap_3_steps", loss_gap, limits["loss_rel_gap"])
    check.at_most("first_grad_norm_gap_worst_leaf", grad_gap,
                  limits["grad_norm_gap"])
    check.at_most("param_change_norm_gap_worst_leaf", delta_gap,
                  limits["delta_norm_gap"])
    say("check_detail", losses=got["losses"], reference_losses=want["losses"],
        grad_worst_leaf=grad_leaf, delta_worst_leaf=delta_leaf)


def run(ctx: dict) -> dict:
    jax, devices = ctx["jax"], ctx["devices"]
    config, mix, seed = ctx["config"], ctx["mix"], ctx["seed"]
    from paddle_tpu.ops import kernel_paths
    from paddle_tpu.utils import compile_counter
    model_kw = config["model"]["kwargs"]
    ref_mod = importlib.import_module(config["reference"])
    spec = ref_mod.param_spec(model_kw)
    batches = trafficgen.train_batches(mix, model_kw["vocab_size"], seed)

    def make(dtype="float32"):
        return weights_mod.make_weights(seed, spec, config["init"], dtype)

    # -- the program: one trainer, read for the check, then timed --------
    kernel_paths.reset()
    t_build = time.perf_counter()
    trainer = build_trainer(jax, devices, config, make())
    t_steps = time.perf_counter()
    step_fn = ctx.get("step_fn", step)
    got = program_readings(jax, trainer, config, make, batches,
                           step_fn=step_fn)
    say("setup", build_s=t_steps - t_build,
        check_steps_s=time.perf_counter() - t_steps,
        before_build_s=t_build - ctx["t_process_start"])

    tracer = harness.Tracer(jax, ctx["workload"]) if ctx["trace"] else None
    snap = compile_counter.snapshot()
    n = len(batches)
    step_ms, losses = [], []
    i = CHECK_STEPS
    t0 = time.perf_counter()
    setup_s = t0 - ctx["t_process_start"]
    t_last = t0
    while t_last - t0 < ctx["seconds"]:
        losses.append(step_fn(trainer, batches[i % n]))
        now = time.perf_counter()
        step_ms.append((now - t_last) * 1e3)
        t_last, i = now, i + 1
    window_s = t_last - t0
    compiles, traces = snap.new_compiles, snap.new_traces
    trace = None
    if tracer is not None:
        tracer.start()
        for _ in range(int(mix.get("trace_steps", 3))):
            step_fn(trainer, batches[i % n])
            i += 1
        trace = tracer.stop()
    peak = harness.memory_peak(devices)
    stats = {k: v for k, v in trainer.stats.items()
             if k in ("data_wait_ms", "h2d_ms", "dispatch_ms", "sync_ms")}
    paths = kernel_paths.counts()

    # -- the reference, after the program's state is freed ----------------
    free_trainer(jax, trainer)
    del trainer
    t_ref = time.perf_counter()
    want = reference_readings(ref_mod, config, make(), batches)
    say("reference", seconds=time.perf_counter() - t_ref, steps=CHECK_STEPS,
        memory_peak_bytes_program=peak,
        memory_peak_bytes_after=harness.memory_peak(devices))
    check = harness.Check()
    compare(check, got, want, config["check"]["limits"])

    tokens_per_step = int(mix["batch"]) * int(mix["seq_len"])
    tokens_per_s = len(step_ms) * tokens_per_step / window_s
    check.at_most("compiles_in_window", compiles, 0)
    check.at_most("traces_in_window", traces, 0)
    check.at_least("losses_finite", float(np.isfinite(losses).all()), 1.0)
    check.at_most("last_loss_over_first_step_loss",
                  losses[-1] / got["losses"][0], 1.0)
    say("window", steps=len(step_ms), window_s=window_s,
        tokens_per_step=tokens_per_step, first_loss=got["losses"][0],
        step_ms={"p50": harness.percentile(step_ms, 50),
                 "p90": harness.percentile(step_ms, 90),
                 "max": max(step_ms)},
        last_loss=losses[-1], trainer_stats=stats)
    return {
        "check": check, "attempted": len(step_ms), "failed": 0,
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "memory_peak_bytes": peak, "trace": trace,
        "obs": {"kind": "train", "step_ms": step_ms,
                "tokens_per_s": tokens_per_s, "chips": len(devices),
                "flops_per_token": ref_mod.train_flops_per_token(
                    model_kw, int(mix["seq_len"])),
                "kernel_paths": paths,
                "memory_peak_bytes": peak, "trace": trace,
                "trace_steps": int(mix.get("trace_steps", 3)),
                "peaks": ctx["peaks"]},
    }
