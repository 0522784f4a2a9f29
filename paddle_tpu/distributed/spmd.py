"""Compiled SPMD trainer — the ParallelExecutor replacement.

Reference mapping:
- ParallelExecutor (/root/reference/paddle/fluid/framework/
  parallel_executor.cc:609) built an SSA graph per device, inserted
  AllReduceOpHandles (ir/multi_devices_graph_pass/
  multi_devices_graph_pass.cc:484,1200) and drained it with a threaded
  scheduler. Here ONE jit'd function (forward + backward + optimizer
  update) is compiled by XLA under a `jax.sharding.Mesh`; GSPMD inserts
  and fuses the collectives (grad all-reduce over 'dp', tensor-parallel
  all-gather/reduce-scatter over 'tp') that the reference hand-scheduled.
- Fleet meta-optimizer program rewrites (sharding_optimizer.py:69-120,
  amp_optimizer.py, gradient_merge_optimizer.py, recompute_optimizer.py)
  become constructor-time choices of sharding specs / dtypes / extra
  buffers on the SAME compiled step — no program surgery.

ZeRO (strategy.sharding, reference sharding_optimizer.py):
  stage 1: optimizer state sharded over 'dp'
  stage 2: + the gradient-merge accumulation buffer sharded over 'dp'
  stage 3: + parameters sharded over 'dp' (XLA all-gathers per-layer at
           use, the GSPMD analogue of the reference's broadcast-on-demand
           program segments)

Every enabled-but-unimplemented strategy flag raises — flags either work
or fail loudly (round-1 verdict: silent flags are worse than errors).
"""
from __future__ import annotations

import itertools
import os
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..func import functional_call
from ..nn.layer_base import Layer
from ..observability import capture as _capture
from ..observability import doctor as _doctor
from ..observability import exec_registry as _exec_registry
from ..observability import flightrec as _flightrec
from ..observability import metrics as _metrics
from ..observability import spans as _spans
from ..observability import watchdog as _watchdog

# telemetry/observatory component ids: one per trainer instance
_TRAINER_IDS = itertools.count()

# executable-observatory kinds per compiled-key family (ISSUE 15)
_EXEC_KINDS = {"fused": "train_step", "fused_out": "train_step",
               "accum": "train_step", "update": "grad_update",
               "eval": "eval"}
from . import async_dispatch
from .async_dispatch import StepResult
from .fleet.strategy import DistributedStrategy
from .mesh import (Mesh, NamedSharding, PartitionSpec, default_mesh,
                   compile_mesh_guard)

__all__ = ["SpmdTrainer", "dp_train_step", "zero_sharding_spec",
           "build_param_specs", "StepResult"]


def _is_floating(a) -> bool:
    return jnp.issubdtype(a.dtype, jnp.floating)


def zero_sharding_spec(shape, base_spec: PartitionSpec, dp_axis: str,
                       dp_size: int) -> PartitionSpec:
    """Extend `base_spec` (tensor-parallel placement, maybe empty) with a
    'dp' sharding on the largest free dim divisible by dp_size — the GSPMD
    expression of the reference's param->rank assignment
    (sharding_optimizer.py `shard` / `_split_program`). Small params
    (biases, norms) that don't divide stay replicated, like the
    reference's below-threshold segments."""
    if dp_size <= 1 or not shape or dp_axis in tuple(base_spec):
        return base_spec
    spec = list(base_spec) + [None] * (len(shape) - len(base_spec))
    # pick the largest unsharded dim divisible by dp_size
    best, best_dim = -1, None
    for i, (s, d) in enumerate(zip(spec, shape)):
        if s is None and d % dp_size == 0 and d > best:
            best, best_dim = d, i
    if best_dim is None or best < dp_size:
        return base_spec
    spec[best_dim] = dp_axis
    return PartitionSpec(*spec)


def build_param_specs(model: Layer, mesh: Mesh, dp_axis: str = "dp",
                      zero_stage: int = 0) -> Dict[str, PartitionSpec]:
    """name -> PartitionSpec for every parameter: tensor-parallel specs
    marked by parallel layers (param.pspec), plus ZeRO-3 dp sharding."""
    dp_size = mesh.shape.get(dp_axis, 1) if dp_axis in mesh.axis_names else 1
    specs = {}
    for name, p in model.named_parameters():
        base = getattr(p, "pspec", None) or PartitionSpec()
        # drop axes the mesh doesn't have (e.g. 'tp' specs on a dp-only
        # mesh fall back to replicated, matching nranks==1 fast paths)
        base = PartitionSpec(*[
            a if (a is not None and a in mesh.axis_names and
                  mesh.shape[a] > 1) else None
            for a in base])
        if zero_stage >= 3:
            base = zero_sharding_spec(tuple(p.data.shape), base, dp_axis,
                                      dp_size)
        specs[name] = base
    return specs


class SpmdTrainer:
    """One XLA executable per (train/eval) step over a device mesh.

    Parameters
    ----------
    model : Layer — the network; tensor-parallel layers may carry
        param.pspec annotations which are honored on the mesh.
    optimizer : paddle_tpu.optimizer.Optimizer — its functional form
        (init_state/apply_gradients) runs inside the compiled step.
    loss_fn : callable(outputs, labels) -> scalar Tensor/array.
    mesh : jax.sharding.Mesh with a 'dp' (and optionally 'tp', ...) axis.
    strategy : DistributedStrategy — amp / sharding / gradient_merge /
        recompute knobs are honored; enabled-but-unsupported knobs raise.
    """

    def __init__(self, model: Layer, optimizer, loss_fn: Callable,
                 mesh: Optional[Mesh] = None,
                 strategy: Optional[DistributedStrategy] = None,
                 dp_axis: str = "dp", sp_axis: Optional[str] = None,
                 donate: bool = True,
                 anomaly_policy: Optional[str] = None,
                 comm_stats: Optional[bool] = None,
                 resume_elastic: Optional[bool] = None):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh or default_mesh()
        self.strategy = strategy or DistributedStrategy()
        self.dp_axis = dp_axis
        # elastic resume (ISSUE 10): checkpoints record their logical
        # mesh; loading one written on a DIFFERENT topology reshards
        # every leaf onto this trainer's mesh.  True/None allow it
        # (None = env default), False makes a cross-topology restore an
        # error — for jobs whose numerics must be bitwise-stable.
        if resume_elastic is None:
            resume_elastic = os.environ.get(
                "PADDLE_TPU_RESUME_ELASTIC", "1") != "0"
        self.resume_elastic = bool(resume_elastic)
        self._reshard_restores = 0
        self._last_restore_info: Optional[dict] = None
        # sequence-parallel axis: explicit arg > model config > "sp"
        self.sp_axis = sp_axis or getattr(
            getattr(model, "config", None), "sp_axis", None) or "sp"
        self._donate = donate
        self._step_count = 0

        # persistent XLA compile cache (utils.compile_cache): warm
        # restarts skip the multi-minute recompile of identical steps
        from ..utils.compile_cache import ensure_compile_cache
        ensure_compile_cache()

        # step-time breakdown (trainer.stats): where did the
        # wall clock go — waiting for data, placing it, dispatching the
        # compiled step, or blocked on a host sync.  compile_ms_cold is
        # the first-call cost per executable in THIS process (trace +
        # compile or persistent-cache deserialize + first run).
        self._timings = {
            "data_wait_ms": 0.0, "h2d_ms": 0.0, "dispatch_ms": 0.0,
            "sync_ms": 0.0, "compile_ms_cold": 0.0, "steps_timed": 0,
        }
        # h2d_ms is written by BOTH the train thread and a
        # DevicePrefetcher thread (shard_batch runs on each); the
        # read-modify-write needs a lock or increments get lost
        import threading
        self._timings_lock = threading.Lock()
        self._first_call_keys: set = set()

        # unified telemetry (observability/): per-step wall timer (the
        # once-orphaned profiler.StepTimer), registry metrics, and the
        # PADDLE_TPU_PROFILE capture window.  Children are bound ONCE
        # here so the per-step cost is attribute arithmetic; when the
        # env is unset the window is a literal None (one check/step).
        from ..profiler import StepTimer
        self.step_timer = StepTimer(warmup=1)
        self.step_timer.start()
        self._profile = _capture.ProfileWindow.from_env(kind="train")
        self._m_steps = _metrics.counter(
            "train_steps_total", "completed train steps",
            labels=("trainer",)).labels(trainer="spmd")
        self._m_step_ms = _metrics.gauge(
            "train_step_time_ms", "last per-step wall time (host)",
            labels=("trainer",)).labels(trainer="spmd")
        self._m_step_hist = _metrics.histogram(
            "train_step_ms", "per-step wall time",
            labels=("trainer",)).labels(trainer="spmd")
        # flight recorder + stall watchdog (observability): crash hooks
        # installed once per process; the watchdog thread is created on
        # the first step only when PADDLE_TPU_WATCHDOG_S arms it
        _flightrec.install()
        self.watchdog: Optional[_watchdog.Watchdog] = None
        self._wd_checked = False

        # collective breakdown (comm_ms/comm_fraction in trainer.stats):
        # opt-in — measuring it AOT-compiles each step executable a
        # second time, which the tight test/CI budgets cannot afford by
        # default
        self._comm_enabled = bool(
            comm_stats if comm_stats is not None
            else os.environ.get("PADDLE_TPU_COMM_STATS") == "1")
        self._comm: Dict[Any, dict] = {}

        st = self.strategy
        if st.pipeline:
            raise NotImplementedError(
                "strategy.pipeline: use paddle_tpu.distributed.pipeline."
                "GPipeTrainer for pipeline parallelism")
        # flags either work here or raise — audit EVERY enabled boolean,
        # not a hand-picked subset (silent flags are worse than errors)
        supported = {
            "amp", "recompute", "sharding", "gradient_merge",
            "qat",                      # fake-quant matmuls (see below)
            "tensor_parallel",          # honored via param.pspec + mesh
            "find_unused_parameters",   # moot: XLA zero-grads unused params
            "fuse_all_reduce_ops",      # moot: XLA fuses collectives
            "use_hierarchical_allreduce",  # moot: XLA picks the algorithm
        }
        for key, val in st.to_dict().items():
            if val is True and key not in supported:
                raise NotImplementedError(
                    f"DistributedStrategy.{key} is not implemented in the "
                    f"compiled trainer; supported flags: {sorted(supported)}")

        self.zero_stage = int(st.sharding_configs.get("stage", 2)) \
            if st.sharding else 0
        self.k_steps = int(st.gradient_merge_configs.get("k_steps", 1)) \
            if st.gradient_merge else 1
        self.gm_avg = bool(st.gradient_merge_configs.get("avg", True))
        self.amp_enabled = bool(st.amp)
        # fp16 parity path (reference update_loss_scaling_op.cc +
        # fluid/dygraph/amp/loss_scaler.py): dynamic loss scaling runs
        # INSIDE the compiled step as (scale, good, bad) state.  bf16 is
        # the TPU-native default and needs no scaling.
        self.fp16_scaling = self.amp_enabled and \
            not st.amp_configs.get("use_bf16", True)
        self.amp_dtype = jnp.float16 if self.fp16_scaling else jnp.bfloat16
        ac = st.amp_configs
        self._scaler_cfg = {
            "init_loss_scaling": float(ac.get("init_loss_scaling", 2.**15)),
            "incr_ratio": float(ac.get("incr_ratio", 2.0)),
            "decr_ratio": float(ac.get("decr_ratio", 0.5)),
            "incr_every_n_steps": int(ac.get("incr_every_n_steps", 1000)),
            "decr_every_n_nan_or_inf": int(
                ac.get("decr_every_n_nan_or_inf", 2)),
            # floor for repeated non-finite streaks: dynamic scaling can
            # halve only down to this, never to a denormal/zero scale
            "min_loss_scaling": float(ac.get("min_loss_scaling", 1.0)),
        }
        if self.fp16_scaling and self.k_steps > 1:
            raise NotImplementedError(
                "fp16 loss scaling with gradient_merge (k_steps > 1) is "
                "not supported; use bf16 AMP or k_steps == 1")

        # FLAGS_check_nan_inf coverage for the COMPILED path (reference
        # scans every kernel output, nan_inf_utils_detail.cc:293; here
        # the jitted step returns one bool per checked tensor and the
        # host raises with the offending names).  Read at build time:
        # the flag changes the compiled program.
        from ..core.flags import GLOBAL_FLAGS
        self._check_nan_inf = bool(GLOBAL_FLAGS.get("check_nan_inf"))

        # ---- anomaly policy (resilience): what a non-finite loss/grad
        # does to the step.  "raise" keeps the historical behavior (the
        # nan guard above, only when FLAGS_check_nan_inf is on);
        # "skip" compiles the fp16 scaler's sel(new, old) machinery into
        # the fp32/bf16 step — the bad batch's update is discarded and an
        # on-device counter records it; "rollback" restores the last-good
        # host snapshot and skips the offending batch (host-side, costs
        # one sync per step + a snapshot every rollback_every good steps).
        self.anomaly_policy = (anomaly_policy or
                               os.environ.get("PADDLE_TPU_ANOMALY_POLICY")
                               or "raise")
        if self.anomaly_policy not in ("raise", "skip", "rollback"):
            raise ValueError(
                f"anomaly_policy must be raise|skip|rollback, got "
                f"{self.anomaly_policy!r}")
        if self.anomaly_policy == "rollback" and (
                self.fp16_scaling or self.k_steps > 1):
            raise NotImplementedError(
                "anomaly_policy='rollback' is not supported with fp16 "
                "loss scaling or gradient_merge; use 'skip' (fp16 "
                "already skips overflowed steps)")
        if self.anomaly_policy != "raise":
            # the policy owns non-finite handling; the raise-only guard
            # would defeat it
            self._check_nan_inf = False
        # fp16's scaler already implements skip; the explicit anomaly
        # state drives the fp32/bf16 paths
        self._anom_skip = (self.anomaly_policy == "skip" and
                           not self.fp16_scaling)
        self._anom_rollback = self.anomaly_policy == "rollback"
        if self._anom_rollback:
            # rollback must be able to re-materialize state from its
            # host snapshot at any step; donated buffers + the extra
            # anomaly-vec output mis-alias on cache-deserialized CPU
            # executables (observed: NaN leaking into params two steps
            # after a rollback). The policy already pays a host sync per
            # step — keeping inputs un-donated is the cheap, safe choice.
            self._donate = False
        self._rollback_count = 0
        self._rollback_every = int(os.environ.get(
            "PADDLE_TPU_ROLLBACK_EVERY", "1"))
        self._last_good = None
        # deterministic chaos: poison grads with NaN at step k (compiled
        # into the step; see testing/faults.py)
        from ..testing import faults as _faults
        self._fault_nan_step = _faults.nan_poison_step()

        if st.recompute:
            # model must cooperate (wrap blocks in distributed.recompute);
            # raising here beats silently training without remat
            if not hasattr(model, "enable_recompute"):
                raise NotImplementedError(
                    "strategy.recompute=True but the model has no "
                    "enable_recompute(); wrap blocks with "
                    "paddle_tpu.distributed.recompute(...) instead")
            # honor recompute_configs['policy'] (selective save-dots etc.)
            # defaulting to 'full' — full-segment remat, matching the
            # reference's recompute_optimizer; models that predate the policy kwarg
            # keep working (signature-checked, so a TypeError raised
            # INSIDE enable_recompute still propagates)
            import inspect
            pol = st.recompute_configs.get("policy")
            if pol is None:
                pol = "full"
            sig = inspect.signature(model.enable_recompute)
            if "policy" in sig.parameters:
                model.enable_recompute(policy=pol)
            else:
                model.enable_recompute()

        # quantization-aware training (strategy.qat): every block linear
        # runs the int8/fp8 fake-quant matmul (quantized forward,
        # straight-through backward — ops.quantized_matmul).  One knob:
        # qat_configs={'quantize': 'int8'|'fp8'}.  Params/optimizer are
        # untouched, so every other strategy flag composes.
        if st.qat:
            if not hasattr(model, "enable_quantize"):
                raise NotImplementedError(
                    "strategy.qat=True but the model has no "
                    "enable_quantize(); route its matmuls through "
                    "paddle_tpu.ops.fake_quant_matmul instead")
            model.enable_quantize(st.qat_configs.get("quantize", "int8"))

        # scan-over-layers (recompute_configs={'scan_layers': True}):
        # the model runs its homogeneous block stack as one lax.scan so
        # XLA traces/compiles the body once instead of once per layer;
        # combined with recompute, jax.checkpoint applies per scan
        # iteration (= per block). Independent of strategy.recompute —
        # the compile-time win stands on its own.
        if st.recompute_configs.get("scan_layers"):
            if not hasattr(model, "enable_scan_layers"):
                raise NotImplementedError(
                    "recompute_configs['scan_layers']=True but the model "
                    "has no enable_scan_layers(); only models with a "
                    "homogeneous block stack (GPT) support scanning")
            model.enable_scan_layers(True)

        # ZeRO-3 overlapped all-gather (distributed.zero3): with stage-3
        # sharded params AND a scanned layer stack, the scan prefetches
        # layer i+1's params (explicit all-gather under shard_map) while
        # layer i computes, and grads come back reduce-scattered over dp.
        # sharding_configs={'overlap': False} (or PADDLE_TPU_OVERLAP=0)
        # keeps the synchronous GSPMD stage-3 placement for A/B.
        from .overlap import overlap_enabled
        _ovl = st.sharding_configs.get("overlap") if st.sharding else None
        self.zero3_overlap = bool(
            self.zero_stage >= 3
            and (_ovl if _ovl is not None else overlap_enabled())
            and st.recompute_configs.get("scan_layers")
            and hasattr(model, "enable_zero3_overlap"))
        if self.zero3_overlap:
            model.enable_zero3_overlap(dp_axis)

        # ---- state pytrees (raw arrays keyed by structured name) --------
        self._param_objs = dict(model.named_parameters())
        # name-based decay hooks (AdamW apply_decay_param_fun, Lamb
        # exclude fn) must see Parameter.name in the compiled path too
        optimizer._param_name_map = {
            n: p.name for n, p in self._param_objs.items()}
        optimizer._param_obj_map = dict(self._param_objs)
        params = {n: p.data for n, p in self._param_objs.items()}
        buffers = {n: b.data for n, b in model.named_buffers()
                   if b is not None}
        self._trainable = {n: p.trainable for n, p in
                           self._param_objs.items()}

        # ---- shardings --------------------------------------------------
        dp_in_mesh = dp_axis in self.mesh.axis_names
        self.dp_size = self.mesh.shape[dp_axis] if dp_in_mesh else 1
        # multi-slice (DCN) tier: a "dcn" mesh axis makes the batch
        # shard over ("dcn", dp) — GSPMD then reduces grads ICI-within-
        # slice + DCN-across-slices while params/optimizer state stay
        # per-slice (ZeRO shards inside a slice, replicas across)
        self.dcn_axis = "dcn"
        self.dcn_size = self.mesh.shape[self.dcn_axis] \
            if self.dcn_axis in self.mesh.axis_names else 1
        # membership / in-memory elasticity (attach_membership arms it)
        self.membership = None
        self.dcn_guard = None
        self.reform_in_progress = False
        self._mesh_reforms = 0
        self._lost_slices: list = []
        self._last_reform_info: Optional[dict] = None
        # membership slice id -> current mesh slice row (reforms
        # renumber mesh rows; membership ids are stable)
        self._slice_ids = list(range(self.dcn_size))
        pspecs = build_param_specs(model, self.mesh, dp_axis,
                                   self.zero_stage)
        self._param_specs = pspecs
        self._param_shardings = {
            n: NamedSharding(self.mesh, s) for n, s in pspecs.items()}
        self._buffer_shardings = {
            n: NamedSharding(self.mesh, PartitionSpec()) for n in buffers}
        self._repl = NamedSharding(self.mesh, PartitionSpec())

        # optimizer state: sharded like the param when same-shaped, with
        # ZeRO stage>=1 adding a dp dimension (the reference's
        # sharding_optimizer assigns `param@accumulator` vars to ranks)
        opt_shapes = jax.eval_shape(self.optimizer.init_state, params)

        self._opt_shardings = {
            pname: jax.tree_util.tree_map(
                lambda leaf, pn=pname: self._zero_state_sharding(pn, leaf),
                tree)
            for pname, tree in opt_shapes.items()}

        # place state on the mesh
        self.params = {
            n: jax.device_put(a, self._param_shardings[n])
            for n, a in params.items()}
        self.buffers = {
            n: jax.device_put(a, self._buffer_shardings[n])
            for n, a in buffers.items()}
        with jax.transfer_guard("allow"):
            opt_state = self.optimizer.init_state(self.params)
        self.opt_state = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(a, s), opt_state,
            self._opt_shardings)

        # dynamic loss-scale state lives on-device so the whole
        # scale/unscale/check/update state machine compiles into the step
        self._scaler_state = None
        if self.fp16_scaling:
            self._scaler_state = {
                "scale": jax.device_put(jnp.asarray(
                    self._scaler_cfg["init_loss_scaling"], jnp.float32),
                    self._repl),
                "good": jax.device_put(jnp.asarray(0, jnp.int32),
                                       self._repl),
                "bad": jax.device_put(jnp.asarray(0, jnp.int32),
                                      self._repl),
                # optimizer-visible step count: does NOT advance on
                # overflow-skipped steps (the reference skips the whole
                # optimizer call)
                "t": jax.device_put(jnp.asarray(0, jnp.int32),
                                    self._repl),
                "found_inf": jax.device_put(
                    jnp.asarray(False, jnp.bool_), self._repl),
            }
            self._scaler_shardings = {k: self._repl
                                      for k in self._scaler_state}

        # anomaly-skip state lives on-device like the fp16 scaler state:
        # `t` is the optimizer-visible step count (does NOT advance on
        # skipped steps, so Adam bias correction matches a run that never
        # saw the bad batch), `skipped` counts discarded updates
        self._anomaly_state = None
        if self._anom_skip:
            self._anomaly_state = {
                "t": jax.device_put(jnp.asarray(self._step_count,
                                                jnp.int32), self._repl),
                "skipped": jax.device_put(jnp.asarray(0, jnp.int32),
                                          self._repl),
            }
            self._anomaly_shardings = {k: self._repl
                                       for k in self._anomaly_state}

        # gradient-merge buffer (reference GradMergeAllReduceOpHandle /
        # gradient_merge_optimizer.py): ZeRO stage>=2 shards it over dp
        self._grad_buf = None
        if self.k_steps > 1:
            self._grad_shardings = {
                n: self._grad_buf_sharding(n) for n in self.params}
            self._grad_buf = {
                n: jax.device_put(jnp.zeros_like(a),
                                  self._grad_shardings[n])
                for n, a in self.params.items()}

        self._compiled: Dict[str, Any] = {}

        # executable observatory + HBM ledger (ISSUE 15): the trainer's
        # compiled step(s) join the process exec registry under this
        # component label (see _timed_call), and the resident training
        # state — params, optimizer state, buffers, grad-merge buffer —
        # is tracked in the ledger (host-side shape math; weakref'd so
        # a torn-down trainer releases its accounting with its HBM)
        self.telemetry_label = f"s{next(_TRAINER_IDS)}"
        self._exec_component = f"trainer:{self.telemetry_label}"
        _exec_registry.track_bytes(
            self, "params", self.telemetry_label,
            _exec_registry.tree_bytes(self.params))
        _exec_registry.track_bytes(
            self, "opt_state", self.telemetry_label,
            _exec_registry.tree_bytes(self.opt_state))
        if self.buffers:
            _exec_registry.track_bytes(
                self, "buffers", self.telemetry_label,
                _exec_registry.tree_bytes(self.buffers))
        if self._grad_buf is not None:
            _exec_registry.track_bytes(
                self, "grad_buffer", self.telemetry_label,
                _exec_registry.tree_bytes(self._grad_buf))

    # ------------------------------------------------------------------
    def _zero_state_sharding(self, pname, leaf):
        """Sharding for one optimizer-state leaf: like the param when
        same-shaped (ZeRO stage>=1 adds a dp dimension), replicated
        otherwise.  Used at build time (on eval_shape structs) and by
        the mesh-reform rebind (on live arrays)."""
        pshape = tuple(self._param_objs[pname].data.shape)
        if tuple(leaf.shape) == pshape:
            base = self._param_specs[pname]
            if self.zero_stage >= 1:
                return NamedSharding(self.mesh, zero_sharding_spec(
                    pshape, base, self.dp_axis, self.dp_size))
            return NamedSharding(self.mesh, base)
        return self._repl

    def _grad_buf_sharding(self, n):
        """Sharding of the gradient-merge buffer for param `n` (ZeRO
        stage>=2 shards it over dp)."""
        if self.zero_stage >= 2:
            return NamedSharding(self.mesh, zero_sharding_spec(
                tuple(self._param_objs[n].data.shape),
                self._param_specs[n], self.dp_axis, self.dp_size))
        return self._param_shardings[n]

    def _batch_sharding(self, arr):
        # dim 0: hierarchical DP when a dcn axis is live — the batch
        # shards over ("dcn", dp), which is what makes GSPMD emit the
        # ICI-within-slice + DCN-across-slices gradient reduce; a batch
        # only divisible by dp falls back to per-slice DP (replicated
        # across slices: consistent, just not hierarchical)
        d0_total = self.dcn_size * self.dp_size
        if (self.dcn_size > 1 and self.dp_size > 1 and arr.ndim > 0
                and arr.shape[0] % d0_total == 0):
            d0 = (self.dcn_axis, self.dp_axis)
        elif (self.dcn_size > 1 and self.dp_size == 1 and arr.ndim > 0
                and arr.shape[0] % self.dcn_size == 0):
            d0 = self.dcn_axis
        elif (self.dp_size > 1 and arr.ndim > 0 and
                arr.shape[0] % self.dp_size == 0):
            d0 = self.dp_axis
        else:
            d0 = None
        dims = [d0]
        # sequence/context parallelism: dim 1 shards over the sp axis
        # (ring attention consumes the blocks; everything else is
        # GSPMD-local)
        sp = self.sp_axis
        sp_size = self.mesh.shape.get(sp, 1) \
            if sp in self.mesh.axis_names else 1
        if arr.ndim > 1:
            dims.append(sp if (sp_size > 1 and
                               arr.shape[1] % sp_size == 0) else None)
        dims += [None] * max(0, arr.ndim - len(dims))
        return NamedSharding(self.mesh, PartitionSpec(*dims))

    def shard_batch(self, batch):
        """Host batch -> device arrays sharded over 'dp' on dim 0 (the
        reference fed per-device scopes; one device_put here).

        Thread-safe and donation-safe: produces fresh committed arrays
        that never alias trainer state, so a DevicePrefetcher may call
        it from a background thread while the step runs.  Leaves that
        are ALREADY committed with the right sharding (a prefetched
        batch re-entering train_step) pass through untouched."""
        t0 = time.perf_counter()

        def put(x):
            arr = x.data if isinstance(x, Tensor) else x
            if isinstance(arr, jax.Array):
                sh = self._batch_sharding(arr)
                if getattr(arr, "sharding", None) == sh and \
                        getattr(arr, "committed", False):
                    return arr  # already placed (device prefetch path)
                return jax.device_put(arr, sh)
            arr = jnp.asarray(arr)
            return jax.device_put(arr, self._batch_sharding(arr))

        with _spans.span("train_step/h2d", "train",
                         step=self._step_count + 1):
            out = jax.tree_util.tree_map(
                put, batch, is_leaf=lambda x: isinstance(x, Tensor))
        dt = (time.perf_counter() - t0) * 1e3
        with self._timings_lock:
            self._timings["h2d_ms"] += dt
        return out

    def _analyze_comm(self, key, args):
        """Collective breakdown of this key's executable (opt-in; one
        AOT lower+compile per executable, done on the FIRST call while
        the args are still alive — the real call may donate them)."""
        from ..utils import comm_stats as _cs
        ss = self.mesh.devices.size // self.dcn_size \
            if self.dcn_size > 1 else None
        res = _cs.analyze_jit(self._compiled[key], *args,
                              device=self.mesh.devices.flat[0],
                              slice_size=ss)
        if res is not None:
            self._comm[key] = res

    def _timed_call(self, key, *args, count_step=True):
        """Invoke a compiled executable, splitting wall time into the
        first call (compile/deserialize) vs steady-state dispatch.
        count_step=False folds the call into dispatch_ms without
        advancing steps_timed (the gradient-merge 'update' executable:
        its cost amortizes over the window, so dispatch_ms/steps_timed
        stays a truthful per-train_step figure)."""
        if key not in self._first_call_keys:
            if self._comm_enabled:
                self._analyze_comm(key, args)
            if _exec_registry.enabled():
                # join the executable observatory at compile time: the
                # arg shape structs are captured pre-call (the step may
                # donate params/opt_state), the XLA cost/memory
                # analysis stays deferred to exec_registry.analyze
                fam = key[0] if isinstance(key, tuple) else str(key)
                _exec_registry.register(
                    self._exec_component, key,
                    _EXEC_KINDS.get(fam, str(fam)),
                    jitfn=self._compiled[key], args=args,
                    donate_argnums=(0, 1) if fam != "eval" else (),
                    meta={"mesh_axes": dict(self.mesh.shape),
                          "zero_stage": self.zero_stage,
                          "amp": self.amp_enabled,
                          # lets analyze() re-lower on THIS mesh: the
                          # uncommitted operands (lr, step number) are
                          # replicated there, as at runtime
                          "submesh": {
                              "shape": {ax: int(n) for ax, n in
                                        self.mesh.shape.items()},
                              "devices": [
                                  int(d.id) for d in
                                  np.asarray(self.mesh.devices).flat]}})
        t0 = time.perf_counter()
        with _spans.span("train_step/launch", "train",
                         step=self._step_count + 1):
            res = self._compiled[key](*args)
        dt = (time.perf_counter() - t0) * 1e3
        if key in self._first_call_keys:
            self._timings["dispatch_ms"] += dt
            if count_step:
                self._timings["steps_timed"] += 1
            _exec_registry.note_runtime(self._exec_component, key, dt)
        else:
            self._first_call_keys.add(key)
            self._timings["compile_ms_cold"] += dt
            _exec_registry.registry().note_compile(
                self._exec_component, key, dt)
        return res

    # ------------------------------------------------------------------
    def _loss_and_buffers(self, params, buffers, inputs, labels,
                          scale=None):
        from ..core.autograd import no_grad
        if self.amp_enabled:
            # cast params AND floating inputs: with fp32 activations JAX
            # type promotion would silently run every matmul in fp32 and
            # AMP would buy nothing (labels/int inputs stay untouched)
            cast = self.amp_dtype
            params = jax.tree_util.tree_map(
                lambda a: a.astype(cast) if _is_floating(a) else a, params)
            inputs = tuple(
                a.astype(cast) if hasattr(a, "dtype") and _is_floating(a)
                else a for a in inputs)
        # the eager tape is bypassed during tracing (jax.grad differentiates
        # the traced ops; recording GradNodes here would only slow compiles)
        from .moe import collect_aux_losses
        with no_grad(), collect_aux_losses() as aux:
            out, new_buffers = functional_call(
                self.model, params, buffers, *inputs, training=True)
        out_t = jax.tree_util.tree_map(
            lambda a: Tensor(a, stop_gradient=True), out)
        label_t = [Tensor(l) if not isinstance(l, Tensor) else l
                   for l in labels]
        loss = self.loss_fn(out_t, *label_t)
        loss_arr = loss.data if isinstance(loss, Tensor) else loss
        # router load-balance losses (MoE) ride on top of the task loss
        for a in aux:
            loss_arr = loss_arr + (a.data if isinstance(a, Tensor) else a)
        loss32 = loss_arr.astype(jnp.float32)
        # loss scaling: differentiate the SCALED loss but report the raw
        # one (reference scale->backward->unscale choreography)
        scaled = loss32 * scale if scale is not None else loss32
        return scaled, (new_buffers, out, loss32)

    def _grads_fn(self, params, buffers, inputs, labels,
                  want_outputs=False, scale=None):
        """value_and_grad over trainable params only; frozen params flow
        as constants.  With `scale`, grads come back SCALED (caller
        unscales after the finite check, like check_finite_and_unscale)."""
        train_p = {n: a for n, a in params.items() if self._trainable[n]}
        frozen_p = {n: a for n, a in params.items()
                    if not self._trainable[n]}

        def lfn(tp):
            return self._loss_and_buffers({**tp, **frozen_p}, buffers,
                                          inputs, labels, scale=scale)

        # what a trace shows under fwd_bwd and under no scope of the
        # model is the trainer's glue: AMP casts, the layer scan's
        # stacking, gradient casts
        with jax.named_scope("fwd_bwd"):
            (_, (new_buffers, outs, loss)), grads = jax.value_and_grad(
                lfn, has_aux=True)(train_p)
        grads = {n: grads.get(n, jnp.zeros_like(a))
                 for n, a in params.items()}
        return loss, new_buffers, grads, (outs if want_outputs else None)

    def _apply(self, params, opt_state, grads, lr, step_no):
        with jax.named_scope("optimizer"):
            new_train, new_state = self.optimizer.apply_gradients(
                {n: a for n, a in params.items() if self._trainable[n]},
                {n: g for n, g in grads.items() if self._trainable[n]},
                {n: s for n, s in opt_state.items() if self._trainable[n]},
                lr=lr, step=step_no)
        new_params = {n: new_train.get(n, a) for n, a in params.items()}
        new_opt = {n: new_state.get(n, s) for n, s in opt_state.items()}
        return new_params, new_opt

    # ------------------------------------------------------------------
    def _nanguard_names(self):
        """Static name list the in-step finite check reports against."""
        return ["loss"] + [f"{n}@GRAD" for n in sorted(self._trainable)
                           if self._trainable[n]]

    def _nanguard_vec(self, loss, grads):
        """One bool per checked tensor: True = contains nan/inf."""
        flags = [~jnp.isfinite(loss)]
        for n in sorted(self._trainable):
            if not self._trainable[n]:
                continue
            g = grads[n]
            if _is_floating(g):
                flags.append(~jnp.all(jnp.isfinite(
                    g.astype(jnp.float32))))
            else:
                flags.append(jnp.asarray(False))
        return jnp.stack(flags)

    def _raise_nonfinite(self, vec, names=None):
        import numpy as _np
        bad = _np.asarray(vec)
        if bad.any():
            names = names or self._nanguard_names()
            names = [n for n, b in zip(names, bad) if b]
            from ..core.errors import PreconditionNotMetError
            raise PreconditionNotMetError(
                f"FLAGS_check_nan_inf: nan/inf detected in compiled "
                f"train step: {names}")

    def _poison_grads(self, grads, step_no):
        """Fault injection (PADDLE_FAULT_NAN_STEP): NaN every floating
        gradient on the armed step. No-op (and nothing compiled in)
        unless armed at trainer build time."""
        k = self._fault_nan_step
        if k is None:
            return grads
        return {n: jnp.where(jnp.asarray(step_no) == k,
                             jnp.full_like(g, jnp.nan), g)
                if _is_floating(g) else g for n, g in grads.items()}

    def _nonfinite_any(self, loss, grads):
        """Scalar bool: loss or any trainable floating grad is nan/inf
        (the skip/rollback policies' trigger)."""
        checks = [jnp.all(jnp.isfinite(g.astype(jnp.float32)))
                  for n, g in grads.items()
                  if self._trainable[n] and _is_floating(g)]
        ok = jnp.stack(checks).all() if checks else jnp.asarray(True)
        return (~jnp.isfinite(loss)) | (~ok)

    # ---- anomaly_policy='rollback' host machinery --------------------
    def _capture_last_good(self):
        """Host-RAM snapshot of the full in-memory training state (the
        rollback target). Must OWN its memory (checkpoint._to_host):
        a zero-copy view would be overwritten by the next donated step
        and the 'last good' snapshot would track the live NaN state."""
        from .checkpoint import _to_host
        self._last_good = {
            "params": _to_host(self.params),
            "opt": _to_host(self.opt_state),
            "buffers": _to_host(self.buffers),
            "step": self._step_count,
        }

    def _restore_last_good(self):
        # device_put of a host array can be ZERO-COPY on the CPU backend;
        # hand it a private copy so the snapshot (which we must be able
        # to restore again) never shares memory with donated live state
        s = self._last_good
        self.params = {
            n: jax.device_put(a.copy(), self._param_shardings[n])
            for n, a in s["params"].items()}
        self.opt_state = jax.tree_util.tree_map(
            lambda a, sh: jax.device_put(a.copy(), sh), s["opt"],
            self._opt_shardings)
        self.buffers = {
            n: jax.device_put(a.copy(), self._buffer_shardings[n])
            for n, a in s["buffers"].items()}
        self._step_count = s["step"]
        self.optimizer._step_count = s["step"]

    def _handle_rollback(self, vec):
        """Host side of anomaly_policy='rollback': on a non-finite step,
        rewind to the last-good snapshot and skip the batch; on a good
        step, refresh the snapshot every rollback_every steps."""
        bad = np.asarray(vec).any()
        if bad:
            self._rollback_count += 1
            # post-mortem FIRST: the bundle must show the state the
            # anomaly was detected in, not the rewound one
            _flightrec.note_event("anomaly_rollback",
                                  step=self._step_count,
                                  rollback_count=self._rollback_count)
            _flightrec.dump("rollback")
            self._restore_last_good()
        elif self._step_count % self._rollback_every == 0:
            self._capture_last_good()
        return bad

    def _build_fused(self, n_inputs, n_labels, with_outputs=False):
        """Single-executable step: fwd+bwd+update (k_steps == 1).
        with_outputs additionally returns the forward outputs (hapi needs
        them for metrics; XLA computes them anyway)."""
        if self.fp16_scaling:
            return self._build_fused_fp16(n_inputs, n_labels, with_outputs)
        anom_skip = self._anom_skip
        want_vec = self._check_nan_inf or self._anom_rollback

        def step(params, opt_state, buffers, *rest):
            if anom_skip:
                anom, lr, step_no = rest[0], rest[1], rest[2]
                batch = rest[3:]
            else:
                anom, (lr, step_no) = None, rest[:2]
                batch = rest[2:]
            inputs, labels = batch[:n_inputs], batch[n_inputs:]
            loss, new_buffers, grads, outs = self._grads_fn(
                params, buffers, inputs, labels, want_outputs=with_outputs)
            grads = self._poison_grads(grads, step_no)
            if anom_skip:
                # fp16-style skip for fp32/bf16: discard the bad batch's
                # update via a scalar select, advance the optimizer step
                # only on finite steps (Adam bias correction parity with
                # a run that never saw the batch)
                bad = self._nonfinite_any(loss, grads)
                t = jnp.where(bad, anom["t"], anom["t"] + 1)
                new_params_u, new_opt_u = self._apply(
                    params, opt_state, grads, lr, t)

                def sel(new, old):
                    return jax.tree_util.tree_map(
                        lambda a, b: jnp.where(bad, b, a), new, old)

                new_params = sel(new_params_u, params)
                new_opt = sel(new_opt_u, opt_state)
                new_anom = {"t": t.astype(jnp.int32),
                            "skipped": (anom["skipped"] +
                                        bad.astype(jnp.int32))}
            else:
                new_params, new_opt = self._apply(
                    params, opt_state, grads, lr, step_no)
                new_anom = None
            merged = dict(buffers)
            merged.update(new_buffers)
            out = (new_params, new_opt, merged, loss)
            if anom_skip:
                out = out + (new_anom,)
            if with_outputs:
                out = out + (outs,)
            if want_vec:
                out = out + (self._nanguard_vec(loss, grads),)
            return out

        donate = ((0, 1, 2, 3) if anom_skip else (0, 1, 2)) \
            if self._donate else ()
        # input shardings come from the committed input arrays (device_put
        # in __init__/shard_batch); out_shardings pin the state placement
        shardings = (self._param_shardings, self._opt_shardings,
                     self._buffer_shardings, self._repl)
        if anom_skip:
            shardings = shardings + (dict(self._anomaly_shardings),)
        if with_outputs:
            shardings = shardings + (None,)  # outputs: let GSPMD place
        if want_vec:
            shardings = shardings + (self._repl,)
        return jax.jit(step, out_shardings=shardings,
                       donate_argnums=donate)

    def _build_fused_fp16(self, n_inputs, n_labels, with_outputs=False):
        """fp16 step with in-graph dynamic loss scaling.

        The whole reference choreography — scale the loss, backward,
        check_finite_and_unscale, conditional optimizer step, scale-state
        update (/root/reference/paddle/fluid/operators/amp/
        update_loss_scaling_op.cc, fluid/dygraph/amp/loss_scaler.py:27) —
        compiles into ONE executable.  Skipping a step is a scalar select
        (no data-dependent control flow; both branches are cheap since
        XLA shares the computed update).  The scaler carries its own step
        counter `t` so Adam bias correction does not advance on skipped
        steps, matching the reference's skipped optimizer call.
        """
        cfg = self._scaler_cfg

        def step(params, opt_state, buffers, scaler, lr, step_no,
                 *batch):
            inputs, labels = batch[:n_inputs], batch[n_inputs:]
            scale = scaler["scale"]
            loss, new_buffers, grads, outs = self._grads_fn(
                params, buffers, inputs, labels,
                want_outputs=with_outputs, scale=scale)
            grads = self._poison_grads(grads, step_no)
            inv = (jnp.asarray(1.0, jnp.float32) / scale)
            grads = {n: g * inv.astype(g.dtype) if _is_floating(g) else g
                     for n, g in grads.items()}
            checks = [jnp.all(jnp.isfinite(g.astype(jnp.float32)))
                      for n, g in grads.items()
                      if self._trainable[n] and _is_floating(g)]
            found_inf = ~jnp.stack(checks).all() if checks \
                else jnp.asarray(False)
            t = jnp.where(found_inf, scaler["t"], scaler["t"] + 1)
            new_params_u, new_opt_u = self._apply(
                params, opt_state, grads, lr, t)

            def sel(new, old):
                return jax.tree_util.tree_map(
                    lambda a, b: jnp.where(found_inf, b, a), new, old)

            new_params = sel(new_params_u, params)
            new_opt = sel(new_opt_u, opt_state)
            # dynamic scale state machine (update_loss_scaling_op.cc):
            # good-step streak doubles the scale every incr_every_n_steps;
            # decr_every_n_nan_or_inf consecutive overflows halve it
            good = jnp.where(found_inf, 0, scaler["good"] + 1)
            bad = jnp.where(found_inf, scaler["bad"] + 1, 0)
            incr = good >= cfg["incr_every_n_steps"]
            decr = bad >= cfg["decr_every_n_nan_or_inf"]
            # keep the old scale if doubling would overflow fp32 (the
            # reference op checks IsFinite(new_scale) the same way —
            # an inf scale would poison every later step)
            grown = scale * cfg["incr_ratio"]
            grown = jnp.where(jnp.isfinite(grown), grown, scale)
            new_scale = jnp.where(incr, grown, scale)
            new_scale = jnp.where(
                decr, jnp.maximum(scale * cfg["decr_ratio"],
                                  jnp.asarray(cfg["min_loss_scaling"],
                                              jnp.float32)),
                new_scale)
            good = jnp.where(incr, jnp.asarray(0, jnp.int32), good)
            bad = jnp.where(decr, jnp.asarray(0, jnp.int32), bad)
            new_scaler = {"scale": new_scale.astype(jnp.float32),
                          "good": good.astype(jnp.int32),
                          "bad": bad.astype(jnp.int32),
                          "t": t.astype(jnp.int32),
                          "found_inf": found_inf}
            merged = dict(buffers)
            merged.update(new_buffers)
            # FLAGS_check_nan_inf under fp16: grad infs are the scaler's
            # legitimate skip signal, but a non-finite UNSCALED loss is a
            # real divergence (log of a negative, etc.) the flag must
            # catch — the scaler would otherwise shrink the scale forever
            extra = ((~jnp.isfinite(loss))[None],) \
                if self._check_nan_inf else ()
            if with_outputs:
                return (new_params, new_opt, merged, loss, new_scaler,
                        outs) + extra
            return (new_params, new_opt, merged, loss,
                    new_scaler) + extra

        donate = (0, 1, 2, 3) if self._donate else ()
        scaler_sh = dict(self._scaler_shardings)
        shardings = (self._param_shardings, self._opt_shardings,
                     self._buffer_shardings, self._repl, scaler_sh)
        if with_outputs:
            shardings = shardings + (None,)
        if self._check_nan_inf:
            shardings = shardings + (self._repl,)
        return jax.jit(step, out_shardings=shardings,
                       donate_argnums=donate)

    def _build_accum(self, n_inputs, n_labels):
        anom_skip = self._anom_skip

        def accum(params, grad_buf, buffers, *rest):
            if anom_skip:
                anom, batch = rest[0], rest[1:]
            else:
                anom, batch = None, rest
            inputs, labels = batch[:n_inputs], batch[n_inputs:]
            loss, new_buffers, grads, _ = self._grads_fn(
                params, buffers, inputs, labels)
            if anom_skip:
                # a poisoned micro-batch is dropped from the window (its
                # grads never enter the merge buffer); the window-end
                # update still divides by k_steps — skip under gradient
                # merge trades a slightly small update for survival
                bad = self._nonfinite_any(loss, grads)
                new_buf = {n: jnp.where(bad, grad_buf[n],
                                        grad_buf[n] + grads[n])
                           for n in grad_buf}
                new_anom = {"t": anom["t"],
                            "skipped": (anom["skipped"] +
                                        bad.astype(jnp.int32))}
            else:
                new_buf = {n: grad_buf[n] + grads[n] for n in grad_buf}
                new_anom = None
            merged = dict(buffers)
            merged.update(new_buffers)
            out = (new_buf, merged, loss)
            if anom_skip:
                out = out + (new_anom,)
            if self._check_nan_inf:
                out = out + (self._nanguard_vec(loss, grads),)
            return out

        donate = ((1, 2, 3) if anom_skip else (1, 2)) \
            if self._donate else ()
        shardings = (self._grad_shardings, self._buffer_shardings,
                     self._repl)
        if anom_skip:
            shardings = shardings + (dict(self._anomaly_shardings),)
        if self._check_nan_inf:
            shardings = shardings + (self._repl,)
        return jax.jit(accum, out_shardings=shardings,
                       donate_argnums=donate)

    def _build_update(self):
        scale = (1.0 / self.k_steps) if self.gm_avg else 1.0

        def update(params, opt_state, grad_buf, lr, step_no):
            grads = {n: g * scale for n, g in grad_buf.items()}
            new_params, new_opt = self._apply(
                params, opt_state, grads, lr, step_no)
            zeroed = {n: jnp.zeros_like(g) for n, g in grad_buf.items()}
            return new_params, new_opt, zeroed

        donate = (0, 1, 2) if self._donate else ()
        return jax.jit(
            update,
            out_shardings=(self._param_shardings, self._opt_shardings,
                           self._grad_shardings),
            donate_argnums=donate)

    def _build_eval(self, n_inputs):
        def fwd(params, buffers, *inputs):
            if self.amp_enabled:
                # cast params AND floating inputs, like the train path —
                # mixed fp32 inputs fail dtype-strict ops (conv) outright
                cast = self.amp_dtype
                params = jax.tree_util.tree_map(
                    lambda a: a.astype(cast) if _is_floating(a) else a,
                    params)
                inputs = tuple(
                    a.astype(cast) if hasattr(a, "dtype") and
                    _is_floating(a) else a for a in inputs)
            out, _ = functional_call(self.model, params, buffers, *inputs,
                                     training=False)
            return out

        return jax.jit(fwd)

    def _watchdog_beat(self):
        """Arm the stall watchdog on the first step when
        PADDLE_TPU_WATCHDOG_S is set, then heartbeat it: one monotonic
        store per step while armed, one cached None check otherwise."""
        if not self._wd_checked:
            self._wd_checked = True
            t = _watchdog.watchdog_seconds()
            if t is not None:
                self.watchdog = _watchdog.Watchdog(
                    t, label="spmd_train").arm()
        if self.watchdog is not None:
            self.watchdog.beat()

    def _telemetry_step_end(self):
        """Per-step telemetry tail: tick the wall timer and mirror it
        into the metrics registry (and the flight-recorder ring).  Pure
        host arithmetic on pre-bound children — no sync, no allocation
        beyond the timer's float and one bounded ring entry."""
        self.step_timer.tick()
        self._m_steps.inc()
        last = self.step_timer.last_ms
        if last is not None:
            self._m_step_ms.set(last)
            self._m_step_hist.observe(last)
        _flightrec.record("train_step", dur_ms=last,
                          step=self._step_count)

    # ---- multi-slice membership / in-memory elasticity ---------------
    def attach_membership(self, membership, guard=None):
        """Arm slice-loss detection (distributed.membership): every
        train_step beats the surviving slices this process hosts (the
        single-process virtual-slice harness; a real multi-host
        deployment beats only its own slice through the file transport)
        and polls the failure detector — a membership change triggers
        the in-memory mesh reform.  `guard` (a DcnCollectiveGuard) is
        adopted for stats and wired into the same membership object,
        so a guard escalation reforms exactly like a heartbeat
        timeout; its backoff waits feed this trainer's stall watchdog.
        """
        self.membership = membership
        self.dcn_guard = guard
        if guard is not None:
            if guard.membership is None:
                guard.membership = membership
            if guard.on_beat is None:
                guard.on_beat = self._watchdog_beat
        return self

    def _membership_tick(self):
        """Step-boundary membership maintenance: beat, poll, and — on a
        membership change — re-form the mesh over the survivors before
        the next step runs."""
        m = self.membership
        if m is None:
            return
        m.beat_all(step=self._step_count)
        m.poll()
        # heartbeat timeouts AND guard escalations both land in
        # dead_slices(); translate stable membership ids to current
        # mesh slice rows (reforms renumber rows, ids persist)
        newly = [sid for sid in sorted(m.dead_slices())
                 if sid in self._slice_ids]
        if newly:
            rows = [self._slice_ids.index(sid) for sid in newly]
            self.reform_mesh(rows, member_ids=newly)

    def reform_mesh(self, lost_rows, member_ids=None):
        """In-memory mid-run elasticity: the current step has finished;
        snapshot the full training state to host (owned copies — the
        donation-safe checkpoint snapshot), re-form the mesh over the
        surviving slices, rebuild every sharding tree against it, and
        re-place the snapshot through the elastic-reshard restore path
        WITHOUT any checkpoint-dir round trip.  Executables re-register
        with the observatory on their first post-reform call; the step
        after that first call is recompile-free again (the
        zero-recompile contract on the new topology).

        lost_rows: indices into the CURRENT mesh's dcn axis.
        member_ids: the stable membership ids those rows carry (for
        stats; defaults to the rows themselves).
        """
        from .checkpoint import restore_trainer, snapshot_trainer
        lost = sorted({int(r) for r in lost_rows})
        if not lost:
            return self
        if self.dcn_size <= 1 or len(lost) >= self.dcn_size:
            raise RuntimeError(
                f"cannot re-form mesh: lost slices {lost} of "
                f"{self.dcn_size} — no survivors")
        ids = sorted(member_ids) if member_ids else lost
        t0 = time.perf_counter()
        self.reform_in_progress = True
        _flightrec.note_event("mesh_reform_begin", lost_slices=ids,
                              step=self._step_count,
                              dcn_from=self.dcn_size)
        try:
            state = snapshot_trainer(self)  # host snapshot, owned copies
            survivors = [r for r in range(self.dcn_size) if r not in lost]
            # the mesh is dcn-major (create_mesh): slice r owns row r of
            # the (dcn, -1) device view
            devs = self.mesh.devices.reshape(self.dcn_size, -1)[survivors]
            axes = {n: int(self.mesh.shape[n])
                    for n in self.mesh.axis_names}
            axes[self.dcn_axis] = len(survivors)
            new_mesh = Mesh(devs.reshape(list(axes.values())),
                            tuple(axes.keys()))
            self._rebind_mesh(new_mesh)
            # the elastic-reshard restore applied to the in-memory
            # snapshot: every leaf is re-placed under the NEW shardings
            # (make_array_from_callback), no disk involved.  elastic is
            # forced — attaching membership IS the opt-in to mid-run
            # topology change, regardless of resume_elastic strictness
            restore_trainer(self, state, elastic=True)
        finally:
            self.reform_in_progress = False
        dur_ms = (time.perf_counter() - t0) * 1e3
        self._mesh_reforms += 1
        self._lost_slices.extend(ids)
        self._slice_ids = [sid for i, sid in enumerate(self._slice_ids)
                           if i not in lost]
        self._last_reform_info = {
            "lost_slices": ids, "dcn_size": self.dcn_size,
            "step": self._step_count, "ms": round(dur_ms, 2)}
        _metrics.counter(
            "mesh_reforms_total",
            "in-memory mesh re-formations after slice loss").inc()
        _metrics.gauge(
            "mesh_reform_ms",
            "last in-memory mesh reform wall time").set(round(dur_ms, 3))
        _flightrec.note_event("mesh_reform", lost_slices=ids,
                              dcn_size=self.dcn_size,
                              step=self._step_count, ms=round(dur_ms, 2))
        return self

    def _rebind_mesh(self, mesh):
        """Rebuild every sharding tree and drop the compiled-executable
        cache for a NEW mesh (the reform path).  State arrays still
        live under the old placement afterwards — the caller re-places
        them (restore_trainer over the host snapshot)."""
        self.mesh = mesh
        self.dp_size = mesh.shape[self.dp_axis] \
            if self.dp_axis in mesh.axis_names else 1
        self.dcn_size = mesh.shape[self.dcn_axis] \
            if self.dcn_axis in mesh.axis_names else 1
        pspecs = build_param_specs(self.model, mesh, self.dp_axis,
                                   self.zero_stage)
        self._param_specs = pspecs
        self._param_shardings = {
            n: NamedSharding(mesh, s) for n, s in pspecs.items()}
        self._buffer_shardings = {
            n: NamedSharding(mesh, PartitionSpec())
            for n in self.buffers}
        self._repl = NamedSharding(mesh, PartitionSpec())
        self._opt_shardings = {
            pname: jax.tree_util.tree_map(
                lambda leaf, pn=pname: self._zero_state_sharding(pn, leaf),
                tree)
            for pname, tree in self.opt_state.items()}
        if self._scaler_state is not None:
            self._scaler_shardings = {k: self._repl
                                      for k in self._scaler_state}
        if self._anomaly_state is not None:
            self._anomaly_shardings = {k: self._repl
                                       for k in self._anomaly_state}
        if self._grad_buf is not None:
            self._grad_shardings = {
                n: self._grad_buf_sharding(n) for n in self.params}
        # new mesh => new executables: drop the compiled cache so the
        # first post-reform call compiles once, and clear the first-call
        # markers so compile-vs-dispatch attribution and exec-registry
        # re-registration behave like a fresh trainer
        self._compiled.clear()
        self._first_call_keys.clear()
        self._comm.clear()

    # ------------------------------------------------------------------
    def train_step(self, inputs, labels, return_outputs=False):
        """Run one compiled training step. inputs/labels: array, Tensor,
        or tuple thereof. Returns a lazy StepResult (no host sync — the
        device scalar is fetched, once, when you float()/read it; until
        then the host keeps dispatching ahead of the device); with
        return_outputs=True returns (StepResult, outputs) — the forward
        outputs ride along for metric computation (hapi)."""
        from . import env as _env
        _env.heartbeat()  # launcher watchdog liveness (no-op if unset)
        self._watchdog_beat()  # stall monitor (PADDLE_TPU_WATCHDOG_S)
        if self._profile is not None:
            # PADDLE_TPU_PROFILE=start:stop — device capture windowed on
            # the step counter (observability.capture)
            self._profile.on_step(self._step_count)
        n = self._step_count + 1
        with _spans.step_span("train_step", "train", step_num=n, step=n):
            return self._train_step(inputs, labels, return_outputs)

    def _train_step(self, inputs, labels, return_outputs):
        """train_step's body, inside its ``train_step`` span."""
        inputs = inputs if isinstance(inputs, (tuple, list)) else (inputs,)
        labels = labels if isinstance(labels, (tuple, list)) else (labels,)
        batch = self.shard_batch(tuple(inputs) + tuple(labels))
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)

        if self.k_steps == 1:
            key = ("fused_out" if return_outputs else "fused",
                   len(inputs), len(labels))
            if key not in self._compiled:
                self._compiled[key] = self._build_fused(
                    len(inputs), len(labels), with_outputs=return_outputs)
            step_no = jnp.asarray(self._step_count + 1, jnp.int32)
            if self._anom_rollback and self._last_good is None:
                self._capture_last_good()  # rollback target before step 1
            # the ambient mesh lets layers place sharding constraints on
            # intermediates (MoE dispatch buffers) while jit traces
            with compile_mesh_guard(self.mesh):
                if self.fp16_scaling:
                    res = self._timed_call(
                        key, self.params, self.opt_state, self.buffers,
                        self._scaler_state, lr, step_no, *batch)
                elif self._anom_skip:
                    res = self._timed_call(
                        key, self.params, self.opt_state, self.buffers,
                        self._anomaly_state, lr, step_no, *batch)
                else:
                    res = self._timed_call(
                        key, self.params, self.opt_state, self.buffers,
                        lr, step_no, *batch)
            res = list(res)
            guard = res.pop() \
                if (self._check_nan_inf or self._anom_rollback) else None
            outs = res.pop() if return_outputs else None
            if self.fp16_scaling:
                (self.params, self.opt_state, self.buffers, loss,
                 self._scaler_state) = res
            elif self._anom_skip:
                (self.params, self.opt_state, self.buffers, loss,
                 self._anomaly_state) = res
            else:
                self.params, self.opt_state, self.buffers, loss = res
            self._step_count += 1
            self.optimizer._step_count = self._step_count
            if self._anom_rollback:
                # one host sync per step — the policy's documented price
                t_sync = time.perf_counter()
                with _spans.span("train_step/read", "train",
                                 step=self._step_count):
                    self._handle_rollback(guard)
                async_dispatch.record_host_sync()
                dt_sync = (time.perf_counter() - t_sync) * 1e3
                self._timings["sync_ms"] += dt_sync
            elif guard is not None:
                t_sync = time.perf_counter()
                with _spans.span("train_step/read", "train",
                                 step=self._step_count):
                    self._raise_nonfinite(
                        guard,
                        names=["loss"] if self.fp16_scaling else None)
                async_dispatch.record_host_sync()
                dt_sync = (time.perf_counter() - t_sync) * 1e3
                self._timings["sync_ms"] += dt_sync
            from ..testing import faults as _faults
            _faults.maybe_sigterm(self._step_count)
            _faults.maybe_hang(self._step_count)
            self._telemetry_step_end()
            self._membership_tick()
            result = StepResult(loss, timings=self._timings, outputs=outs,
                                step=self._step_count)
            return (result, outs) if return_outputs else result
        if return_outputs:
            raise NotImplementedError(
                "return_outputs with gradient merge (k_steps > 1) is not "
                "supported; drop metrics or gradient_merge")

        akey = ("accum", len(inputs), len(labels))
        if akey not in self._compiled:
            self._compiled[akey] = self._build_accum(
                len(inputs), len(labels))
        if "update" not in self._compiled:
            self._compiled["update"] = self._build_update()
        with compile_mesh_guard(self.mesh):
            if self._anom_skip:
                res = self._timed_call(
                    akey, self.params, self._grad_buf, self.buffers,
                    self._anomaly_state, *batch)
            else:
                res = self._timed_call(
                    akey, self.params, self._grad_buf, self.buffers,
                    *batch)
        res = list(res)
        guard = res.pop() if self._check_nan_inf else None
        if self._anom_skip:
            self._grad_buf, self.buffers, loss, self._anomaly_state = res
        else:
            self._grad_buf, self.buffers, loss = res
        self._step_count += 1
        if guard is not None:
            t_sync = time.perf_counter()
            self._raise_nonfinite(guard)
            async_dispatch.record_host_sync()
            self._timings["sync_ms"] += (time.perf_counter() - t_sync) * 1e3
        if self._step_count % self.k_steps == 0:
            step_no = jnp.asarray(
                self._step_count // self.k_steps, jnp.int32)
            self.params, self.opt_state, self._grad_buf = \
                self._timed_call(
                    "update", self.params, self.opt_state, self._grad_buf,
                    lr, step_no, count_step=False)
            self.optimizer._step_count = self._step_count // self.k_steps
        from ..testing import faults as _faults
        _faults.maybe_sigterm(self._step_count)
        _faults.maybe_hang(self._step_count)
        self._telemetry_step_end()
        self._membership_tick()
        return StepResult(loss, timings=self._timings,
                          step=self._step_count)

    def eval_step(self, inputs):
        # an eval loop is progress too: heartbeat (never arm — an
        # eval-only user has no step loop to watch), so a post-training
        # evaluation phase neither false-fires nor goes unwatched
        if self.watchdog is not None:
            self.watchdog.beat()
        inputs = inputs if isinstance(inputs, (tuple, list)) else (inputs,)
        batch = self.shard_batch(tuple(inputs))
        key = ("eval", len(inputs))
        if key not in self._compiled:
            self._compiled[key] = self._build_eval(len(inputs))
        with compile_mesh_guard(self.mesh):
            return self._compiled[key](self.params, self.buffers, *batch)

    predict_step = eval_step

    # ------------------------------------------------------------------
    def sync_to_model(self):
        """Write trainer-owned arrays back into the model's Tensors (for
        checkpointing / eager inspection). Reference analogue: fetching
        persistables out of the ParallelExecutor's scopes."""
        for n, p in self._param_objs.items():
            p._data = self.params[n]
        buf_objs = dict(self.model.named_buffers())
        for n, a in self.buffers.items():
            if n in buf_objs and buf_objs[n] is not None:
                buf_objs[n]._data = a
        return self.model

    def sync_from_model(self):
        """Adopt the model's current Tensor values as the trainer state
        (after a checkpoint load into the model) — the reverse of
        sync_to_model; re-places every array with its mesh sharding."""
        self.params = {
            n: jax.device_put(jnp.asarray(p.data),
                              self._param_shardings[n])
            for n, p in self._param_objs.items()}
        buf_objs = dict(self.model.named_buffers())
        self.buffers = {
            n: jax.device_put(jnp.asarray(buf_objs[n].data),
                              self._buffer_shardings[n])
            if n in buf_objs and buf_objs[n] is not None else a
            for n, a in self.buffers.items()}
        return self

    def state_dict(self):
        sd = {n: Tensor(a) for n, a in self.params.items()}
        sd.update({n: Tensor(a) for n, a in self.buffers.items()})
        return sd

    def save(self, path: str, extra=None, manifest: bool = False) -> str:
        """Checkpoint the full training state (params + opt state + step
        + LR scheduler [+ grad-merge buffer, scaler, anomaly counters]) —
        reference auto_checkpoint.py:71 / fleet.save_persistables.
        manifest=True writes the integrity-checked directory format
        (sha256-verified on load; see distributed/resilience.py for the
        async keep-last-K manager built on it)."""
        from .checkpoint import save_trainer
        return save_trainer(self, path, extra=extra, manifest=manifest)

    def load(self, path: str) -> dict:
        """Restore a save() checkpoint (single-file or manifest dir);
        shardings are re-applied from THIS trainer, so the mesh layout
        may differ from the writer's."""
        from .checkpoint import load_trainer
        return load_trainer(self, path)

    def export_train_step(self, path: str, example_inputs,
                          example_labels) -> str:
        """Serialize the WHOLE fused train step (fwd+bwd+update) as
        StableHLO + initial state — the artifact a non-Python runtime
        (inference/capi trainer entry) drives for native training, the
        TPU-native answer to the reference's C++ train demo
        (fluid/train/demo: load a program with backward ops and run it).
        """
        import pickle
        from jax import export as jexport
        if self.fp16_scaling or self._check_nan_inf or \
                self.anomaly_policy != "raise":
            raise NotImplementedError(
                "export_train_step supports the standard bf16/fp32 step "
                "(no fp16 scaler state, no nan guard, no anomaly policy) "
                "for a stable serialized signature")
        inputs = example_inputs if isinstance(example_inputs,
                                              (tuple, list)) \
            else (example_inputs,)
        labels = example_labels if isinstance(example_labels,
                                              (tuple, list)) \
            else (example_labels,)
        batch = self.shard_batch(tuple(inputs) + tuple(labels))
        # a fresh non-donating jit: donation has no meaning across the
        # serialization boundary
        saved_donate, self._donate = self._donate, False
        try:
            step = self._build_fused(len(inputs), len(labels))
        finally:
            self._donate = saved_donate

        def aval(a):
            return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)

        with compile_mesh_guard(self.mesh):
            exported = jexport.export(step)(
                jax.tree_util.tree_map(aval, self.params),
                jax.tree_util.tree_map(aval, self.opt_state),
                jax.tree_util.tree_map(aval, self.buffers),
                jax.ShapeDtypeStruct((), jnp.float32),
                jax.ShapeDtypeStruct((), jnp.int32),
                *[aval(b) for b in batch])
        import os as _os
        _os.makedirs(_os.path.dirname(path) or ".", exist_ok=True)
        with open(path + ".pdtrain", "wb") as f:
            f.write(exported.serialize())
        state = {
            "params": jax.tree_util.tree_map(np.asarray, self.params),
            "opt_state": jax.tree_util.tree_map(np.asarray,
                                                self.opt_state),
            "buffers": jax.tree_util.tree_map(np.asarray, self.buffers),
            "lr": float(self.optimizer.get_lr()),
            "step_count": self._step_count,
        }
        with open(path + ".pdtrainstate", "wb") as f:
            pickle.dump(state, f, protocol=4)
        return path

    @property
    def stats(self) -> dict:
        """Resilience counters + step-time breakdown for logging.

        Anomaly half: the active policy plus how many updates it
        discarded (skip: on-device counter; fp16: steps whose
        optimizer-visible count did not advance; rollback: host rewinds).
        Reading the on-device counters is itself a host sync — call this
        at log boundaries, not per step.

        Timing half (milliseconds, cumulative since construction):
        ``data_wait_ms`` (consumer blocked on the prefetch queue),
        ``h2d_ms`` (host spent placing batches), ``dispatch_ms``
        (steady-state compiled-step calls), ``sync_ms`` (blocked host
        read-backs), ``compile_ms_cold`` (first-call compile/deserialize
        cost per executable), ``steps_timed``."""
        s = {"anomaly_policy": self.anomaly_policy,
             "rollback_steps": self._rollback_count,
             "resume_elastic": self.resume_elastic,
             "reshard_restores": self._reshard_restores,
             # multi-slice tier: how many in-memory reforms ran, which
             # membership slice ids were lost, and the live dcn extent
             "mesh_reforms": self._mesh_reforms,
             "lost_slices": list(self._lost_slices),
             "dcn_slices": self.dcn_size}
        if self._last_reform_info is not None:
            s["last_reform"] = dict(self._last_reform_info)
        if self.membership is not None:
            ms = self.membership.stats()
            s["slice_heartbeat_ages"] = ms["heartbeat_ages"]
            s["slice_timeout_s"] = ms["timeout_s"]
            s["slices_dead"] = ms["dead"]
        if self.dcn_guard is not None:
            s["dcn_guard"] = self.dcn_guard.stats()
        t_sync = time.perf_counter()
        if self._anomaly_state is not None:
            s["skipped_steps"] = int(self._anomaly_state["skipped"])
            async_dispatch.record_host_sync()
        elif self.fp16_scaling and self._scaler_state is not None:
            s["skipped_steps"] = int(
                self._step_count - int(self._scaler_state["t"]))
            async_dispatch.record_host_sync()
        else:
            s["skipped_steps"] = 0
        # expert-balance totals the dropless MoE layers keep in their
        # buffers (kept pairs by held expert, pairs assigned, tokens):
        # read here, once, and published process-wide (moe.expert_totals);
        # None, and nothing read, for a model without such a buffer
        from .moe import publish_expert_totals
        s["expert_stats"] = publish_expert_totals(self.buffers)
        if s["expert_stats"] is not None:
            async_dispatch.record_host_sync()
        self._timings["sync_ms"] += (time.perf_counter() - t_sync) * 1e3
        for k, v in self._timings.items():
            s[k] = round(v, 3) if isinstance(v, float) else v
        # per-step wall clock (profiler.StepTimer, warmup-excluded):
        # step_time_ms is the figure hapi logs; mean/p50 summarize
        s["step_time_ms"] = round(self.step_timer.last_ms, 3) \
            if self.step_timer.last_ms is not None else None
        s["step_time_mean_ms"] = round(self.step_timer.mean_ms, 3) \
            if self.step_timer.mean_ms is not None else None
        s["step_time_p50_ms"] = round(self.step_timer.p50_ms, 3) \
            if self.step_timer.p50_ms is not None else None

        # collective breakdown (PADDLE_TPU_COMM_STATS / comm_stats=True):
        # per-step bytes each compiled step moves over the interconnect
        # and the bandwidth-model transfer time; comm_fraction divides
        # that by the MEASURED mean step time, so an overlap schedule
        # that actually hides its collectives shows the fraction shrink
        # instead of the step time growing
        comm_ms = comm_bytes = comm_count = 0.0
        comm_ici = comm_dcn = 0.0
        comm_split = False
        by_op: Dict[str, dict] = {}
        # one per-step executable counts (the most recently analyzed
        # fused/accum variant — 'fused' and 'fused_out' are the SAME
        # step, summing both would double the figure); the gradient-
        # merge 'update' amortizes over its window
        step_keys = [k for k in self._comm
                     if k == "update" or k[0] in ("fused", "fused_out",
                                                  "accum")]
        per_step = [k for k in step_keys if k != "update"]
        chosen = ([per_step[-1]] if per_step else []) + \
            (["update"] if "update" in self._comm else [])
        for key in chosen:
            res = self._comm[key]
            scale = 1.0 / self.k_steps if key == "update" else 1.0
            comm_ms += res["comm_ms"] * scale
            comm_bytes += res["bytes"] * scale
            comm_count += res["count"] * scale
            if "dcn_bytes" in res:
                comm_split = True
                comm_ici += res["ici_bytes"] * scale
                comm_dcn += res["dcn_bytes"] * scale
            for op, v in res["by_op"].items():
                slot = by_op.setdefault(op, {"count": 0.0, "bytes": 0.0})
                slot["count"] += v["count"] * scale
                slot["bytes"] += v["bytes"] * scale
                if "dcn_bytes" in v:
                    slot["ici_bytes"] = slot.get("ici_bytes", 0.0) \
                        + v["ici_bytes"] * scale
                    slot["dcn_bytes"] = slot.get("dcn_bytes", 0.0) \
                        + v["dcn_bytes"] * scale
        s["comm_ms"] = round(comm_ms, 4) if self._comm else None
        s["comm_bytes"] = int(comm_bytes) if self._comm else None
        s["comm_collectives"] = int(comm_count) if self._comm else None
        s["comm_by_op"] = by_op if self._comm else None
        # ici/dcn byte split (multi-slice meshes with comm stats on):
        # the evidence for the dcn-bound doctor rule and the dcn phase
        s["comm_bytes_ici"] = int(comm_ici) if comm_split else None
        s["comm_bytes_dcn"] = int(comm_dcn) if comm_split else None
        steps = self._timings["steps_timed"]
        mean_step = (self._timings["dispatch_ms"] / steps) if steps else 0.0
        s["comm_fraction"] = round(comm_ms / mean_step, 4) \
            if (self._comm and mean_step > 0) else None
        # executable observatory (ISSUE 15): per-kind roofline digest
        # for this trainer's executables — populated once the deferred
        # analyses ran (report CLI, exec_registry.analyze_all).
        # Reading stats never compiles.
        s["exec_profile"] = _exec_registry.profile(self._exec_component)
        s["hbm"] = _exec_registry.ledger().snapshot()
        # perf-doctor verdict over everything above (observability.
        # doctor): ranked [{bottleneck, evidence, knob}] — host-side
        # dict math, the machine-readable half of the ROADMAP-1 triage
        s["doctor"] = _doctor.diagnose(s, kind="train")
        return s

    @property
    def loss_scale(self):
        """Current dynamic loss scale (None unless fp16 AMP)."""
        if self._scaler_state is None:
            return None
        return float(self._scaler_state["scale"])

    @property
    def last_step_skipped(self):
        """True when the previous fp16 step hit inf/nan and was skipped."""
        if self._scaler_state is None:
            return False
        return bool(self._scaler_state["found_inf"])

    @property
    def step_executable(self):
        """The underlying compiled step (for introspection/tests)."""
        for k in ("fused", "accum"):
            for key, v in self._compiled.items():
                if key[0] == k:
                    return v
        return None


def dp_train_step(model: Layer, optimizer, loss_fn,
                  mesh: Optional[Mesh] = None, **kwargs):
    """Convenience promised by distributed.parallel: build an SpmdTrainer
    on a dp mesh and return (trainer, trainer.train_step)."""
    trainer = SpmdTrainer(model, optimizer, loss_fn, mesh=mesh, **kwargs)
    return trainer, trainer.train_step
