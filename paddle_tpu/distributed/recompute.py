"""Activation recompute (checkpointing).

Reference: fluid/backward.py:725 `_append_backward_ops_with_checkpoints_`
(re-runs forward segments inside the backward program) and
fleet/meta_optimizers/recompute_optimizer.py. TPU-native: `jax.checkpoint`
(remat) — XLA drops the segment's activations and re-executes its forward
in the backward pass, trading FLOPs for HBM exactly like the reference's
program rewrite, but scheduled by the compiler.

Works in BOTH execution modes:
- eagerly, `recompute(block, x)` records ONE tape node whose vjp is the
  checkpointed function's vjp (recompute happens inside `backward()`);
- under a compiled trainer trace, the remat region is inlined into the
  jaxpr and honored by jax.grad.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax

from ..core.autograd import apply
from ..core.tensor import Tensor
from ..nn.layer_base import Layer

__all__ = ["recompute", "RecomputeWrapper", "checkpoint_policy"]

_POLICIES = {
    "full": None,  # save nothing, recompute everything
    "dots": "checkpoint_dots",
    "dots_no_batch": "checkpoint_dots_with_no_batch_dims",
    "nothing": "nothing_saveable",
    "everything": "everything_saveable",
}
# the policies that keep the products' outputs and recompute the cheap
# element-wise work between them
_KEEP_PRODUCTS = ("dots", "dots_no_batch")


def checkpoint_policy(name: Optional[str]):
    """Map strategy.recompute_configs['policy'] names onto
    jax.checkpoint_policies.

    `dots` and `dots_no_batch` keep the outputs of the products: XLA's
    `dot_general`s and, under the names its forward rule gives them
    (`ops.flash_attention.RESIDUAL_NAMES`), the flash attention
    kernel's output and log-sum-exp. The kernel IS two products a tile,
    and to a policy that looks for `dot_general` a Pallas custom call
    is not one: without the names the backward would run the kernel's
    forward a second time. `full` (None) recomputes everything,
    `nothing` and `everything` and a raw `jax.checkpoint_policies`
    attribute name mean what jax says."""
    if name is None or name == "full":
        return None
    attr = _POLICIES.get(name, name)
    pol = getattr(jax.checkpoint_policies, attr, None)
    if pol is None:
        raise ValueError(f"unknown recompute policy {name!r}")
    if name in _KEEP_PRODUCTS:
        from ..ops.flash_attention import RESIDUAL_NAMES
        pol = jax.checkpoint_policies.save_from_both_policies(
            pol, jax.checkpoint_policies.save_only_these_names(
                *RESIDUAL_NAMES))
    return pol


def recompute(function, *args, policy=None, **kwargs):
    """paddle.distributed.fleet.utils.recompute parity: run `function`
    (a Layer or a Tensor-level callable) without saving its internal
    activations; they are recomputed during backward.

    Buffers the block mutates in place (BatchNorm running stats) are
    threaded through the checkpointed region as explicit inputs/outputs —
    the block's buffer tensors are restored after tracing and re-assigned
    with the region's OUTPUT values, so no inner-trace tracer ever leaks
    into live module state.
    """
    from .moe import add_aux_loss, collect_aux_losses

    if isinstance(function, Layer):
        param_objs = [p for _, p in function.named_parameters()]
        buf_objs = [b for _, b in function.named_buffers()
                    if b is not None]
    else:
        param_objs, buf_objs = [], []
    n_params, n_bufs = len(param_objs), len(buf_objs)
    meta = {}

    def pure(*flat):
        p_arrs = flat[:n_params]
        b_arrs = flat[n_params:n_params + n_bufs]
        in_arrs = flat[n_params + n_bufs:]
        orig_p = [p._data for p in param_objs]
        orig_b = [b._data for b in buf_objs]
        for o, a in zip(param_objs, p_arrs):
            o._data = a
        for o, a in zip(buf_objs, b_arrs):
            o._data = a
        try:
            wrapped = [Tensor(a) if not isinstance(a, Tensor) else a
                       for a in in_arrs]
            # aux losses (MoE routers) produced inside the remat region
            # are tracers of the INNER checkpoint trace; they must leave
            # the region as explicit outputs, then be re-emitted outside
            # (otherwise adding them to the loss later leaks the tracer)
            with collect_aux_losses() as aux:
                out = function(*wrapped, **kwargs)
            aux_arrs = tuple(a.data if isinstance(a, Tensor) else a
                             for a in aux)
            new_bufs = tuple(b._data for b in buf_objs)
        finally:
            for o, a in zip(param_objs, orig_p):
                o._data = a
            for o, a in zip(buf_objs, orig_b):
                o._data = a
        out_arrs = jax.tree_util.tree_map(
            lambda x: x.data if isinstance(x, Tensor) else x, out,
            is_leaf=lambda x: isinstance(x, Tensor))
        leaves, treedef = jax.tree_util.tree_flatten(out_arrs)
        meta["treedef"] = treedef
        meta["n_out"] = len(leaves)
        return tuple(leaves) + new_bufs + aux_arrs

    ckpt = jax.checkpoint(pure, policy=checkpoint_policy(policy))
    res = apply(ckpt, *param_objs, *buf_objs, *args, name="recompute")
    res = res if isinstance(res, tuple) else (res,)
    out_leaves = list(res[:meta["n_out"]])
    for b, nv in zip(buf_objs, res[meta["n_out"]:meta["n_out"] + n_bufs]):
        b._data = nv.data
    for a in res[meta["n_out"] + n_bufs:]:
        add_aux_loss(a)
    out = jax.tree_util.tree_unflatten(meta["treedef"], out_leaves)
    return out


class RecomputeWrapper(Layer):
    """Wrap a block so every forward goes through `recompute` (the layer
    form of the reference's checkpoint list). `enable(False)` turns it
    into a transparent passthrough."""

    def __init__(self, layer: Layer, policy: Optional[str] = None):
        super().__init__()
        self._inner = layer
        self._policy = policy
        self._active = True

    def enable(self, active: bool = True):
        self._active = active
        return self

    def forward(self, *args, **kwargs):
        if not self._active:
            return self._inner(*args, **kwargs)
        return recompute(self._inner, *args, policy=self._policy, **kwargs)
