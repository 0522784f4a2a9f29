"""ZeRO-3 with overlapped parameter all-gather (Rajbhandari et al. 2020).

The GSPMD ZeRO-3 path (`spmd.zero_sharding_spec` with stage>=3) leaves
the gather placement to XLA: params live dp-sharded and the partitioner
inserts an all-gather at each use site.  That is correct but gives the
scheduler no structure to hide the gathers behind: the
partitioned module typically gathers a layer's weights right before its
matmuls need them, serializing ICI transfer and MXU work.

This module expresses the schedule explicitly, the way the scan-over-
layers stack makes possible: inside `shard_map` over the dp axis, the
layer scan's carry holds the CURRENT layer's already-gathered weights
while the body issues the all-gather for layer i+1 — two independent op
islands XLA's async collectives can overlap (the `PADDLE_TPU_OVERLAP`
flags in `distributed.overlap` turn the latency-hiding scheduler on for
real backends).  Because the gather is differentiated explicitly, its
transpose is `psum_scatter`: gradients leave the backward REDUCE-
SCATTERED over dp instead of all-reduced, which is the other half of
ZeRO-3 — per-device grad (and param) memory drops ~1/dp and the wire
moves 2x less gradient data.

Numerics are untouched: the gather reconstructs the exact replicated
weights, every per-token op inside the block is batch-local, and
reduce-scatter + sharded-Adam-update is elementwise-equal to
all-reduce + full-Adam-update on the same shard.  The parity tests
assert this against the synchronous stage-3 path.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from . import mesh as _mesh
from .mesh import Mesh, PartitionSpec, shard_map

__all__ = ["zero3_shard_dims", "zero3_scan_available",
           "scan_layers_zero3"]


def zero3_shard_dims(stacked: Dict[str, jax.Array], axis: str,
                     dp_size: int) -> Dict[str, Optional[int]]:
    """Per-param shard dim (on the UNSTACKED [per-layer] shape, so dim 0
    here is the layer axis and is never sharded).  Must agree with the
    placement `spmd.zero_sharding_spec` gives the live params, so the
    shard_map in_specs match the arrays' residency and no resharding
    copy is inserted."""
    from .spmd import zero_sharding_spec
    dims = {}
    for name, arr in stacked.items():
        spec = zero_sharding_spec(tuple(arr.shape[1:]), PartitionSpec(),
                                  axis, dp_size)
        d = next((i for i, a in enumerate(tuple(spec)) if a == axis),
                 None)
        dims[name] = None if d is None else d + 1   # +1: layer axis
    return dims


def zero3_scan_available(mesh: Optional[Mesh], axis: str,
                         batch: int) -> bool:
    """The overlapped path needs a real dp axis and a batch it can
    shard; anything else falls back to the GSPMD formulation (same
    memory story, XLA-placed gathers)."""
    return (mesh is not None and axis in mesh.axis_names
            and mesh.shape[axis] > 1 and batch % mesh.shape[axis] == 0)


def scan_layers_zero3(call_block: Callable, stacked: Dict[str, jax.Array],
                      h: jax.Array, mesh: Mesh, axis: str,
                      use_remat: bool = False, policy=None) -> jax.Array:
    """Run the stacked layer scan with one-layer-ahead gathered params.

    call_block(layer_params: {name: full array}, h) -> h runs ONE block
    with fully-gathered weights; `stacked` maps name -> [L, ...] arrays
    (dp-sharded per `zero3_shard_dims`); `h` is the [B, ...] activation,
    batch-sharded over `axis`.
    """
    dp = mesh.shape[axis]
    shard_dims = zero3_shard_dims(stacked, axis, dp)
    nd = {n: a.ndim for n, a in stacked.items()}
    param_specs = {}
    for n, d in shard_dims.items():
        dims = [None] * nd[n]
        if d is not None:
            dims[d] = axis
        param_specs[n] = PartitionSpec(*dims)
    batch_spec = PartitionSpec(axis)

    def local(h_loc, shards):
        def gather_layer(xs):
            """One layer's param shards -> full arrays (dim offsets are
            post-layer-slice, hence shard_dims[n] - 1)."""
            return {n: (x if shard_dims[n] is None else
                        _mesh.all_gather(x, axis, axis=shard_dims[n] - 1))
                    for n, x in xs.items()}

        if use_remat:
            # remat path: the gather lives INSIDE the checkpointed
            # region, so the per-iteration residual is the 1/dp SHARD
            # and the backward re-gathers — classic ZeRO-3.  The
            # prefetch-carry formulation below would make the gathered
            # full params a per-layer residual (L x full model on every
            # device), i.e. MORE memory than the sync stage-3 path the
            # overlap replaces.  Trade: no one-layer-ahead prefetch
            # here; the forward gather is still a separate op island
            # the async scheduler can hoist within the body.
            def body(hc, xs_cur):
                return call_block(gather_layer(xs_cur), hc), None

            body = jax.checkpoint(body, policy=policy, prevent_cse=False)
            h_out, _ = jax.lax.scan(body, h_loc, shards)
            return h_out

        # Non-remat residual note: the scan transpose keeps each
        # iteration's gathered weights alive for the backward — but the
        # synchronous GSPMD stage-3 scan does the same (its in-body
        # gather result is equally a per-iteration residual), so this is
        # parity, not a regression.  The '~1/dp param+grad memory' claim
        # is about PERSISTENT state (params, grads, optimizer); for 1/dp
        # backward residuals too, enable recompute — the remat branch
        # above re-gathers from shards.
        def body(carry, xs_next):
            hc, cur = carry
            # issue layer i+1's gather FIRST: it has no data dependence
            # on layer i's compute, so the async scheduler can run the
            # transfer under the block's matmuls
            nxt = gather_layer(xs_next)
            out = call_block(cur, hc)
            return (out, nxt), None

        first = gather_layer({n: s[0] for n, s in shards.items()})
        # iteration i consumes layer i+1's shard, read by dynamic index
        # from the closed-over shard stacks — NOT a jnp.roll copy, which
        # would transiently double the per-device sharded-param memory
        # (the final iteration re-gathers layer 0 into a dead carry
        # slot, keeping the scan body uniform)
        n_layers = next(iter(shards.values())).shape[0]

        def body_i(carry, i):
            nxt_shard = {
                n: jax.lax.dynamic_index_in_dim(
                    s, jax.lax.rem(i + 1, n_layers), 0, keepdims=False)
                for n, s in shards.items()}
            return body(carry, nxt_shard)

        (h_out, _), _ = jax.lax.scan(body_i, (h_loc, first),
                                     jnp.arange(n_layers))
        return h_out

    smapped = shard_map(local, mesh=mesh,
                        in_specs=(batch_spec, param_specs),
                        out_specs=batch_spec, check_vma=False)
    return smapped(h, stacked)
