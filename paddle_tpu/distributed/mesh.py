"""Device mesh management.

Reference mapping (SURVEY.md §5/§7): the reference's ring_id->NCCLComm
registry (collective_helper.h:65) + per-parallel-dimension rings
(sharding/dp/pp pairs, pipeline_optimizer.py:136) become ONE
jax.sharding.Mesh with named axes; a "ring" is just a mesh axis name, and
XLA lowers collectives over the right ICI links from the device
assignment. Axis-name conventions used across the framework:

    dp - data parallel          tp - tensor model parallel
    pp - pipeline stages        sp - sequence/context parallel
    ep - expert parallel        dcn - data-parallel across slices

The `dcn` axis is the multi-slice tier: devices within one slice talk
over ICI, slices talk over the (much slower) data-center network.
`create_mesh(..., dcn_slices=N)` (or PADDLE_TPU_DCN_SLICES=N) prepends
a dcn axis of size N, and sharding the batch over ("dcn", "dp") makes
GSPMD emit the hierarchical gradient reduce: ICI all-reduce within a
slice, DCN all-reduce across slices.
"""
from __future__ import annotations

import contextlib
import os
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from jax import shard_map  # noqa: F401  (re-exported to every schedule)


def axis_size(axis_name: str) -> int:
    """Static size of a bound mesh axis inside shard_map/pmap bodies."""
    return jax.lax.axis_size(axis_name)


# ---------------------------------------------------------------------------
# Collective helpers (used inside shard_map bodies): one spelling for
# spmd/pipeline/moe/ring_attention.  All three return the TILED layout: gather concatenates shards on `axis`,
# reduce_scatter leaves each rank its `axis` slice of the sum.
# ---------------------------------------------------------------------------
def all_gather(x, axis_name: str, *, axis: int = 0):
    """Concatenate every rank's shard along `axis` (tiled all-gather)."""
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=True)


def reduce_scatter(x, axis_name: str, *, axis: int = 0):
    """Sum over the axis group and keep this rank's `axis` slice — the
    transpose of `all_gather`, and the collective ZeRO grads leave the
    backward as."""
    if hasattr(jax.lax, "psum_scatter"):
        return jax.lax.psum_scatter(x, axis_name,
                                    scatter_dimension=axis, tiled=True)
    # very old jax: psum + per-rank dynamic slice (correct, not bandwidth
    # optimal — only a fallback)
    n = axis_size(axis_name)
    if x.shape[axis] % n:
        # psum_scatter would raise here; the fallback must not silently
        # truncate the trailing rows instead
        raise ValueError(
            f"reduce_scatter: dim {axis} of shape {x.shape} is not "
            f"divisible by axis '{axis_name}' size {n}")
    full = jax.lax.psum(x, axis_name)
    idx = jax.lax.axis_index(axis_name)
    shard = x.shape[axis] // n
    return jax.lax.dynamic_slice_in_dim(full, idx * shard, shard, axis)


def ppermute(x, axis_name: str, perm):
    """Point-to-point send/recv over the axis ring (pipeline stage
    boundaries). perm: [(src, dst), ...]; unaddressed dsts receive
    zeros."""
    return jax.lax.ppermute(x, axis_name, perm)


__all__ = ["Mesh", "NamedSharding", "PartitionSpec", "axis_size",
           "all_gather", "reduce_scatter", "ppermute",
           "create_mesh", "get_mesh", "set_mesh", "mesh_axis_size",
           "default_mesh", "shard_map", "dcn_slice_count", "slice_size"]

_current_mesh: Optional[Mesh] = None


def create_mesh(axes: Union[Dict[str, int], Sequence[int]],
                axis_names: Optional[Sequence[str]] = None,
                devices=None,
                dcn_slices: Optional[int] = None) -> Mesh:
    """Build a Mesh from {'dp': 2, 'tp': 4} style spec. -1 for one axis
    means 'all remaining devices'.

    dcn_slices=N (or PADDLE_TPU_DCN_SLICES=N) prepends a "dcn" axis of
    size N — the mesh becomes N slices of equal shape, dcn-major in
    device order (slice s owns `devices.reshape(N, -1)[s]`), so ICI
    collectives group within a slice and dcn-axis collectives cross
    slices. A spec that already names a "dcn" axis wins over both.
    """
    if isinstance(axes, dict):
        names = list(axes.keys())
        shape = list(axes.values())
    else:
        shape = list(axes)
        names = list(axis_names or [f"axis{i}" for i in range(len(shape))])
    if dcn_slices is None:
        env = os.environ.get("PADDLE_TPU_DCN_SLICES", "").strip()
        if env:
            try:
                dcn_slices = int(env)
            except ValueError:
                dcn_slices = None
    if dcn_slices is not None and int(dcn_slices) >= 1 and "dcn" not in names:
        names = ["dcn"] + names
        shape = [int(dcn_slices)] + shape
    devs = np.asarray(devices if devices is not None else jax.devices())
    # deterministic chaos (PADDLE_FAULT_MESH_SHRINK): the scheduler
    # handed back fewer chips — build the mesh from the survivors only,
    # so elastic-restore tests exercise a real topology change without
    # re-execing under a different device-count flag
    from ..testing import faults as _faults
    _shrink = _faults.mesh_shrink()
    if _shrink is not None and _shrink < devs.size:
        n_dcn = shape[names.index("dcn")] if "dcn" in names else 0
        if n_dcn > 0:
            # multi-slice clamp at whole-slice granularity: a ragged
            # slice (half its chips gone) can't host its shard of the
            # per-slice axes, so the survivors are the largest whole
            # number of slices that fit under the clamp — the dcn
            # extent shrinks, every surviving slice stays intact
            per_slice = max(devs.size // n_dcn, 1)
            whole = max((_shrink // per_slice) * per_slice, per_slice)
            devs = devs.reshape(-1)[:whole]
            shape[names.index("dcn")] = whole // per_slice
        else:
            devs = devs.reshape(-1)[:_shrink]
    n = devs.size
    if -1 in shape:
        known = int(np.prod([s for s in shape if s != -1]))
        shape[shape.index(-1)] = n // known
    total = int(np.prod(shape))
    if total > n:
        raise ValueError(f"mesh {dict(zip(names, shape))} needs {total} "
                         f"devices, only {n} available")
    mesh = Mesh(devs[:total].reshape(shape), tuple(names))
    return mesh


def dcn_slice_count(mesh: Mesh) -> int:
    """Number of DCN slices in the mesh (1 when there is no dcn axis)."""
    if "dcn" not in mesh.axis_names:
        return 1
    return max(int(mesh.shape["dcn"]), 1)


def slice_size(mesh: Mesh) -> int:
    """Devices per DCN slice (the whole mesh when single-slice)."""
    return mesh.devices.size // dcn_slice_count(mesh)


def set_mesh(mesh: Optional[Mesh]):
    global _current_mesh
    _current_mesh = mesh
    return mesh


def get_mesh() -> Optional[Mesh]:
    return _current_mesh


def default_mesh() -> Mesh:
    """Current mesh, or a 1-axis 'dp' mesh over all devices."""
    global _current_mesh
    if _current_mesh is None:
        _current_mesh = create_mesh({"dp": -1})
    return _current_mesh


def mesh_axis_size(name: str, mesh: Optional[Mesh] = None) -> int:
    m = mesh or get_mesh()
    if m is None or name not in m.axis_names:
        return 1
    return m.shape[name]


@contextlib.contextmanager
def mesh_guard(mesh: Mesh):
    prev = get_mesh()
    set_mesh(mesh)
    try:
        yield mesh
    finally:
        set_mesh(prev)


# The COMPILE mesh is a separate channel set only while a compiled
# trainer traces its step: layers use it to place sharding constraints
# on intermediates. It must not be satisfied by a mesh that merely got
# cached through default_mesh() — eager tape ops also trace (jax.vjp)
# and would otherwise pick up constraints from an unrelated mesh.
_compile_mesh: Optional[Mesh] = None


def get_compile_mesh() -> Optional[Mesh]:
    return _compile_mesh


@contextlib.contextmanager
def compile_mesh_guard(mesh: Mesh):
    """Used by SpmdTrainer around compiled-step calls: publishes the
    mesh on BOTH channels (ambient get_mesh for e.g. ring attention
    routing, compile channel for sharding constraints)."""
    global _compile_mesh
    prev_c, _compile_mesh = _compile_mesh, mesh
    with mesh_guard(mesh):
        try:
            yield mesh
        finally:
            _compile_mesh = prev_c
