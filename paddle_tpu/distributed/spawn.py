"""paddle.distributed.spawn — multiprocessing entry for dygraph.

Reference: python/paddle/distributed/spawn.py:276 (spawn: start nprocs
python processes running func(rank, *args) with the PADDLE_* env set,
join and re-raise child failures). TPU-native: children rendezvous via
the JAX coordinator address exported in the env (env.init_parallel_env),
and children can be pinned to a specific jax platform via
spawn(..., backend='cpu') so single-host CPU rings (the reference's
localhost test strategy) work on machines with one real accelerator.
"""
from __future__ import annotations

import multiprocessing as mp
import os
import time
import traceback
from typing import Optional, Tuple

from .launch import find_free_port, trainer_env_vars

__all__ = ["spawn", "SpawnContext"]


def _worker(func, rank, world, coordinator, endpoints, args, err_q,
            backend):
    try:
        os.environ.update(
            trainer_env_vars(rank, world, endpoints, coordinator))
        if backend:
            # pin the child's jax platform BEFORE it imports jax
            os.environ["JAX_PLATFORMS"] = backend
        func(rank, *args)
    except Exception:
        err_q.put((rank, traceback.format_exc()))
        raise


class SpawnContext:
    def __init__(self, procs, err_q):
        self.processes = procs
        self._err_q = err_q

    def join(self, timeout: Optional[float] = None) -> bool:
        """Wait for all workers; on the FIRST failure terminate the
        survivors (they may be blocked in a collective waiting for the
        dead rank) and re-raise — the reference spawn's watch loop."""
        deadline = time.time() + timeout if timeout is not None else None

        def fail(rank=None, tb=None, codes=None):
            for p in self.processes:
                if p.is_alive():
                    p.terminate()
            for p in self.processes:
                p.join(5)
            if tb is not None:
                raise RuntimeError(
                    f"spawned trainer rank {rank} failed:\n{tb}")
            raise RuntimeError(f"spawned trainers exited with {codes}")

        while True:
            if not self._err_q.empty():
                rank, tb = self._err_q.get()
                fail(rank=rank, tb=tb)
            bad = [p.exitcode for p in self.processes
                   if p.exitcode not in (0, None)]
            if bad:
                # give the failed rank a moment to flush its traceback
                time.sleep(0.2)
                if not self._err_q.empty():
                    rank, tb = self._err_q.get()
                    fail(rank=rank, tb=tb)
                fail(codes=bad)
            if all(not p.is_alive() for p in self.processes):
                return True
            if deadline and time.time() > deadline:
                return False
            time.sleep(0.1)


def spawn(func, args: Tuple = (), nprocs: int = 2, join: bool = True,
          daemon: bool = False, backend: Optional[str] = None,
          **options):
    """Start `nprocs` processes running func(rank, *args) (reference
    spawn.py:276). Returns a SpawnContext (join=False) or joins.

    backend: jax platform to pin the children to (None = inherit the
    parent's platform selection, matching the reference's behavior).
    Pass backend='cpu' for single-host CPU rings on a machine with one
    real accelerator — otherwise every child grabs the same chip."""
    ctx = mp.get_context("spawn")
    err_q = ctx.Queue()
    coordinator = f"127.0.0.1:{find_free_port()}"
    endpoints = [f"127.0.0.1:{find_free_port()}" for _ in range(nprocs)]
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(
            target=_worker,
            args=(func, rank, nprocs, coordinator, endpoints, args, err_q,
                  backend),
            daemon=daemon)
        p.start()
        procs.append(p)
    sctx = SpawnContext(procs, err_q)
    if join:
        sctx.join()
        return None
    return sctx
