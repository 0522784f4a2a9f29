"""DevicePrefetcher: overlap host->device transfer with compute.

The DataLoader's worker threads already overlap *decode/collate* with
the step; what still ran inside the step path was the ``device_put`` of
the collated batch (``SpmdTrainer.shard_batch``).  On a dispatch-bound
step loop that transfer serializes with dispatch: the host cannot queue
step N+1 before it finished placing batch N+1.

This wrapper moves the placement onto a background thread: while the
device runs step N, the thread ``device_put``s batches N+1..N+depth with
the trainer's batch sharding into a bounded queue.  The consumer then
feeds already-committed device arrays into ``train_step``, whose
``shard_batch`` fast-path recognizes them and skips the transfer.

Donation safety
---------------
``put_fn`` must produce FRESH committed arrays (a ``device_put`` of host
data does).  Prefetched buffers therefore never alias the trainer's
donated state: the compiled step donates only params/opt-state/buffers
(argnums 0..3), never the batch operands, and a rollback host snapshot
copies device state that was never handed to this queue.  Do not pass a
``put_fn`` that returns views of live training state.

Hygiene: worker exceptions surface on the consuming thread at the point
of the failed batch; ``close()`` (also called when the consumer exits
the loop early) drains the queue, unblocks and joins the thread.
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Iterable, Optional

__all__ = ["DevicePrefetcher"]

_BATCH, _ERROR, _END = 0, 1, 2


class DevicePrefetcher:
    """Iterate device-committed batches, transferred ``depth`` ahead.

    Parameters
    ----------
    host_iter : iterable of host batches (numpy / Tensor pytrees).
    put_fn : callable(batch) -> device batch.  Runs on the background
        thread; must return fresh committed arrays (e.g.
        ``SpmdTrainer.shard_batch``).
    depth : how many batches may be in flight on the device ahead of the
        consumer (bounded queue size).
    timings : optional dict accumulating ``data_wait_ms`` /
        ``h2d_ms`` (the trainer's step-time breakdown).
    """

    def __init__(self, host_iter: Iterable, put_fn: Callable[[Any], Any],
                 depth: int = 2, timings: Optional[dict] = None):
        self._iter = iter(host_iter)
        self._put = put_fn
        self._depth = max(1, int(depth))
        self._timings = timings if timings is not None else {}
        self._timings.setdefault("data_wait_ms", 0.0)
        self._q: queue.Queue = queue.Queue(maxsize=self._depth)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.batches_prefetched = 0

    # -- producer ------------------------------------------------------
    def _post(self, item) -> bool:
        """Enqueue, yielding to the stop flag; True if delivered."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _run(self):
        try:
            while True:
                # check stop BEFORE pulling: close() must not consume an
                # extra batch from a caller-owned single-pass stream
                if self._stop.is_set():
                    return
                try:
                    batch = next(self._iter)
                except StopIteration:
                    break
                dev = self._put(batch)
                self.batches_prefetched += 1
                if not self._post((_BATCH, dev)):
                    return
        except BaseException as e:  # propagate to the consumer
            self._post((_ERROR, e))
            return
        self._post((_END, None))

    def _ensure_started(self):
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="pd-device-prefetch", daemon=True)
            self._thread.start()

    # -- consumer ------------------------------------------------------
    def __iter__(self):
        from ..observability import spans as _spans
        self._ensure_started()
        try:
            while True:
                t0 = time.perf_counter()
                with _spans.span("data_wait", "train"):
                    while True:
                        try:
                            kind, payload = self._q.get(timeout=0.5)
                            break
                        except queue.Empty:
                            # a worker killed without posting its END/ERROR
                            # frame must not hang the training loop.  The
                            # producer may have posted its FINAL frame and
                            # exited between our timeout and this check, so
                            # drain once more before declaring it dead
                            if not self.alive:
                                try:
                                    kind, payload = self._q.get_nowait()
                                    break
                                except queue.Empty:
                                    raise RuntimeError(
                                        "device prefetch thread died without "
                                        "delivering a batch")
                dt = (time.perf_counter() - t0) * 1e3
                self._timings["data_wait_ms"] += dt
                if kind == _END:
                    return
                if kind == _ERROR:
                    raise payload
                yield payload
        finally:
            self.close()

    def __enter__(self):
        self._ensure_started()
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def close(self, join_timeout: float = 5.0):
        """Stop the transfer thread and reclaim the queue. Safe to call
        repeatedly and from ``finally`` blocks on early loop exit."""
        self._stop.set()
        # drain so a producer blocked on put() observes the stop flag
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        if self._thread is not None:
            self._thread.join(timeout=join_timeout)

    @property
    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()
