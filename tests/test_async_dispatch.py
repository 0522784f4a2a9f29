"""Async dispatch & input-pipeline overlap (ISSUE 3).

The pipelined step loop's contracts:
- DevicePrefetcher delivers batches in order, committed with the
  trainer's sharding, and its fast-path re-entry into train_step is a
  no-op placement;
- worker/iterator failures surface on the consumer; early exit joins the
  transfer thread (no leaked daemons);
- anomaly_policy='rollback' stays correct when batches arrive through
  the prefetcher (the host snapshot never aliases a prefetched buffer);
- Model.fit performs at most ONE blocking host sync per log_freq window
  (counted, not eyeballed);
- the persistent XLA compile cache serves a warm second compile on the
  CPU backend.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import SpmdTrainer, async_dispatch, create_mesh
from paddle_tpu.distributed.async_dispatch import LazyValue, StepResult
from paddle_tpu.io import DataLoader
from paddle_tpu.io.device_prefetch import DevicePrefetcher



def make_mlp(seed):
    paddle.seed(seed)
    return nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 10))


def ce_loss(out, label):
    return nn.functional.cross_entropy(out, label)


def make_batches(n=4, bs=16, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(bs, 16).astype(np.float32),
             rng.randint(0, 10, size=(bs,)).astype(np.int64))
            for _ in range(n)]


def _trainer(seed=0, mesh_spec=None, **kw):
    mesh = create_mesh(mesh_spec or {"dp": 8})
    model = make_mlp(seed)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    return SpmdTrainer(model, opt, ce_loss, mesh=mesh, **kw)


# ---------------------------------------------------------------------------
# DevicePrefetcher
# ---------------------------------------------------------------------------
def test_device_prefetch_order_and_sharding():
    tr = _trainer()
    batches = make_batches(6)
    pref = DevicePrefetcher(iter(batches), tr.shard_batch, depth=2)
    out = list(pref)
    assert len(out) == 6
    for (hx, hy), (dx, dy) in zip(batches, out):
        np.testing.assert_array_equal(np.asarray(dx), hx)
        np.testing.assert_array_equal(np.asarray(dy), hy)
        # committed with the trainer's batch sharding on the full mesh
        assert getattr(dx, "committed", False)
        assert len(dx.sharding.device_set) == 8
        assert dx.sharding == tr._batch_sharding(dx)
    assert not pref.alive  # producer drained and exited


def test_prefetched_steps_match_direct_feed():
    batches = make_batches(4)
    ref = _trainer(0)
    direct = [float(ref.train_step(x, y)) for x, y in batches]

    tr = _trainer(0)
    pref = DevicePrefetcher(iter(batches), tr.shard_batch, depth=3)
    got = [float(tr.train_step(x, y)) for x, y in pref]
    np.testing.assert_allclose(got, direct, rtol=1e-6, atol=1e-7)
    # fast path: re-sharding an already-committed batch found them placed
    assert pref.batches_prefetched == 4


def test_prefetcher_propagates_source_exception():
    tr = _trainer()
    batches = make_batches(2)

    def gen():
        yield batches[0]
        raise RuntimeError("boom in the loader")

    pref = DevicePrefetcher(gen(), tr.shard_batch, depth=2)
    it = iter(pref)
    next(it)
    with pytest.raises(RuntimeError, match="boom in the loader"):
        next(it)
    assert not pref.alive


def test_prefetcher_early_exit_joins_thread():
    tr = _trainer()
    pref = DevicePrefetcher(iter(make_batches(50)), tr.shard_batch,
                            depth=2)
    it = iter(pref)
    next(it)
    next(it)
    it.close()  # consumer leaves the loop early
    assert not pref.alive


# ---------------------------------------------------------------------------
# StepResult laziness
# ---------------------------------------------------------------------------
def test_train_step_returns_lazy_step_result():
    tr = _trainer(0)
    x, y = make_batches(1)[0]
    res = tr.train_step(x, y)
    assert isinstance(res, StepResult)
    before = async_dispatch.host_sync_count()
    v1 = float(res)
    v2 = float(res)  # cached: no second sync
    assert v1 == v2 and np.isfinite(v1)
    assert async_dispatch.host_sync_count() == before + 1
    assert f"{res:.4f}" == f"{v1:.4f}"
    # stats carry the step-time breakdown fields
    st = tr.stats
    for k in ("data_wait_ms", "h2d_ms", "dispatch_ms", "sync_ms",
              "compile_ms_cold", "steps_timed"):
        assert k in st
    assert st["compile_ms_cold"] > 0
    assert st["steps_timed"] == 0  # single step was the compile call


# ---------------------------------------------------------------------------
# rollback + prefetch: donation safety
# ---------------------------------------------------------------------------
def test_step_result_wraps_plain_numpy_values():
    # numpy exposes .data as a memoryview — the unwrap must not grab it
    assert float(StepResult(np.float32(2.5))) == 2.5
    assert float(StepResult(np.array(1.25))) == 1.25
    assert float(LazyValue(lambda: np.float64(0.5))) == 0.5


def test_thread_prefetcher_slow_iterator_does_not_block_emission():
    """A slow batch ITERATOR must not stall delivery of batches that are
    already collated (workers pull tasks outside the emit lock)."""
    from paddle_tpu.io.dataloader import _Prefetcher

    def make_iter():
        def gen():
            yield (lambda: "fast")
            time.sleep(1.5)  # stream stall while producing task 2
            yield (lambda: "slow")
        return gen()

    p = _Prefetcher(make_iter, num_workers=2, capacity=4)
    it = iter(p)
    t0 = time.monotonic()
    first = next(it)
    waited = time.monotonic() - t0
    assert first == "fast"
    assert waited < 1.0, f"emission blocked {waited:.2f}s on the iterator"
    assert next(it) == "slow"


def test_rollback_correct_with_prefetched_batches():
    batches = make_batches(5, bs=8, seed=3)
    bomb_x = batches[2][0].copy()
    bomb_x[0, 0] = np.nan
    fed = [(bomb_x if i == 2 else x, y)
           for i, (x, y) in enumerate(batches)]

    clean = _trainer(13, {"dp": 2})
    for i, (x, y) in enumerate(batches):
        if i != 2:
            clean.train_step(x, y)

    tr = _trainer(13, {"dp": 2}, anomaly_policy="rollback")
    pref = DevicePrefetcher(iter(fed), tr.shard_batch, depth=3)
    for x, y in pref:
        tr.train_step(x, y)
    assert tr.stats["rollback_steps"] == 1
    assert tr._step_count == 4  # the poisoned step never counted
    # the restored state must match a run that never saw the bomb: a
    # host snapshot aliasing a prefetched/donated buffer would diverge
    for n in tr.params:
        np.testing.assert_allclose(np.asarray(tr.params[n]),
                                   np.asarray(clean.params[n]),
                                   rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# fit(): at most one blocking sync per log_freq window
# ---------------------------------------------------------------------------
class _DS:
    def __init__(self, n=64, seed=0):
        rng = np.random.RandomState(seed)
        self.x = rng.randn(n, 16).astype(np.float32)
        self.y = rng.randint(0, 10, (n,)).astype(np.int64)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


def test_fit_syncs_at_most_once_per_log_window():
    from paddle_tpu.hapi import Model
    paddle.seed(11)
    m = Model(make_mlp(11))
    m.prepare(paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=m.parameters()),
              nn.CrossEntropyLoss(), mesh={"dp": 8})
    steps, log_freq = 8, 4
    async_dispatch.reset_host_sync_count()
    m.fit(_DS(8 * 8), batch_size=8, epochs=1, verbose=0, shuffle=False,
          log_freq=log_freq)
    syncs = async_dispatch.host_sync_count()
    # windows at steps 0 and 4, plus the end-of-epoch resolve
    assert 1 <= syncs <= steps // log_freq + 2, syncs
    assert syncs < steps  # and emphatically not one per step


def test_fit_loss_curve_unchanged_by_async_loop():
    """Laziness must not change WHAT is computed: per-batch losses seen
    by a callback equal the eager loop's (the PR-0 parity bar)."""
    from paddle_tpu.hapi import Model

    def run(mesh):
        paddle.seed(7)
        m = Model(make_mlp(7))
        kw = {"mesh": mesh} if mesh else {}
        m.prepare(paddle.optimizer.SGD(learning_rate=0.05,
                                       parameters=m.parameters()),
                  nn.CrossEntropyLoss(), **kw)
        seen = []

        class Rec(paddle.callbacks.Callback):
            def on_train_batch_end(self, step, logs=None):
                seen.append(float(logs["loss"]))

        m.fit(_DS(48), batch_size=16, epochs=2, verbose=0, shuffle=False,
              callbacks=[Rec()])
        return seen

    np.testing.assert_allclose(run({"dp": 8}), run(None),
                               rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# persistent compile cache: warm start on the CPU backend
# ---------------------------------------------------------------------------
def test_compile_cache_warm_start_cpu(monkeypatch):
    from jax._src import compilation_cache as _cc
    import jax

    x, y = make_batches(1)[0]
    tr = _trainer(0, {"dp": 1})
    float(tr.train_step(x, y))  # populates the persistent cache

    jax.clear_caches()  # drop in-memory executables, keep the disk cache
    tr2 = _trainer(0, {"dp": 1})
    hits = [0]
    orig = _cc.get_executable_and_time

    def counting(*a, **kw):
        ex, t = orig(*a, **kw)
        if ex is not None:
            hits[0] += 1
        return ex, t

    monkeypatch.setattr(_cc, "get_executable_and_time", counting)
    float(tr2.train_step(x, y))
    assert hits[0] >= 1  # the recompile was served from disk


def test_compile_cache_env_off(monkeypatch):
    from paddle_tpu.utils import compile_cache as cc
    monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE", "off")
    monkeypatch.setattr(cc, "_STATE", {"resolved": False, "dir": None})
    assert cc.ensure_compile_cache() is None
    assert not cc.compile_cache_enabled()


# ---------------------------------------------------------------------------
# DataLoader thread-prefetcher hygiene
# ---------------------------------------------------------------------------
class _CountingDS:
    fetched = 0

    def __init__(self, n=64):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        type(self).fetched += 1
        return np.full(4, i, np.float32)


def test_thread_prefetcher_backpressure():
    """Workers must not collate the whole dataset ahead of a slow
    consumer — the reorder buffer is bounded."""
    _CountingDS.fetched = 0
    loader = DataLoader(_CountingDS(64), batch_size=4, num_workers=2,
                        prefetch_factor=2, use_shared_memory=False)
    it = iter(loader)
    next(it)
    time.sleep(0.5)  # let unbounded workers run away, if they could
    # capacity (2*2=4 batches) + in-flight (2) + consumed (1), in items
    assert _CountingDS.fetched <= 10 * 4, _CountingDS.fetched
    it.close()


def test_thread_prefetcher_propagates_dataset_error():
    class Bad:
        def __len__(self):
            return 16

        def __getitem__(self, i):
            if i >= 8:
                raise ValueError("bad sample")
            return np.zeros(4, np.float32)

    loader = DataLoader(Bad(), batch_size=4, num_workers=2,
                        use_shared_memory=False)
    with pytest.raises(ValueError, match="bad sample"):
        list(loader)


def test_thread_prefetcher_iterator_error_no_deadlock():
    from paddle_tpu.io.dataloader import _Prefetcher

    def make_iter():
        def gen():
            yield (lambda: 1)
            raise RuntimeError("iter broke")
        return gen()

    p = _Prefetcher(make_iter, num_workers=2, capacity=4)
    out = []
    with pytest.raises(RuntimeError, match="iter broke"):
        for v in p:
            out.append(v)
    assert out == [1]


def test_thread_prefetcher_early_exit_joins_workers():
    base = threading.active_count()
    loader = DataLoader(_CountingDS(64), batch_size=4, num_workers=3,
                        use_shared_memory=False)
    it = iter(loader)
    next(it)
    it.close()  # break out early: workers must be woken and joined
    deadline = time.monotonic() + 5
    while threading.active_count() > base and time.monotonic() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= base


# ---------------------------------------------------------------------------
# metrics: device-array update path (no eager np.asarray per step)
# ---------------------------------------------------------------------------
def test_accuracy_update_stays_on_device():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.metric import Accuracy

    rng = np.random.RandomState(0)
    logits = rng.randn(8, 10).astype(np.float32)
    labels = rng.randint(0, 10, (8, 1)).astype(np.int64)

    m = Accuracy()
    pre = m.compute(Tensor(jnp.asarray(logits)), Tensor(jnp.asarray(labels)))
    assert isinstance(pre.data, jax.Array)
    m.update(pre)
    # the running total is a device scalar — nothing was pulled to host
    assert isinstance(m.total[0], jax.Array)

    ref = Accuracy()
    ref_pre = ref.compute(Tensor(np.asarray(logits)), labels)
    ref.update(np.asarray(ref_pre.data))
    assert m.accumulate() == pytest.approx(ref.accumulate())
