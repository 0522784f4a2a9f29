"""Profiler + memory/FLOP evidence tests (VERDICT r2 #8).

Replaces the shape-only assertions: ZeRO-3 is proven by per-device
param BYTES, recompute by compiled FLOP counts (the CPU backend reports
temp_size_in_bytes=0, so the peak-HBM assertion is TPU-gated; the FLOPs
side of the remat trade is assertable everywhere).
"""
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import profiler
from paddle_tpu.distributed import SpmdTrainer, create_mesh
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                               GPTPretrainingCriterion)


@pytest.mark.slow
def test_record_event_and_trace_capture(tmp_path):
    """profiler ctx writes a real trace artifact; RecordEvent nests.
    Spinning up the real JAX profiler costs ~15s — slow-marked under
    the tight tier-1 budget; the start/stop state machine and step
    timer below keep the API surface covered in tier-1."""
    d = str(tmp_path / "trace")
    with profiler.profiler(log_dir=d):
        with profiler.RecordEvent("train_step"):
            x = jnp.ones((128, 128))
            (x @ x).block_until_ready()
    files = glob.glob(os.path.join(d, "**", "*"), recursive=True)
    assert any(os.path.isfile(f) for f in files), "no trace written"


def test_start_stop_profiler_state_machine(tmp_path):
    d = str(tmp_path / "t2")
    profiler.start_profiler(d)
    with pytest.raises(RuntimeError):
        profiler.start_profiler(d)
    assert profiler.stop_profiler() == d
    assert profiler.stop_profiler() is None  # idempotent


def test_step_timer():
    t = profiler.StepTimer(warmup=1)
    t.start()
    for _ in range(4):
        t.tick()
    s = t.summary()
    assert s["steps"] == 3 and s["mean_ms"] >= 0


def test_hapi_fit_logs_step_time():
    import paddle_tpu.nn as nn
    from paddle_tpu.hapi import Model
    from paddle_tpu.vision.models import LeNet

    class DS:
        def __len__(self):
            return 32

        def __getitem__(self, i):
            r = np.random.RandomState(i)
            return (r.randn(1, 28, 28).astype(np.float32),
                    np.array([i % 10], np.int64))

    seen = []

    class Rec(paddle.callbacks.Callback):
        def on_train_batch_end(self, step, logs=None):
            if "step_time_ms" in logs:
                seen.append(logs["step_time_ms"])

    paddle.seed(0)
    m = Model(LeNet())
    m.prepare(paddle.optimizer.SGD(learning_rate=0.01,
                                   parameters=m.parameters()),
              nn.CrossEntropyLoss())
    m.fit(DS(), batch_size=16, epochs=1, verbose=0, callbacks=[Rec()])
    assert seen and all(v >= 0 for v in seen)


def _gpt_loss_grad(remat: bool):
    from paddle_tpu.func import functional_call
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=4,
                    num_heads=4, max_seq_len=64, use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    if remat:
        model.enable_recompute()
    model.train()
    crit = GPTPretrainingCriterion()
    params = {n: p.data for n, p in model.named_parameters()}
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 128, (4, 64)).astype(np.int32))
    labels = jnp.asarray(np.roll(np.asarray(ids), -1, 1))

    def loss_fn(p):
        from paddle_tpu.core.autograd import no_grad
        from paddle_tpu.core.tensor import Tensor
        with no_grad():
            out, _ = functional_call(model, p, {}, ids, training=True)
        return crit(Tensor(out, stop_gradient=True),
                    Tensor(labels)).data

    return jax.jit(jax.grad(loss_fn)).lower(params).compile()


def test_recompute_trades_flops_for_memory():
    """recompute re-executes forwards in backward: compiled FLOPs must
    rise; on a real accelerator peak temp memory must drop (the CPU
    backend reports temp=0, so that half is TPU-gated)."""
    plain = _gpt_loss_grad(remat=False)
    remat = _gpt_loss_grad(remat=True)
    f_plain = profiler.cost_stats(plain)["flops"]
    f_remat = profiler.cost_stats(remat)["flops"]
    assert f_remat > f_plain * 1.15, (f_plain, f_remat)
    if jax.default_backend() not in ("cpu",):  # pragma: no cover
        m_plain = profiler.memory_stats(plain)["temp_bytes"]
        m_remat = profiler.memory_stats(remat)["temp_bytes"]
        assert m_remat < m_plain


def test_zero3_shards_param_bytes():
    """ZeRO-3: per-device param bytes ~ total/dp for shardable params
    (byte-level evidence replacing round-2's shape-only assertion)."""
    import paddle_tpu.nn as nn
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(64, 256), nn.ReLU(),
                          nn.Linear(256, 64))
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters())
    st = DistributedStrategy()
    st.sharding = True
    st.sharding_configs = {"stage": 3}
    mesh = create_mesh({"dp": 8})
    tr = SpmdTrainer(model, opt, lambda o, l: (o - l).square().mean(),
                     mesh=mesh, strategy=st)
    dev0 = mesh.devices.ravel()[0]
    for name, arr in tr.params.items():
        total = arr.nbytes
        local = sum(sh.data.nbytes for sh in arr.addressable_shards
                    if sh.device == dev0)
        if any(d % 8 == 0 and d >= 8 for d in arr.shape):
            assert local * 8 == total, \
                f"{name}: local {local} * 8 != total {total}"
    # optimizer moment state sharded the same way (stage>=1)
    m0 = tr.opt_state["0.weight"]["moment1"]
    local = sum(sh.data.nbytes for sh in m0.addressable_shards
                if sh.device == dev0)
    assert local * 8 == m0.nbytes
