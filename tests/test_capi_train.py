"""Native training entry: exported StableHLO train step driven from C.

Reference: paddle/fluid/train/demo/demo_trainer.cc (a C++ binary that
loads a saved train program and steps it). Here the artifact is
SpmdTrainer.export_train_step's serialized fwd+bwd+update program.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import SpmdTrainer, create_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_trainer():
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(6, 16), nn.ReLU(), nn.Linear(16, 1))
    opt = paddle.optimizer.Adam(learning_rate=0.02,
                                parameters=model.parameters())
    return SpmdTrainer(model, opt, lambda o, y: F.mse_loss(o, y),
                       mesh=create_mesh({"dp": 1}))


def example_batch(bs=8, nf=6):
    rng = np.random.RandomState(0)
    x = rng.randn(bs, nf).astype(np.float32)
    return x, x.sum(axis=1, keepdims=True).astype(np.float32)


@pytest.fixture(scope="module")
def exported_trainer(tmp_path_factory):
    tr = make_trainer()
    x, y = example_batch()
    path = str(tmp_path_factory.mktemp("train") / "reg")
    tr.export_train_step(path, x, y)
    return path


def test_exported_step_matches_live_trainer(exported_trainer):
    """Stepping the deserialized program must equal the live trainer."""
    from paddle_tpu.inference import capi_bridge as B
    x, y = example_batch()
    h = B.create_trainer(exported_trainer)
    live = make_trainer()
    for i in range(5):
        raw, shape, dtype = B.trainer_step(
            h, [(x.tobytes(), x.shape, "float32"),
                (y.tobytes(), y.shape, "float32")])
        got = float(np.frombuffer(raw, np.dtype(dtype)))
        want = float(live.train_step(x, y))
        assert got == pytest.approx(want, rel=1e-4), f"step {i}"
    B.destroy_trainer(h)


@pytest.mark.slow
def test_standalone_c_binary_trains(exported_trainer, tmp_path_factory):
    from paddle_tpu.inference.capi.build import build_demo
    try:
        exe = build_demo(str(tmp_path_factory.mktemp("bin") /
                             "pd_capi_train_demo"),
                         source="capi_train_demo.c")
    except Exception as e:
        pytest.skip(f"cannot build train demo: {e}")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"    # a CPU-only child
    proc = subprocess.run([exe, exported_trainer, "6", "8"], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, \
        f"rc={proc.returncode}\nstdout={proc.stdout}\nstderr={proc.stderr}"
    assert "CAPI-TRAIN-OK" in proc.stdout


def test_export_refuses_fp16_and_guard():
    import paddle_tpu
    from paddle_tpu.distributed.fleet import DistributedStrategy
    paddle.seed(0)
    model = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    st = DistributedStrategy()
    st.amp = True
    st.amp_configs = {"use_bf16": False}
    tr = SpmdTrainer(model, opt, lambda o, y: F.mse_loss(o, y),
                     mesh=create_mesh({"dp": 1}), strategy=st)
    with pytest.raises(NotImplementedError):
        tr.export_train_step("/tmp/nope", np.ones((2, 4), np.float32),
                             np.ones((2, 2), np.float32))
