"""Elastic cross-topology resilience (ISSUE 10): reshardable manifest-v2
checkpoints, shrink/grow restores, the expanded fault harness, and the
serving-side drain/deadline satellites.

Done criteria exercised here:
- a checkpoint written on one mesh (dp=8 / ZeRO-3 dp=4 / pp=4) restores
  onto a SMALLER mesh with loss-curve parity (bitwise for plain dp,
  rtol 1e-5 where the collective structure changes) and records the
  reshard in trainer/manager stats;
- MANIFEST.json v2 carries mesh_axes + per-leaf global shape/dtype/
  logical sharding spec; legacy v1 states still load on an identical
  mesh;
- restore_latest falls back past a corrupt newest candidate onto the
  newest LOADABLE one and reshards it when its topology differs;
- the new fault knobs are deterministic: PADDLE_FAULT_CKPT_TRUNCATE
  commits a partial shard and kills the process, PADDLE_FAULT_MESH_SHRINK
  clamps the devices create_mesh sees, PADDLE_FAULT_FS_DELAY_MS injects
  write jitter;
- kill-and-resume onto a SHRUNK mesh reproduces the uninterrupted loss
  curve end to end (subprocess tests);
- CheckpointManager surfaces background commit failures (on_error /
  wait timeout), and the InferenceEngine drains gracefully and enforces
  per-request deadlines.
"""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import (CheckpointManager, SpmdTrainer,
                                    create_mesh, latest_checkpoint)
from paddle_tpu.distributed.checkpoint import (read_checkpoint,
                                               read_manifest,
                                               validate_checkpoint)
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.testing import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _mesh(dp):
    return create_mesh({"dp": dp}, devices=jax.devices()[:dp])


def _trainer(dp, seed=0, strategy=None, **kw):
    paddle.seed(seed)
    model = nn.Linear(6, 4)
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=model.parameters())
    return SpmdTrainer(model, opt, lambda o, y: F.mse_loss(o, y),
                       mesh=_mesh(dp), strategy=strategy, **kw)


def _batches(n, seed=0, cols=6, out=4):
    rng = np.random.RandomState(seed)
    return [(rng.randn(8, cols).astype(np.float32),
             rng.randn(8, out).astype(np.float32)) for _ in range(n)]


# ---------------------------------------------------------------------------
# manifest v2 metadata
# ---------------------------------------------------------------------------
def test_manifest_v2_records_topology(tmp_path):
    tr = _trainer(4)
    for x, y in _batches(2):
        tr.train_step(x, y)
    p = str(tmp_path / "ck")
    tr.save(p, manifest=True)
    man = read_manifest(p)
    assert man["version"] == 2
    assert man["mesh_axes"] == {"dp": 4}
    # per-leaf global shape + dtype + LOGICAL spec (no device ids)
    leaves = man["leaves"]
    w = leaves["params['weight']"]
    assert w["shape"] == [6, 4] and w["dtype"] == "float32"
    assert all(e is None or isinstance(e, (str, list))
               for e in w["spec"])
    # the pickled state carries the same record
    state = read_checkpoint(p)
    assert state["version"] == 2
    assert state["mesh_axes"] == {"dp": 4}
    assert "params" in state["sharding_specs"]
    # still validates under the v1 manifest walker
    assert validate_checkpoint(p)


def test_legacy_v1_state_restores_on_identical_mesh(tmp_path):
    """A pre-v2 checkpoint (no topology record) must keep loading
    unchanged on the same layout."""
    tr = _trainer(2)
    for x, y in _batches(3):
        tr.train_step(x, y)
    from paddle_tpu.distributed.checkpoint import (snapshot_trainer,
                                                   write_checkpoint)
    state = snapshot_trainer(tr)
    for k in ("version", "mesh_axes", "sharding_specs"):
        state.pop(k, None)               # forge the PR-2 layout
    p = str(tmp_path / "legacy")
    write_checkpoint(state, p)
    assert read_manifest(p)["version"] == 1
    tr2 = _trainer(2, seed=9)
    tr2.load(p)
    assert tr2._step_count == 3
    assert tr2._last_restore_info["resharded"] is False
    assert tr2._last_restore_info["version"] == 1
    for n in tr.params:
        np.testing.assert_array_equal(np.asarray(tr.params[n]),
                                      np.asarray(tr2.params[n]))


# ---------------------------------------------------------------------------
# elastic restores: dp shrink (bitwise), ZeRO-3, pipeline, strict mode
# ---------------------------------------------------------------------------
def test_dp_shrink_restore_parity(tmp_path):
    """dp=4 -> dp=2: the canonical elastic shrink.  Plain dp resharding
    leaves the math identical up to the dp-reduce tree's summation
    order, so parity is ulp-tight (the SUBPROCESS test below runs the
    default-precision environment where the dp8->dp4 curve is bitwise;
    this suite forces jax_default_matmul_precision=highest, which
    reorders the reduce)."""
    data = _batches(5, seed=3)
    ref = _trainer(4, seed=1)
    ref_losses = [float(ref.train_step(x, y)) for x, y in data]

    tr = _trainer(4, seed=1)
    for x, y in data[:3]:
        tr.train_step(x, y)
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(tr)

    tr2 = _trainer(2, seed=8)
    mgr2 = CheckpointManager(str(tmp_path))
    assert mgr2.restore_latest(tr2) is not None
    assert tr2._step_count == 3
    info = tr2._last_restore_info
    assert info["resharded"] and info["saved_mesh_axes"] == {"dp": 4} \
        and info["mesh_axes"] == {"dp": 2}
    assert mgr2.stats["reshard_restores"] == 1
    assert tr2.stats["reshard_restores"] == 1
    res = [float(tr2.train_step(x, y)) for x, y in data[3:]]
    np.testing.assert_allclose(res, ref_losses[3:], rtol=1e-6)


def test_grow_restore_dp2_to_dp4(tmp_path):
    """Elastic GROW: the mesh got its chips back."""
    data = _batches(4, seed=5)
    ref = _trainer(4, seed=2)
    ref_losses = [float(ref.train_step(x, y)) for x, y in data]
    tr = _trainer(2, seed=2)
    for x, y in data[:2]:
        tr.train_step(x, y)
    p = str(tmp_path / "ck")
    tr.save(p, manifest=True)
    tr2 = _trainer(4, seed=6)
    tr2.load(p)
    assert tr2._last_restore_info["resharded"]
    res = [float(tr2.train_step(x, y)) for x, y in data[2:]]
    np.testing.assert_allclose(res, ref_losses[2:], rtol=1e-6)


def test_zero3_stage3_repartition_on_shrink(tmp_path):
    """ZeRO-3: params/optimizer state live dp-SHARDED; a shrink restore
    must repartition every shard set onto the new dp extent (the
    reduce/gather structure changes, so parity is rtol 1e-5, not
    bitwise)."""
    def build(dp):
        paddle.seed(3)
        m = nn.Sequential(nn.Linear(8, 8), nn.ReLU(), nn.Linear(8, 4))
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=m.parameters())
        st = DistributedStrategy()
        st.sharding = True
        st.sharding_configs = {"stage": 3}
        return SpmdTrainer(m, opt, lambda o, y: F.mse_loss(o, y),
                           mesh=_mesh(dp), strategy=st)

    data = _batches(5, seed=1, cols=8)
    ref = build(4)
    ref_losses = [float(ref.train_step(x, y)) for x, y in data]
    tr = build(4)
    for x, y in data[:3]:
        tr.train_step(x, y)
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(tr)
    tr2 = build(2)
    mgr2 = CheckpointManager(str(tmp_path))
    mgr2.restore_latest(tr2)
    assert tr2._last_restore_info["resharded"]
    res = [float(tr2.train_step(x, y)) for x, y in data[3:]]
    np.testing.assert_allclose(res, ref_losses[3:], rtol=1e-5)


@pytest.mark.slow  # tier-1 wall budget: heaviest in file
def test_pipeline_restore_pp4_to_pp2(tmp_path):
    """GPipeTrainer pp=4 -> pp=2: the stacked [L, ...] slabs re-split
    over the new pp extent (each rank's stage param group doubles),
    optimizer state riding along; parity rtol 1e-5."""
    from paddle_tpu.distributed.pipeline import GPipeTrainer
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    from paddle_tpu.models.gpt import gpt_pipeline_parts
    crit = GPTPretrainingCriterion()

    def build(pp):
        paddle.seed(5)
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                        num_heads=4, max_seq_len=16,
                        use_flash_attention=False,
                        tie_word_embeddings=False)
        model = GPTForCausalLM(cfg)
        pre, blocks, post = gpt_pipeline_parts(model)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=model.parameters())
        return GPipeTrainer(
            pre, blocks, post, opt, lambda o, l: crit(o, l),
            mesh=create_mesh({"pp": pp}, devices=jax.devices()[:pp]),
            num_microbatches=4)

    rng = np.random.RandomState(2)
    ids = [rng.randint(0, 64, (4, 16)).astype(np.int32)
           for _ in range(5)]
    labs = [np.roll(i, -1, 1).astype(np.int64) for i in ids]
    ref = build(4)
    ref_losses = [float(ref.train_step(i, l))
                  for i, l in zip(ids, labs)]
    tr = build(4)
    for i, l in zip(ids[:3], labs[:3]):
        tr.train_step(i, l)
    p = str(tmp_path / "ppck")
    tr.save(p, manifest=True)
    assert read_manifest(p)["mesh_axes"] == {"pp": 4}
    tr2 = build(2)
    tr2.load(p)
    assert tr2._last_restore_info["resharded"]
    assert tr2.stats["reshard_restores"] == 1
    res = [float(tr2.train_step(i, l))
           for i, l in zip(ids[3:], labs[3:])]
    np.testing.assert_allclose(res, ref_losses[3:], rtol=1e-5)


def test_tensor_parallel_reshard_tp_to_dp(tmp_path):
    """tp=2 -> dp=2: a tensor-parallel trainer's column/row-sharded
    params restore onto a pure-dp mesh (and the reverse path grows tp
    back) — the train-on-one-topology/serve-on-another direction."""
    from paddle_tpu.distributed import (ColumnParallelLinear,
                                        RowParallelLinear)

    def build(axes):
        paddle.seed(4)
        m = nn.Sequential(ColumnParallelLinear(8, 8),
                          nn.ReLU(),
                          RowParallelLinear(8, 4))
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=m.parameters())
        n = int(np.prod(list(axes.values())))
        return SpmdTrainer(m, opt, lambda o, y: F.mse_loss(o, y),
                           mesh=create_mesh(
                               axes, devices=jax.devices()[:n]))

    data = _batches(5, seed=7, cols=8)
    ref = build({"dp": 1, "tp": 2})
    ref_losses = [float(ref.train_step(x, y)) for x, y in data]
    tr = build({"dp": 1, "tp": 2})
    for x, y in data[:3]:
        tr.train_step(x, y)
    p = str(tmp_path / "tpck")
    tr.save(p, manifest=True)
    tr2 = build({"dp": 2, "tp": 1})
    tr2.load(p)
    assert tr2._last_restore_info["resharded"]
    res = [float(tr2.train_step(x, y)) for x, y in data[3:]]
    np.testing.assert_allclose(res, ref_losses[3:], rtol=1e-5)


def test_resume_elastic_false_rejects_cross_topology(tmp_path):
    tr = _trainer(4)
    tr.train_step(*_batches(1)[0])
    p = str(tmp_path / "ck")
    tr.save(p, manifest=True)
    strict = _trainer(2, resume_elastic=False)
    assert strict.stats["resume_elastic"] is False
    with pytest.raises(ValueError, match="resume_elastic"):
        strict.load(p)
    # same topology stays fine under strict mode
    strict4 = _trainer(4, seed=9, resume_elastic=False)
    strict4.load(p)
    assert strict4._step_count == 1


# ---------------------------------------------------------------------------
# restore-fallback ordering (satellite)
# ---------------------------------------------------------------------------
def test_restore_fallback_ordering_prefers_newest_loadable(tmp_path):
    """Newest ckpt corrupt, middle from a DIFFERENT topology, oldest
    same-topology: restore must land on the middle one (newest
    loadable) and reshard it — never fall through to the older
    same-topology candidate."""
    d = str(tmp_path)
    mgr = CheckpointManager(d, keep_last=5, async_save=False)
    data = _batches(3, seed=11)
    # oldest: written on dp=2 (the topology we restore on)
    t2 = _trainer(2, seed=1)
    t2.train_step(*data[0])
    mgr.save(t2, step=1)
    # middle: written on dp=4 — different topology
    t4 = _trainer(4, seed=1)
    for x, y in data[:2]:
        t4.train_step(x, y)
    mgr.save(t4, step=2)
    # newest: corrupt (truncated payload)
    t4.train_step(*data[2])
    mgr.save(t4, step=3)
    entry = os.path.join(d, "ckpt-3", "state.pdtrainer")
    with open(entry, "r+b") as f:
        f.truncate(16)

    live = _trainer(2, seed=5)
    mgr2 = CheckpointManager(d)
    assert mgr2.restore_latest(live) is not None
    assert live._step_count == 2          # the middle candidate
    assert mgr2.stats["fallbacks"] == 1
    assert mgr2.stats["reshard_restores"] == 1
    assert live._last_restore_info["saved_mesh_axes"] == {"dp": 4}
    # and its params match what the dp=4 writer committed at step 2
    step2 = read_checkpoint(os.path.join(d, "ckpt-2"))
    for n in live.params:
        np.testing.assert_array_equal(np.asarray(live.params[n]),
                                      step2["params"][n])


# ---------------------------------------------------------------------------
# new fault knobs
# ---------------------------------------------------------------------------
def test_mesh_shrink_fault_clamps_devices(monkeypatch):
    monkeypatch.setenv("PADDLE_FAULT_MESH_SHRINK", "4")
    m = create_mesh({"dp": -1})
    assert m.shape["dp"] == 4
    monkeypatch.delenv("PADDLE_FAULT_MESH_SHRINK")
    assert create_mesh({"dp": -1}).shape["dp"] == len(jax.devices())


def test_fs_delay_jitter(monkeypatch, tmp_path):
    from paddle_tpu.framework.fs import open_for_write
    monkeypatch.setenv("PADDLE_FAULT_FS_DELAY_MS", "open_write:120")
    t0 = time.perf_counter()
    with open_for_write(str(tmp_path / "slow.bin")) as f:
        f.write(b"x")
    assert time.perf_counter() - t0 >= 0.1
    # non-matching ops are not delayed
    monkeypatch.setenv("PADDLE_FAULT_FS_DELAY_MS", "put:5000")
    t0 = time.perf_counter()
    with open_for_write(str(tmp_path / "fast.bin")) as f:
        f.write(b"x")
    assert time.perf_counter() - t0 < 2.0


def test_ckpt_truncate_counter_arms_nth(monkeypatch):
    monkeypatch.setenv("PADDLE_FAULT_CKPT_TRUNCATE", "2")
    assert faults.ckpt_truncate_commit() is False   # 1st commit
    assert faults.ckpt_truncate_commit() is True    # 2nd: armed
    assert faults.ckpt_truncate_commit() is False   # 3rd


# ---------------------------------------------------------------------------
# CheckpointManager: commit-failure surfacing (satellite)
# ---------------------------------------------------------------------------
def test_manager_on_error_callback_and_counter(tmp_path, monkeypatch):
    import paddle_tpu.distributed.resilience as rmod
    tr = _trainer(1)
    tr.train_step(*_batches(1)[0])
    monkeypatch.setattr(rmod, "write_checkpoint",
                        lambda state, path: (_ for _ in ()).throw(
                            IOError("dead dir")))
    seen = []
    mgr = CheckpointManager(str(tmp_path), async_save=True,
                            on_error=seen.append)
    mgr.save(tr)
    mgr.wait()                       # routed to the callback, no raise
    assert len(seen) == 1 and "dead dir" in str(seen[0])
    assert mgr.stats["commit_failures"] == 1
    # without a callback the NEXT save() call re-raises
    mgr2 = CheckpointManager(str(tmp_path), async_save=True)
    mgr2.save(tr)
    with pytest.raises(IOError, match="dead dir"):
        mgr2.save(tr)
    assert mgr2.stats["commit_failures"] == 1


def test_manager_wait_timeout(tmp_path, monkeypatch):
    import threading

    import paddle_tpu.distributed.resilience as rmod
    tr = _trainer(1)
    tr.train_step(*_batches(1)[0])
    gate = threading.Event()
    real = rmod.write_checkpoint

    def gated(state, path):
        gate.wait(30)
        return real(state, path)

    monkeypatch.setattr(rmod, "write_checkpoint", gated)
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    p = mgr.save(tr)
    with pytest.raises(TimeoutError, match="still running"):
        mgr.wait(timeout=0.1)
    # every untimed join against the known-stuck commit refuses fast
    # instead of hanging forever — save() included (restore_latest and
    # latest() go through the same wait())
    with pytest.raises(TimeoutError, match="still stuck"):
        mgr.save(tr)
    with pytest.raises(TimeoutError, match="still stuck"):
        mgr.wait()
    gate.set()
    mgr.wait(timeout=30)       # storage recovered: a TIMED join clears
    assert validate_checkpoint(p)
    mgr.save(tr)                               # and saves work again
    mgr.wait()


# ---------------------------------------------------------------------------
# kill-and-resume onto a SHRUNK mesh (subprocess, end to end)
# ---------------------------------------------------------------------------
_ELASTIC_TRAIN = """
import sys
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import (SpmdTrainer, create_mesh,
                                    CheckpointManager, PreemptionGuard)

ckdir, mode = sys.argv[1], sys.argv[2]
N = 6


def build():
    paddle.seed(7)
    m = nn.Linear(6, 3)
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=m.parameters())
    return SpmdTrainer(m, opt, lambda o, y: F.mse_loss(o, y),
                       mesh=create_mesh({"dp": -1}))


rng = np.random.RandomState(0)
data = [(rng.randn(8, 6).astype(np.float32),
         rng.randn(8, 3).astype(np.float32)) for _ in range(N)]
tr = build()
print("DP", tr.dp_size, flush=True)
mgr = CheckpointManager(ckdir, keep_last=2)
mgr.restore_latest(tr)
start = tr._step_count
if mode == "resume_shrunk":
    assert start > 0, "resume did not find a checkpoint"
    assert tr._last_restore_info["resharded"], tr._last_restore_info
    assert mgr.stats["reshard_restores"] == 1
losses = []
with PreemptionGuard() as g:
    for i in range(start, N):
        losses.append(float(tr.train_step(*data[i])))
        if g.preempted:
            mgr.save(tr, block=True)
            print("PREEMPTED", tr._step_count, flush=True)
            sys.exit(0)
mgr.wait()
for l in losses:
    print("LOSS", repr(l), flush=True)
print("DONE", tr._step_count, flush=True)
"""


def _run_elastic_child(script, ckdir, mode, extra_env, timeout=240):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    kept = [f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        kept + ["--xla_force_host_platform_device_count=8"])
    for k in ("PADDLE_FAULT_SIGTERM_STEP", "PADDLE_FAULT_MESH_SHRINK",
              "PADDLE_FAULT_NAN_STEP", "PADDLE_FAULT_CKPT_TRUNCATE"):
        env.pop(k, None)
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, str(script), ckdir, mode],
        env=env, capture_output=True, text=True, timeout=timeout)


def _losses_from(stdout):
    return [float(line.split(" ", 1)[1])
            for line in stdout.splitlines() if line.startswith("LOSS")]


@pytest.mark.slow  # tier-1 wall budget: heaviest in file
def test_subprocess_dp8_kill_resumes_on_dp4(tmp_path):
    """The acceptance run: a dp=8 trainer is SIGTERM-killed mid-run by
    the fault harness, drains + checkpoints, and a second process that
    WAKES UP WITH 4 DEVICES (PADDLE_FAULT_MESH_SHRINK) resumes from the
    same directory — the combined loss curve matches an uninterrupted
    dp=8 run to the last ulps (the dp-reduce tree is the only thing
    that changed; the state itself round-trips bitwise)."""
    script = tmp_path / "train.py"
    script.write_text(_ELASTIC_TRAIN)
    ckdir = str(tmp_path / "ck")

    p_ref = _run_elastic_child(script, str(tmp_path / "ref"), "ref", {})
    assert p_ref.returncode == 0, p_ref.stderr
    ref = _losses_from(p_ref.stdout)
    assert len(ref) == 6 and "DP 8" in p_ref.stdout

    p1 = _run_elastic_child(script, ckdir, "train",
                            {"PADDLE_FAULT_SIGTERM_STEP": "3"})
    assert p1.returncode == 0, p1.stderr
    assert "PREEMPTED 3" in p1.stdout
    ck = latest_checkpoint(ckdir)
    assert ck is not None and validate_checkpoint(ck)
    assert read_manifest(ck)["mesh_axes"] == {"dp": 8}

    p2 = _run_elastic_child(script, ckdir, "resume_shrunk",
                            {"PADDLE_FAULT_MESH_SHRINK": "4"})
    assert p2.returncode == 0, p2.stderr
    assert "DP 4" in p2.stdout and "DONE 6" in p2.stdout
    np.testing.assert_allclose(_losses_from(p2.stdout), ref[3:],
                               rtol=1e-6)


def test_subprocess_ckpt_truncate_falls_back(tmp_path):
    """PADDLE_FAULT_CKPT_TRUNCATE: the 2nd commit dies mid-write
    leaving a committed-LOOKING dir whose shard is cut; the resumed
    process must fall back to the older valid checkpoint and finish
    with the uninterrupted curve's tail."""
    script = tmp_path / "train.py"
    script.write_text(_ELASTIC_TRAIN)
    ckdir = str(tmp_path / "ck")

    p_ref = _run_elastic_child(script, str(tmp_path / "ref"), "ref", {})
    assert p_ref.returncode == 0, p_ref.stderr
    ref = _losses_from(p_ref.stdout)

    # run 1: checkpoint at step 2 (clean), die inside the step-4 commit
    p1 = _run_elastic_child(
        script, ckdir, "train",
        {"PADDLE_FAULT_SIGTERM_STEP": "2"})
    assert p1.returncode == 0 and "PREEMPTED 2" in p1.stdout, p1.stderr
    p2 = _run_elastic_child(
        script, ckdir, "train",
        {"PADDLE_FAULT_SIGTERM_STEP": "4",
         "PADDLE_FAULT_CKPT_TRUNCATE": "1"})
    assert p2.returncode == 137, (p2.returncode, p2.stderr)
    # the partial shard is at its FINAL name but fails validation...
    names = sorted(n for n in os.listdir(ckdir) if n.startswith("ckpt-")
                   and not n.endswith(".tmp"))
    assert "ckpt-4" in names
    assert not validate_checkpoint(os.path.join(ckdir, "ckpt-4"))
    # ...so resume lands on ckpt-2 and re-trains 3..6 to the same curve
    p3 = _run_elastic_child(script, ckdir, "train", {})
    assert p3.returncode == 0, p3.stderr
    assert "DONE 6" in p3.stdout
    assert _losses_from(p3.stdout) == ref[2:]


_ELASTIC_PIPE = """
import sys
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.distributed import create_mesh, CheckpointManager
from paddle_tpu.distributed.resilience import PreemptionGuard
from paddle_tpu.distributed.pipeline import GPipeTrainer
from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                               GPTPretrainingCriterion)
from paddle_tpu.models.gpt import gpt_pipeline_parts
import jax

ckdir, mode = sys.argv[1], sys.argv[2]
N = 5
crit = GPTPretrainingCriterion()


def build():
    paddle.seed(5)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                    num_heads=4, max_seq_len=16,
                    use_flash_attention=False,
                    tie_word_embeddings=False)
    model = GPTForCausalLM(cfg)
    pre, blocks, post = gpt_pipeline_parts(model)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters())
    # surviving device count (PADDLE_FAULT_MESH_SHRINK clamps it),
    # capped at 4: dp=2/pp=2 healthy, dp=1/pp=2 after the shrink to 2
    from paddle_tpu.testing import faults
    n = min(faults.mesh_shrink() or len(jax.devices()), 4)
    pp = 2
    dp = max(n // pp, 1)
    mesh = create_mesh({"dp": dp, "pp": pp},
                       devices=jax.devices()[:dp * pp])
    return GPipeTrainer(pre, blocks, post, opt,
                        lambda o, l: crit(o, l), mesh=mesh,
                        num_microbatches=4)


rng = np.random.RandomState(2)
# 8 rows / 4 microbatches -> microbatch of 2, divisible by dp in {1, 2}
ids = [rng.randint(0, 64, (8, 16)).astype(np.int32) for _ in range(N)]
labs = [np.roll(i, -1, 1).astype(np.int64) for i in ids]
tr = build()
print("MESH", dict(tr.mesh.shape), flush=True)
mgr = CheckpointManager(ckdir, keep_last=2)
mgr.restore_latest(tr)
start = tr._step_count
if mode == "resume_shrunk":
    assert start > 0, "no checkpoint found"
    assert tr._last_restore_info["resharded"], tr._last_restore_info
losses = []
with PreemptionGuard() as g:
    for i in range(start, N):
        losses.append(float(tr.train_step(ids[i], labs[i])))
        if g.preempted:
            mgr.save(tr, block=True)
            print("PREEMPTED", tr._step_count, flush=True)
            sys.exit(0)
mgr.wait()
for l in losses:
    print("LOSS", repr(l), flush=True)
print("DONE", tr._step_count, flush=True)
"""


@pytest.mark.slow
def test_subprocess_dp2pp2_kill_resumes_on_pp2(tmp_path):
    """The tp/pp acceptance leg: a dp=2/pp=2 pipeline run killed by the
    fault harness resumes on a 4-device mesh (dp=1/pp=2) with rtol-1e-5
    loss parity against the uninterrupted run."""
    script = tmp_path / "train.py"
    script.write_text(_ELASTIC_PIPE)
    ckdir = str(tmp_path / "ck")

    p_ref = _run_elastic_child(script, str(tmp_path / "ref"), "ref", {},
                               timeout=420)
    assert p_ref.returncode == 0, p_ref.stderr
    ref = _losses_from(p_ref.stdout)
    assert len(ref) == 5

    p1 = _run_elastic_child(script, ckdir, "train",
                            {"PADDLE_FAULT_SIGTERM_STEP": "3"},
                            timeout=420)
    assert p1.returncode == 0, p1.stderr
    assert "PREEMPTED 3" in p1.stdout

    p2 = _run_elastic_child(script, ckdir, "resume_shrunk",
                            {"PADDLE_FAULT_MESH_SHRINK": "2"},
                            timeout=420)
    assert p2.returncode == 0, p2.stderr
    assert "{'dp': 1, 'pp': 2}" in p2.stdout and "DONE 5" in p2.stdout
    np.testing.assert_allclose(_losses_from(p2.stdout), ref[3:],
                               rtol=1e-5)
