"""Latency-hiding collectives (virtual 8-device CPU mesh).

Covers the overlap pass end to end: the mesh.py collective shims, the
ZeRO-3 overlapped-gather scan (parity vs the synchronous GSPMD stage-3
placement), the 1F1B pipeline schedule (parity vs GPipe + the structural
peak-activation claim), chunked MoE all-to-all (bitwise parity), the
comm_ms/comm_fraction stats plumbing, and the PADDLE_TPU_OVERLAP knob.

Fixture discipline: meshes and batches are module-scoped (tier-1 runs
~700-780s of its 870s budget — every shared compile matters); the
longer multi-step soaks are marked `slow`.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.distributed import SpmdTrainer, create_mesh
from paddle_tpu.distributed import overlap as overlap_mod
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.distributed.mesh import PartitionSpec as P, shard_map
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.distributed.pipeline import GPipeTrainer
from paddle_tpu.utils import comm_stats, compile_counter


# ---------------------------------------------------------------------------
# module-scoped fixtures (one mesh / batch set for the whole module)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def dp8_mesh():
    return create_mesh({"dp": 8})


@pytest.fixture(scope="module")
def ep8_mesh():
    return create_mesh({"ep": 8})


@pytest.fixture(scope="module")
def pp2_mesh():
    return create_mesh({"pp": 2})


@pytest.fixture(scope="module")
def gpt_batch():
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 64, (8, 16)).astype(np.int32)
    return ids, np.roll(ids, -1, 1).astype(np.int64)


# ---------------------------------------------------------------------------
# mesh.py collective shims
# ---------------------------------------------------------------------------
def test_mesh_collective_helpers(dp8_mesh):
    """all_gather/reduce_scatter/ppermute shims: gather ∘ scatter over a
    ring behaves like the identities they claim."""
    x = jnp.arange(64.0).reshape(8, 8)

    def body(xs):
        full = mesh_mod.all_gather(xs, "dp", axis=0)          # [8, 8]
        rs = mesh_mod.reduce_scatter(full, "dp", axis=0)      # [1, 8]
        rolled = mesh_mod.ppermute(
            xs, "dp", [(i, (i + 1) % 8) for i in range(8)])
        return full, rs, rolled

    full, rs, rolled = jax.jit(shard_map(
        body, mesh=dp8_mesh, in_specs=P("dp"),
        out_specs=(P(), P("dp"), P("dp")), check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(full), np.asarray(x))
    # reduce_scatter of a replicated value = 8x each rank's slice
    np.testing.assert_allclose(np.asarray(rs), np.asarray(x) * 8)
    np.testing.assert_allclose(np.asarray(rolled),
                               np.roll(np.asarray(x), 1, axis=0))


def test_collective_all_to_all_list_api_in_trace(ep8_mesh):
    """The reference list-API all_to_all now works inside shard_map (the
    path chunked MoE dispatch needed): 8 slices exchanged = the global
    block transpose."""
    from paddle_tpu.distributed import collective

    def body(x):
        outs = []
        collective.all_to_all(outs, [Tensor(x[i]) for i in range(8)],
                              axis_name="ep")
        return jnp.stack([o.data if isinstance(o, Tensor) else o
                          for o in outs])

    sm = jax.jit(shard_map(body, mesh=ep8_mesh, in_specs=P("ep"),
                           out_specs=P("ep")))
    got = np.asarray(sm(jnp.arange(64.0)))
    np.testing.assert_allclose(got,
                               np.arange(64.0).reshape(8, 8).T.ravel())


# ---------------------------------------------------------------------------
# comm-stats plumbing
# ---------------------------------------------------------------------------
def test_comm_stats_parser_counts_and_bytes():
    hlo = """
  %all-gather.3 = f32[4,16]{1,0} all-gather(f32[1,16]{1,0} %p), dims={0}
  %all-reduce = bf16[8]{0} all-reduce(bf16[8]{0} %x), to_apply=%add
  %rs = f32[2,4]{1,0} reduce-scatter(f32[16,4]{1,0} %y), dims={0}
  %a2a = (f32[1,8]{1,0}, f32[1,8]{1,0}, /*index=2*/f32[1,8]{1,0}) all-to-all(%a, %b, %c)
  %ags = (f32[1,16]{1,0}, f32[4,16]{1,0}) all-gather-start(f32[1,16]{1,0} %p)
  %agd = f32[4,16]{1,0} all-gather-done((f32[1,16]{1,0}, f32[4,16]{1,0}) %ags)
  %cp-start = f32[4]{0} collective-permute-start(f32[4]{0} %z)
  %cp-done = f32[4]{0} collective-permute-done(f32[4]{0} %cp-start)
  %cps2 = (f32[8]{0}, f32[8]{0}, u32[]{:T(128)}, u32[]{:T(128)}) collective-permute-start(f32[8]{0} %w)
  %rss = (f32[64,4]{1,0}, f32[8,4]{1,0}) reduce-scatter-start(f32[64,4]{1,0} %v)
"""
    out = comm_stats.parse_hlo_collectives(hlo)
    # sync all-gather 256B + async -start (operand, result) tuple: only
    # the result half (256B) is wire traffic; the -done is bookkeeping
    assert out["by_op"]["all-gather"] == {"count": 2,
                                          "bytes": 4 * 16 * 4 * 2}
    assert out["by_op"]["all-reduce"] == {"count": 1, "bytes": 8 * 2}
    # sync form sums its shape; the async -start (operand, result)
    # tuple takes the SMALLEST data buffer — reduce-scatter's result is
    # operand/groupsize, which a relative filter would misread as a
    # context token at large group sizes
    assert out["by_op"]["reduce-scatter"] == {"count": 2,
                                              "bytes": 2 * 4 * 4
                                              + 8 * 4 * 4}
    # variadic sync all-to-all: every tuple element is a result
    assert out["by_op"]["all-to-all"] == {"count": 1, "bytes": 3 * 8 * 4}
    # -start counted once, -done not double counted; the TPU 4-tuple
    # form (op, result, ctx, ctx — nested-paren layout annotations)
    # counts the result buffer, not the u32 sync contexts
    assert out["by_op"]["collective-permute"] == {"count": 2,
                                                  "bytes": 16 + 32}
    assert out["count"] == 8
    est = comm_stats.estimate_comm_ms(out["bytes"])
    assert est > 0


def test_comm_stats_parser_scales_while_bodies():
    """A collective inside a scan/while body executes once per trip —
    the ZeRO-3 layer scan and the 1F1B tick scan would otherwise
    underreport comm by the trip count."""
    hlo = """
%region_0.9_spmd (p: (s32[], f32[2,4])) -> (s32[], f32[2,4]) {
  %ag.1 = f32[16,4]{1,0} all-gather(f32[2,4]{1,0} %x), dims={0}
}
%region_1.9_spmd (p: (s32[], f32[2,4])) -> pred[] {
  %c.4 = s32[] constant(6)
  ROOT %cmp = pred[] compare(s32[] %i, s32[] %c.4), direction=LT
}
ENTRY %main (a: f32[2,4]) -> f32[2,4] {
  %ag.0 = f32[16,4]{1,0} all-gather(f32[2,4]{1,0} %a), dims={0}
  %w = (s32[], f32[2,4]) while((s32[], f32[2,4]) %t), condition=%region_1.9_spmd, body=%region_0.9_spmd
}
"""
    out = comm_stats.parse_hlo_collectives(hlo)
    # 1 top-level + 6 trips x 1 in-body
    assert out["by_op"]["all-gather"]["count"] == 7, out
    assert out["by_op"]["all-gather"]["bytes"] == 7 * 16 * 4 * 4, out


# ---------------------------------------------------------------------------
# ZeRO-3 overlapped all-gather
# ---------------------------------------------------------------------------
def _zero3_trainer(overlap, dp8_mesh, seed=7, comm=False):
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=16,
                    use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters())
    crit = GPTPretrainingCriterion()
    st = DistributedStrategy()
    st.sharding = True
    st.sharding_configs = {"stage": 3, "overlap": overlap}
    st.recompute_configs = {"scan_layers": True}
    # comm analysis AOT-compiles the step a second time — only the
    # trainer whose HLO the test asserts on pays for it (time budget)
    return SpmdTrainer(model, opt, lambda o, l: crit(o, l),
                       mesh=dp8_mesh, strategy=st, comm_stats=comm)


@pytest.mark.slow  # tier-1 wall budget: heaviest in file
def test_zero3_overlap_matches_sync_and_recompile_free(dp8_mesh,
                                                       gpt_batch):
    """The tentpole contract: overlapped ZeRO-3 losses == synchronous
    GSPMD stage-3 (rtol 1e-5 fp32), zero XLA compiles across steps 2..N,
    grads leave the backward as reduce-scatter, and comm_ms /
    comm_fraction are reported."""
    ids, labels = gpt_batch
    steps = 3

    def run(overlap, comm):
        tr = _zero3_trainer(overlap, dp8_mesh, comm=comm)
        assert tr.zero3_overlap == overlap
        losses = [float(tr.train_step(ids, labels))]
        snap = compile_counter.snapshot()
        for _ in range(steps - 1):
            losses.append(float(tr.train_step(ids, labels)))
        return tr, losses, snap.new_compiles, tr.stats

    _, loss_sync, _, _ = run(False, comm=False)
    tr, loss_ovl, compiles, stats = run(True, comm=True)
    np.testing.assert_allclose(loss_ovl, loss_sync, rtol=1e-5)
    assert compiles == 0
    # structural: explicit gathers + reduce-scattered grads in the HLO
    by_op = stats["comm_by_op"]
    assert by_op.get("all-gather", {}).get("count", 0) > 0
    assert by_op.get("reduce-scatter", {}).get("count", 0) > 0
    assert stats["comm_ms"] is not None
    assert stats["comm_fraction"] is not None
    assert stats["comm_bytes"] > 0
    # ZeRO-3 memory: block params live dp-sharded (1/dp per device)
    w = tr.params["gpt.blocks.0.mlp.up_proj.weight"]
    assert "dp" in str(w.sharding.spec)
    assert w.addressable_shards[0].data.size == w.size // 8


def test_zero3_scan_with_flash_kernel_inside_the_manual_region():
    """The chip's default recipe (flash + scan over layers) under
    overlapped ZeRO-3: the flash kernel is traced INSIDE zero3's
    shard_map body, where every mesh axis is already Manual, so its own
    mesh partition must stand down instead of nesting a second
    shard_map over the same mesh (PR 21 review: "The context mesh ...
    should match the mesh passed to shard_map" at trace).  Kernel
    interpreted; losses equal the composite's."""
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    from paddle_tpu.ops import kernel_paths, set_interpret_mode
    mesh = create_mesh({"dp": 4}, devices=jax.devices()[:4])
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 128, (8, 128)).astype(np.int32)
    labels = np.roll(ids, -1, 1).astype(np.int64)

    def run(flash):
        paddle.seed(11)
        cfg = GPTConfig(vocab_size=128, hidden_size=128, num_layers=2,
                        num_heads=2, max_seq_len=128,
                        use_flash_attention=flash)
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=model.parameters())
        crit = GPTPretrainingCriterion()
        st = DistributedStrategy()
        st.sharding = True
        st.sharding_configs = {"stage": 3, "overlap": True}
        st.recompute = True
        st.recompute_configs = {"scan_layers": True,
                                "policy": "dots_no_batch"}
        model.enable_recompute("dots_no_batch")
        tr = SpmdTrainer(model, opt, lambda o, l: crit(o, l),
                         mesh=mesh, strategy=st)
        assert tr.zero3_overlap
        return [float(tr.train_step(ids, labels)) for _ in range(2)]

    set_interpret_mode(True)
    kernel_paths.reset()
    try:
        with_kernel = run(True)
        assert kernel_paths.counts()["flash_attention"]["kernel"] >= 1
    finally:
        set_interpret_mode(False)
    np.testing.assert_allclose(with_kernel, run(False), rtol=1e-5)
    assert with_kernel[1] < with_kernel[0]


# ---------------------------------------------------------------------------
# 1F1B pipeline schedule
# ---------------------------------------------------------------------------
class _Block(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(16, 16)

    def forward(self, x):
        return F.relu(self.fc(x))


def _pipe(schedule, mesh, num_micro, seed=0, n_blocks=2, comm=False):
    paddle.seed(seed)
    pre = nn.Linear(8, 16)
    blocks = [_Block() for _ in range(n_blocks)]
    post = nn.Linear(16, 10)
    params = (list(pre.parameters())
              + [p for b in blocks for p in b.parameters()]
              + list(post.parameters()))
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=params)
    return GPipeTrainer(pre, blocks, post, opt,
                        lambda o, l: F.cross_entropy(o, l), mesh=mesh,
                        num_microbatches=num_micro, remat=False,
                        schedule=schedule, comm_stats=comm)


@pytest.mark.slow  # tier-1 wall budget: heaviest in file
def test_1f1b_matches_gpipe_and_recompile_free(pp2_mesh):
    """1F1B loss parity vs GPipe at pp=2, M=8 (the acceptance config),
    zero recompiles across steps 2..N, and comm fields reported."""
    rng = np.random.RandomState(1)
    batches = [(rng.randn(16, 8).astype(np.float32),
                rng.randint(0, 10, (16,)).astype(np.int64))
               for _ in range(3)]

    def run(schedule, comm=False):
        tr = _pipe(schedule, pp2_mesh, num_micro=8, comm=comm)
        losses = [float(tr.train_step(*batches[0]))]
        snap = compile_counter.snapshot()
        for x, y in batches[1:]:
            losses.append(float(tr.train_step(x, y)))
        return tr, losses, snap.new_compiles

    tr_g, loss_g, _ = run("gpipe")
    tr_o, loss_o, compiles = run("1f1b", comm=True)
    np.testing.assert_allclose(loss_o, loss_g, rtol=1e-5, atol=1e-7)
    assert compiles == 0
    # the structural memory claim: the 1F1B stage-input stash allocates
    # min(2*pp-1, M) microbatch slots — 3 here — vs GPipe's M=8 banked
    # outputs (peak live activation count <= GPipe's)
    assert tr_o.peak_activation_slots() == 3
    assert tr_g.peak_activation_slots() == 8
    assert tr_o.peak_activation_slots() <= tr_g.peak_activation_slots()
    st = tr_o.stats
    assert st["schedule"] == "1f1b"
    assert st["comm_ms"] is not None and st["comm_fraction"] is not None


def test_1f1b_schedule_validation(pp2_mesh):
    with pytest.raises(ValueError):
        _pipe("zigzag", pp2_mesh, num_micro=2)


def test_microbatch_remainder_raises(pp2_mesh):
    """Satellite: a batch not divisible by num_microbatches must raise a
    clear error (never silently truncate)."""
    tr = _pipe("gpipe", pp2_mesh, num_micro=4)
    x = np.random.RandomState(0).randn(10, 8).astype(np.float32)
    y = np.zeros((10,), np.int64)
    with pytest.raises(ValueError, match="num_microbatches"):
        tr.train_step(x, y)


# ---------------------------------------------------------------------------
# chunked MoE all-to-all
# ---------------------------------------------------------------------------
def test_moe_chunked_a2a_bitwise_equal(ep8_mesh):
    """K-chunked dispatch/combine is bitwise-equal to the monolithic
    exchange and issues K times the all-to-alls."""
    from paddle_tpu.distributed.moe import MoELayer
    paddle.seed(0)
    layer = MoELayer(8, 16, num_experts=8, top_k=2, capacity_factor=4.0)
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(8, 8, 8).astype(np.float32))
    args = (x, layer.gate.data, layer.experts.w_up.data,
            layer.experts.b_up.data, layer.experts.w_down.data,
            layer.experts.b_down.data)

    def make(k):
        def fn(xs, gate, wu, bu, wd, bd):
            layer.a2a_chunks = k      # bound at trace time
            y, _, _ = layer._fn_shard_map(xs, gate, wu, bu, wd, bd)
            return y
        return jax.jit(shard_map(
            fn, mesh=ep8_mesh,
            in_specs=(P("ep"), P(), P("ep"), P("ep"), P("ep"), P("ep")),
            out_specs=P("ep")))

    f1, f2 = make(1), make(2)
    c1 = comm_stats.analyze_jit(f1, *args)
    c2 = comm_stats.analyze_jit(f2, *args)
    np.testing.assert_array_equal(np.asarray(f2(*args)),
                                  np.asarray(f1(*args)))
    n1 = c1["by_op"]["all-to-all"]["count"]
    n2 = c2["by_op"]["all-to-all"]["count"]
    assert n1 >= 2 and n2 == 2 * n1
    # an explicit K on the GSPMD (non-shard_map) path is refused, not
    # silently ignored — that path's a2a is XLA-inserted
    layer.a2a_chunks = 2
    with pytest.raises(NotImplementedError, match="a2a_chunks"):
        layer(paddle.to_tensor(np.asarray(x)))


# ---------------------------------------------------------------------------
# the PADDLE_TPU_OVERLAP knob
# ---------------------------------------------------------------------------
def test_overlap_knob_defaults(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_OVERLAP", raising=False)
    monkeypatch.delenv("PADDLE_TPU_MOE_A2A_CHUNKS", raising=False)
    assert overlap_mod.overlap_enabled() is True
    assert overlap_mod.moe_a2a_chunks(8) == 2
    monkeypatch.setenv("PADDLE_TPU_PIPELINE_SCHEDULE", "1f1b")
    assert overlap_mod.pipeline_schedule_default() == "1f1b"
    monkeypatch.setenv("PADDLE_TPU_OVERLAP", "0")
    assert overlap_mod.overlap_enabled() is False
    assert overlap_mod.moe_a2a_chunks(8) == 1
    # the kill switch also downgrades the env-selected schedule AND an
    # env-selected chunk count: an A/B flip of the ONE knob must
    # actually change the compiled program
    assert overlap_mod.pipeline_schedule_default() == "gpipe"
    monkeypatch.setenv("PADDLE_TPU_MOE_A2A_CHUNKS", "4")
    assert overlap_mod.moe_a2a_chunks(8) == 1
    monkeypatch.delenv("PADDLE_TPU_MOE_A2A_CHUNKS", raising=False)
    monkeypatch.delenv("PADDLE_TPU_PIPELINE_SCHEDULE", raising=False)
    monkeypatch.setenv("PADDLE_TPU_OVERLAP", "1")
    monkeypatch.setenv("PADDLE_TPU_MOE_A2A_CHUNKS", "4")
    assert overlap_mod.moe_a2a_chunks(8) == 4
    # clamped to a divisor: 4 doesn't divide 6 -> 3
    assert overlap_mod.moe_a2a_chunks(6) == 3


def test_launcher_writes_no_accelerator_flags(monkeypatch):
    """A launched child's XLA_FLAGS are its parent's: nothing guesses
    the child's platform and appends accelerator options (an option the
    installed compiler does not know aborts the child at start-up)."""
    from paddle_tpu.distributed import launch
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setenv("PADDLE_TPU_OVERLAP", "1")
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
    env = launch._trainer_env(0, 2, ["127.0.0.1:1", "127.0.0.1:2"],
                              "127.0.0.1:3")
    assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=2"


# ---------------------------------------------------------------------------
# slow soaks
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_zero3_overlap_gpt_soak(dp8_mesh):
    """Longer ZeRO-3 parity soak: 4 layers + remat policy, 6 steps."""
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 128, (16, 32)).astype(np.int32)
    labels = np.roll(ids, -1, 1).astype(np.int64)

    def run(overlap):
        paddle.seed(11)
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=4,
                        num_heads=4, max_seq_len=32,
                        use_flash_attention=False)
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=model.parameters())
        crit = GPTPretrainingCriterion()
        st = DistributedStrategy()
        st.sharding = True
        st.sharding_configs = {"stage": 3, "overlap": overlap}
        st.recompute = True
        st.recompute_configs = {"scan_layers": True,
                                "policy": "dots_no_batch"}
        model.enable_recompute("dots_no_batch")
        tr = SpmdTrainer(model, opt, lambda o, l: crit(o, l),
                         mesh=dp8_mesh, strategy=st)
        return [float(tr.train_step(ids, labels)) for _ in range(6)]

    np.testing.assert_allclose(run(True), run(False), rtol=1e-5)


@pytest.mark.slow
def test_1f1b_gpt_moe_soak():
    """1F1B carries MoE router aux losses through its explicit backward:
    parity vs GPipe on a dp2 x pp2 GPT-MoE."""
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    from paddle_tpu.models.gpt import gpt_pipeline_parts
    crit = GPTPretrainingCriterion()
    mesh = create_mesh({"dp": 2, "pp": 2})
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 64, (8, 16)).astype(np.int32)
    labels = np.roll(ids, -1, 1).astype(np.int64)

    def run(schedule):
        paddle.seed(5)
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, max_seq_len=16,
                        use_flash_attention=False,
                        tie_word_embeddings=False, moe_num_experts=4,
                        moe_top_k=2, moe_capacity_factor=4.0,
                        moe_aux_loss_coeff=0.05)
        model = GPTForCausalLM(cfg)
        pre, blocks, post = gpt_pipeline_parts(model)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=model.parameters())
        tr = GPipeTrainer(pre, blocks, post, opt,
                          lambda o, l: crit(o, l), mesh=mesh,
                          num_microbatches=2, remat=True,
                          schedule=schedule)
        return [float(tr.train_step(ids, labels)) for _ in range(4)]

    np.testing.assert_allclose(run("1f1b"), run("gpipe"), rtol=1e-5)
