"""Shared multichip overlap-parity phases.

These back ``bench.py --multichip-smoke`` (a CPU-only child on eight
virtual devices), where "the overlapped schedule matches its
synchronous counterpart" is asserted at GPT size.  The
tier-1 tests (tests/test_overlap_collectives.py) assert the SAME
contract (parity at PARITY_RTOL, zero recompiles, comm fields) but on
deliberately smaller configs — the suite runs close to its time
budget, so they do not reuse these GPT-sized phases; keep the two in
step when the contract changes.

Each phase returns a JSON-able dict:
  {"name", "t_s", "loss_sync": [...], "loss_overlap": [...],
   "max_rel_diff", "comm_ms", "comm_fraction", "comm_by_op",
   "compiles_steps_2plus", ...}
and RAISES (AssertionError) when parity, the recompile-free contract, or
the comm-stats fields are violated — the callers decide whether that
kills a dryrun phase or fails a bench.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

__all__ = ["run_zero3_phase", "run_1f1b_phase", "run_moe_a2a_phase",
           "run_elastic_restore_phase", "run_dcn_phase",
           "run_serve_tp_phase", "run_serve_ep_phase", "PARITY_RTOL"]

# fp32 loss parity between a schedule and its synchronous counterpart
PARITY_RTOL = 1e-5


def _assert_comm_fields(stats: dict, who: str):
    for k in ("comm_ms", "comm_fraction", "comm_bytes",
              "comm_collectives"):
        assert stats.get(k) is not None, \
            f"{who}: stats[{k!r}] missing/None (comm breakdown not wired)"


def _parity(sync: List[float], overlap: List[float], who: str) -> float:
    np.testing.assert_allclose(overlap, sync, rtol=PARITY_RTOL,
                               err_msg=f"{who}: overlap schedule diverged "
                               f"from synchronous baseline")
    s, o = np.asarray(sync), np.asarray(overlap)
    return float(np.max(np.abs(o - s) / np.maximum(np.abs(s), 1e-12)))


def run_zero3_phase(steps: int = 3) -> Dict:
    """ZeRO-3 stage: GSPMD-placed gathers (overlap=False) vs the
    shard_map prefetched-gather scan (overlap=True)."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed import SpmdTrainer, create_mesh
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    from paddle_tpu.utils import compile_counter

    t0 = time.perf_counter()
    n = len(jax.devices())
    crit = GPTPretrainingCriterion()
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 128, (n, 32)).astype(np.int32)
    labels = np.roll(ids, -1, 1).astype(np.int64)

    def run(overlap):
        paddle.seed(7)
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=4,
                        num_heads=4, max_seq_len=32,
                        use_flash_attention=False)
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=model.parameters())
        st = DistributedStrategy()
        st.sharding = True
        st.sharding_configs = {"stage": 3, "overlap": overlap}
        st.recompute_configs = {"scan_layers": True}
        # comm analysis AOT-compiles the step a second time; only the
        # overlap run's stats are asserted on, so only it pays
        tr = SpmdTrainer(model, opt, lambda o, l: crit(o, l),
                         mesh=create_mesh({"dp": n}), strategy=st,
                         comm_stats=overlap)
        losses = [float(tr.train_step(ids, labels))]
        snap = compile_counter.snapshot()
        for _ in range(steps - 1):
            losses.append(float(tr.train_step(ids, labels)))
        return losses, snap.new_compiles, tr.stats

    loss_sync, _, _ = run(False)
    loss_ovl, compiles, stats = run(True)
    _assert_comm_fields(stats, "zero3")
    assert compiles == 0, \
        f"zero3 overlap: {compiles} XLA compiles in steps 2..{steps}"
    # the overlapped program must actually gather params and reduce-
    # scatter grads — that IS the ZeRO-3 schedule, assert it structurally
    by_op = stats["comm_by_op"] or {}
    assert by_op.get("all-gather", {}).get("count", 0) > 0, \
        f"zero3 overlap: no all-gather in step HLO ({by_op})"
    assert by_op.get("reduce-scatter", {}).get("count", 0) > 0, \
        f"zero3 overlap: no reduce-scatter in step HLO ({by_op})"
    return {
        "name": "zero3_overlap", "t_s": round(time.perf_counter() - t0, 1),
        "loss_sync": loss_sync, "loss_overlap": loss_ovl,
        "max_rel_diff": _parity(loss_sync, loss_ovl, "zero3"),
        "compiles_steps_2plus": compiles,
        "comm_ms": stats["comm_ms"],
        "comm_fraction": stats["comm_fraction"],
        "comm_by_op": {k: v["count"] for k, v in by_op.items()},
    }


def run_1f1b_phase(steps: int = 3, num_micro: int = 8) -> Dict:
    """Pipeline: GPipe fill/drain vs the 1F1B steady state at pp=2,
    including the structural peak-activation comparison."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed import create_mesh
    from paddle_tpu.distributed.pipeline import GPipeTrainer
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    from paddle_tpu.models.gpt import gpt_pipeline_parts
    from paddle_tpu.utils import compile_counter

    t0 = time.perf_counter()
    n = len(jax.devices())
    pp = 2 if n % 2 == 0 else 1
    dp = n // pp
    crit = GPTPretrainingCriterion()
    rng = np.random.RandomState(0)
    # microbatch rows must divide by dp (the shard_map batch spec)
    ids = rng.randint(0, 64, (num_micro * max(dp, 1), 16)) \
        .astype(np.int32)
    labels = np.roll(ids, -1, 1).astype(np.int64)

    def run(schedule):
        paddle.seed(1)
        cfg = GPTConfig(vocab_size=64, hidden_size=64, num_layers=4,
                        num_heads=4, max_seq_len=16,
                        use_flash_attention=False,
                        tie_word_embeddings=False)
        model = GPTForCausalLM(cfg)
        pre, blocks, post = gpt_pipeline_parts(model)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=model.parameters())
        tr = GPipeTrainer(pre, blocks, post, opt,
                          lambda o, l: crit(o, l),
                          mesh=create_mesh({"dp": dp, "pp": pp}),
                          num_microbatches=num_micro, remat=True,
                          schedule=schedule,
                          comm_stats=(schedule == "1f1b"))
        losses = [float(tr.train_step(ids, labels))]
        snap = compile_counter.snapshot()
        for _ in range(steps - 1):
            losses.append(float(tr.train_step(ids, labels)))
        return tr, losses, snap.new_compiles

    tr_g, loss_sync, _ = run("gpipe")
    tr_o, loss_ovl, compiles = run("1f1b")
    stats = tr_o.stats
    _assert_comm_fields(stats, "1f1b")
    assert compiles == 0, \
        f"1f1b: {compiles} XLA compiles in steps 2..{steps}"
    # the acceptance memory claim, asserted structurally: the 1F1B
    # stage-input stash holds at most O(pp) microbatches vs GPipe's M
    slots_o = tr_o.peak_activation_slots()
    slots_g = tr_g.peak_activation_slots()
    assert slots_o <= slots_g, (slots_o, slots_g)
    by_op = stats["comm_by_op"] or {}
    return {
        "name": "1f1b", "t_s": round(time.perf_counter() - t0, 1),
        "pp": pp, "num_micro": num_micro,
        "loss_sync": loss_sync, "loss_overlap": loss_ovl,
        "max_rel_diff": _parity(loss_sync, loss_ovl, "1f1b"),
        "compiles_steps_2plus": compiles,
        "peak_activation_slots": slots_o,
        "peak_activation_slots_gpipe": slots_g,
        "comm_ms": stats["comm_ms"],
        "comm_fraction": stats["comm_fraction"],
        "comm_by_op": {k: v["count"] for k, v in by_op.items()},
    }


def run_elastic_restore_phase(steps: int = 3,
                              extra_steps: int = 2) -> Dict:
    """Elastic shrink restore (ISSUE 10): train on the full dp mesh,
    checkpoint (manifest v2 with the topology record), restore onto
    HALF the devices, and keep training — the resumed loss curve must
    match the uninterrupted full-mesh run, and the restored trainer
    must not recompile after its first (expected, new-mesh) step."""
    import tempfile

    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed import (CheckpointManager, SpmdTrainer,
                                        create_mesh)
    from paddle_tpu.distributed.checkpoint import read_manifest
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    from paddle_tpu.utils import compile_counter

    t0 = time.perf_counter()
    n = len(jax.devices())
    shrink = max(n // 2, 1)
    crit = GPTPretrainingCriterion()
    rng = np.random.RandomState(4)
    total = steps + extra_steps
    batches = [rng.randint(0, 128, (n, 32)).astype(np.int32)
               for _ in range(total)]
    labels = [np.roll(b, -1, 1).astype(np.int64) for b in batches]

    def build(dp):
        paddle.seed(11)
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=4, max_seq_len=32,
                        use_flash_attention=False)
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=model.parameters())
        return SpmdTrainer(model, opt, lambda o, l: crit(o, l),
                           mesh=create_mesh(
                               {"dp": dp},
                               devices=jax.devices()[:dp]))

    # the uninterrupted reference on the full mesh
    ref = build(n)
    loss_ref = [float(ref.train_step(b, l))
                for b, l in zip(batches, labels)]

    # killed-and-resumed: train `steps`, checkpoint, restore on half
    ckdir = tempfile.mkdtemp(prefix="elastic_ck_")
    tr = build(n)
    loss_pre = [float(tr.train_step(b, l))
                for b, l in zip(batches[:steps], labels[:steps])]
    mgr = CheckpointManager(ckdir, async_save=False)
    path = mgr.save(tr)
    man = read_manifest(path)
    assert man and man.get("version", 1) >= 2 and \
        man.get("mesh_axes") == {"dp": n}, \
        f"manifest topology record missing: {man and man.keys()}"

    tr2 = build(shrink)
    mgr2 = CheckpointManager(ckdir)
    assert mgr2.restore_latest(tr2) is not None
    info = tr2._last_restore_info
    assert info and info["resharded"] and \
        info["mesh_axes"] == {"dp": shrink}, info
    loss_post = [float(tr2.train_step(batches[steps], labels[steps]))]
    snap = compile_counter.snapshot()     # step 1 on the new mesh paid
    for b, l in zip(batches[steps + 1:], labels[steps + 1:]):
        loss_post.append(float(tr2.train_step(b, l)))
    compiles = snap.new_compiles
    assert compiles == 0, \
        f"elastic restore: {compiles} XLA compiles after the first " \
        f"post-restore step"
    resumed = loss_pre + loss_post
    return {
        "name": "elastic_restore",
        "t_s": round(time.perf_counter() - t0, 1),
        "dp_from": n, "dp_to": shrink,
        "manifest_version": man.get("version"),
        "loss_sync": loss_ref, "loss_overlap": resumed,
        "max_rel_diff": _parity(loss_ref, resumed, "elastic_restore"),
        "reshard_restores": mgr2.stats["reshard_restores"],
        "compiles_steps_2plus": compiles,
    }


def run_dcn_phase(steps: int = 3, slices: int = 2) -> Dict:
    """Hierarchical data parallelism (ISSUE 17): flat dp over all
    devices vs a ('dcn', 'dp') mesh — dense all-reduce within a slice
    over ICI, only the cross-slice grad reduce over DCN.  Loss parity
    at PARITY_RTOL, zero recompiles in steps 2+, and the comm split
    must attribute bytes to BOTH tiers (that IS the hierarchy)."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed import SpmdTrainer, create_mesh
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    from paddle_tpu.utils import compile_counter

    t0 = time.perf_counter()
    n = len(jax.devices())
    if n % slices != 0 or n // slices < 2:
        slices = 2 if n % 2 == 0 and n >= 4 else 1
    crit = GPTPretrainingCriterion()
    rng = np.random.RandomState(17)
    ids = rng.randint(0, 128, (n * 2, 32)).astype(np.int32)
    labels = np.roll(ids, -1, 1).astype(np.int64)

    def run(hier):
        paddle.seed(9)
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=4,
                        num_heads=4, max_seq_len=32,
                        use_flash_attention=False)
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=model.parameters())
        st = DistributedStrategy()
        st.sharding = True
        # ZeRO shards optimizer state over dp WITHIN a slice, so the
        # hierarchical program carries guaranteed intra-slice (ICI)
        # gathers next to the cross-slice (DCN) grad reduce
        st.sharding_configs = {"stage": 3, "overlap": False}
        mesh = create_mesh({"dp": n // slices}, dcn_slices=slices) \
            if hier else create_mesh({"dp": n})
        tr = SpmdTrainer(model, opt, lambda o, l: crit(o, l),
                         mesh=mesh, strategy=st, comm_stats=hier)
        losses = [float(tr.train_step(ids, labels))]
        snap = compile_counter.snapshot()
        for _ in range(steps - 1):
            losses.append(float(tr.train_step(ids, labels)))
        return losses, snap.new_compiles, tr.stats

    loss_flat, _, _ = run(False)
    loss_hier, compiles, stats = run(True)
    _assert_comm_fields(stats, "dcn")
    assert compiles == 0, \
        f"dcn hierarchical: {compiles} XLA compiles in steps 2..{steps}"
    assert stats.get("dcn_slices") == slices, \
        f"dcn: expected {slices} slices in stats, {stats.get('dcn_slices')}"
    ici, dcn = stats.get("comm_bytes_ici"), stats.get("comm_bytes_dcn")
    if slices > 1:
        assert ici and ici > 0, f"dcn: no ICI bytes attributed ({ici})"
        assert dcn and dcn > 0, f"dcn: no DCN bytes attributed ({dcn})"
    by_op = stats["comm_by_op"] or {}
    return {
        "name": "dcn_hierarchical",
        "t_s": round(time.perf_counter() - t0, 1),
        "dcn_slices": slices, "dp_per_slice": n // max(slices, 1),
        "loss_sync": loss_flat, "loss_overlap": loss_hier,
        "max_rel_diff": _parity(loss_flat, loss_hier, "dcn"),
        "compiles_steps_2plus": compiles,
        "comm_ms": stats["comm_ms"],
        "comm_fraction": stats["comm_fraction"],
        "comm_bytes_ici": ici, "comm_bytes_dcn": dcn,
        "comm_by_op": {k: v["count"] for k, v in by_op.items()},
    }


def run_moe_a2a_phase(chunks: int = 2) -> Dict:
    """MoE dispatch/combine: monolithic all-to-all vs K-chunked —
    bitwise-equal outputs, and the chunked program must carry K times
    the collective count."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.distributed import create_mesh
    from paddle_tpu.distributed.mesh import PartitionSpec as P, shard_map
    from paddle_tpu.distributed.moe import MoELayer
    from paddle_tpu.utils import comm_stats as _cs

    t0 = time.perf_counter()
    n = len(jax.devices())
    H, Fd = 8, 16
    paddle.seed(3)
    layer = MoELayer(H, Fd, num_experts=n, top_k=2, capacity_factor=4.0)
    rng = np.random.RandomState(3)
    x = rng.randn(n, 8, H).astype(np.float32)
    mesh = create_mesh({"ep": n})
    args = (jnp.asarray(x), layer.gate.data, layer.experts.w_up.data,
            layer.experts.b_up.data, layer.experts.w_down.data,
            layer.experts.b_down.data)

    def make(k):
        def fn(xs, gate, wu, bu, wd, bd):
            # bind the chunk count at TRACE time (jit defers tracing, so
            # setting it outside would race between the two programs)
            layer.a2a_chunks = k
            y, aux, zl = layer._fn_shard_map(xs, gate, wu, bu, wd, bd)
            return y
        return jax.jit(shard_map(
            fn, mesh=mesh,
            in_specs=(P("ep"), P(), P("ep"), P("ep"), P("ep"), P("ep")),
            out_specs=P("ep")))

    f_mono, f_chunk = make(1), make(chunks)
    comm_mono = _cs.analyze_jit(f_mono, *args)
    comm_chunk = _cs.analyze_jit(f_chunk, *args)
    out_mono = np.asarray(f_mono(*args))
    out_chunk = np.asarray(f_chunk(*args))
    np.testing.assert_array_equal(
        out_chunk, out_mono,
        err_msg="chunked MoE a2a is not bitwise-equal to monolithic")
    # recompile-free contract (steps 2..N) + comm_fraction, same as the
    # other schedules: re-run the chunked program and time it
    from paddle_tpu.utils import compile_counter
    snap = compile_counter.snapshot()
    steps = 3
    t1 = time.perf_counter()
    for _ in range(steps):
        f_chunk(*args).block_until_ready()
    mean_ms = (time.perf_counter() - t1) * 1e3 / steps
    compiles = snap.new_compiles
    assert compiles == 0, \
        f"chunked MoE a2a: {compiles} XLA compiles in steps 2..N"
    a2a_mono = comm_mono["by_op"].get("all-to-all", {}).get("count", 0) \
        if comm_mono else 0
    a2a_chunk = comm_chunk["by_op"].get("all-to-all", {}).get("count", 0) \
        if comm_chunk else 0
    # XLA may decompose one lax.all_to_all into several HLO ops, so the
    # invariant is proportionality: K chunks issue K times the exchanges
    # of the monolithic program (dispatch + combine each)
    assert a2a_mono >= 2, f"monolithic MoE: expected >=2 a2a, {a2a_mono}"
    assert a2a_chunk == chunks * a2a_mono, \
        f"chunked MoE: expected {chunks}x{a2a_mono} a2a, {a2a_chunk}"
    comm_ms = comm_chunk["comm_ms"] if comm_chunk else None
    return {
        "name": "moe_a2a_chunked",
        "t_s": round(time.perf_counter() - t0, 1),
        "chunks": chunks, "a2a_count_mono": a2a_mono,
        "a2a_count_chunked": a2a_chunk,
        "comm_ms": comm_ms,
        "comm_fraction": round(comm_ms / mean_ms, 4)
        if (comm_ms is not None and mean_ms > 0) else None,
        "compiles_steps_2plus": compiles,
        "max_abs_diff": 0.0,
    }


def run_serve_tp_phase(gen_tokens: int = 8) -> Dict:
    """Pod-scale serving (ISSUE 18): a tp=2 serving mesh must generate
    TOKEN-IDENTICAL output to the unsharded engine on BOTH KV layouts,
    the decode loop must stay recompile-free after warmup with sharded
    weights/cache, and the executable observatory entries must record
    the submesh + tp degree they compiled against."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import create_mesh
    from paddle_tpu.inference import InferenceEngine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability import exec_registry
    from paddle_tpu.utils import compile_counter

    t0 = time.perf_counter()
    assert len(jax.devices()) >= 2, \
        f"serve_tp phase needs >=2 devices, found {len(jax.devices())}"
    # vocab/heads divisible by tp=2 so the embedding and KV heads
    # actually SHARD (non-divisible dims degrade to replicated)
    cfg = GPTConfig(vocab_size=96, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=64,
                    use_flash_attention=False)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 96, (n,)).astype(np.int32)
               for n in (5, 7, 6)]

    def run(layout, tp):
        mesh = create_mesh({"dp": 1, "tp": tp}) if tp > 1 else None
        paddle.seed(0)
        m = GPTForCausalLM(cfg)
        kw = dict(batch_slots=2, prefill_buckets=[16], mesh=mesh,
                  kv_layout=layout)
        if layout == "paged":
            kw.update(kv_block_size=8, kv_num_blocks=24)
        eng = InferenceEngine(m, **kw)
        eng.warmup(buckets=[16])
        snap = compile_counter.snapshot()
        rids = [eng.add_request(p, max_new_tokens=gen_tokens)
                for p in prompts]
        toks = eng.run()
        return ([list(map(int, toks[r])) for r in rids],
                snap.new_compiles, eng)

    out: Dict = {"name": "serve_tp", "layouts": {}}
    for layout in ("dense", "paged"):
        base, _, _ = run(layout, 1)
        tok2, compiles, eng = run(layout, 2)
        assert tok2 == base, (
            f"serve tp=2 ({layout}): tokens diverged from tp=1\n"
            f"  tp=1: {base}\n  tp=2: {tok2}")
        assert compiles == 0, (
            f"serve tp=2 ({layout}): {compiles} XLA compiles after "
            f"warmup (decode is not shape-stable under tp)")
        metas = [e.meta for e in
                 exec_registry.registry().entries(eng._exec_component)
                 if e.meta.get("submesh")]
        assert metas, \
            f"serve tp=2 ({layout}): no exec entries carry submesh meta"
        for meta in metas:
            assert meta.get("tp") == 2, f"tp meta wrong: {meta}"
            assert meta["submesh"]["shape"].get("tp") == 2, \
                f"submesh shape wrong: {meta}"
            assert len(meta["submesh"]["devices"]) == 2, \
                f"submesh devices wrong: {meta}"
        out["layouts"][layout] = {
            "tokens": sum(len(t) for t in tok2),
            "compiles_after_warmup": compiles,
            "exec_entries_with_submesh": len(metas),
        }
    out["t_s"] = round(time.perf_counter() - t0, 1)
    return out


def run_serve_ep_phase(gen_tokens: int = 8) -> Dict:
    """Expert-parallel MoE serving (ISSUE 19): an ep=2 serving mesh
    must generate TOKEN-IDENTICAL output to the replicated ep=1 MoE
    engine on BOTH KV layouts (the capacity a2a dispatch is an exact
    reformulation of the dense one-hot combine, not an approximation),
    stay recompile-free after warmup, halve the per-device expert-FFN
    residency, carry 'ep' in the exec-registry meta, and attribute the
    dispatch/combine all-to-all bytes to the ep axis in the collective
    fold."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import create_mesh
    from paddle_tpu.inference import InferenceEngine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability import exec_registry
    from paddle_tpu.utils import compile_counter

    t0 = time.perf_counter()
    assert len(jax.devices()) >= 2, \
        f"serve_ep phase needs >=2 devices, found {len(jax.devices())}"
    cfg = GPTConfig(vocab_size=96, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=64,
                    use_flash_attention=False,
                    moe_num_experts=4, moe_top_k=2)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 96, (n,)).astype(np.int32)
               for n in (5, 7, 6)]

    def run(layout, ep):
        mesh = create_mesh({"dp": 1, "tp": 1, "ep": ep}) \
            if ep > 1 else None
        paddle.seed(0)
        m = GPTForCausalLM(cfg)
        kw = dict(batch_slots=2, prefill_buckets=[16], mesh=mesh,
                  kv_layout=layout)
        if layout == "paged":
            kw.update(kv_block_size=8, kv_num_blocks=24)
        eng = InferenceEngine(m, **kw)
        eng.warmup(buckets=[16])
        snap = compile_counter.snapshot()
        rids = [eng.add_request(p, max_new_tokens=gen_tokens)
                for p in prompts]
        toks = eng.run()
        return ([list(map(int, toks[r])) for r in rids],
                snap.new_compiles, eng)

    out: Dict = {"name": "serve_ep", "layouts": {}}
    for layout in ("dense", "paged"):
        base, _, eng1 = run(layout, 1)
        tok2, compiles, eng = run(layout, 2)
        assert tok2 == base, (
            f"serve ep=2 ({layout}): tokens diverged from ep=1\n"
            f"  ep=1: {base}\n  ep=2: {tok2}")
        assert compiles == 0, (
            f"serve ep=2 ({layout}): {compiles} XLA compiles after "
            f"warmup (the capacity a2a dispatch is not shape-stable)")
        s1, s2 = eng1.stats, eng.stats
        assert s2["ep"] == 2 and s2["moe_num_experts"] == 4
        assert s2["moe_expert_load"] == s1["moe_expert_load"], (
            f"serve ep=2 ({layout}): expert load histogram diverged\n"
            f"  ep=1: {s1['moe_expert_load']}\n"
            f"  ep=2: {s2['moe_expert_load']}")
        # per-device expert-FFN residency must drop ~ep× vs replicated
        b1 = eng1._moe_expert_bytes_per_device()
        b2 = eng._moe_expert_bytes_per_device()
        assert b2 * 2 == b1, \
            f"expert bytes/device not halved under ep=2: {b1} -> {b2}"
        metas = [e.meta for e in
                 exec_registry.registry().entries(eng._exec_component)
                 if e.meta.get("submesh")]
        assert metas, \
            f"serve ep=2 ({layout}): no exec entries carry submesh meta"
        for meta in metas:
            assert meta.get("ep") == 2, f"ep meta wrong: {meta}"
            assert meta["submesh"]["shape"].get("ep") == 2, \
                f"submesh shape wrong: {meta}"
        # the collective fold must attribute the MoE dispatch/combine
        # all-to-all to the 'ep' axis on the decode executable
        reg = exec_registry.registry()
        reg.analyze_all(eng._exec_component)
        rows = [r for r in reg.snapshot(
                    eng._exec_component)["executables"]
                if r["kind"] == "decode" and r["analyzed"]]
        assert rows, f"serve ep=2 ({layout}): no analyzed decode rows"
        ep_colls = [r for r in rows
                    if (r.get("collectives") or {})
                    .get("by_axis", {}).get("ep", {}).get("count", 0)]
        assert ep_colls, (
            f"serve ep=2 ({layout}): no decode executable attributes "
            f"collective bytes to the ep axis")
        out["layouts"][layout] = {
            "tokens": sum(len(t) for t in tok2),
            "compiles_after_warmup": compiles,
            "expert_bytes_per_device": b2,
            "moe_dropped_rate": s2["moe_dropped_rate"],
            "exec_entries_with_submesh": len(metas),
        }
    out["t_s"] = round(time.perf_counter() - t0, 1)
    return out
