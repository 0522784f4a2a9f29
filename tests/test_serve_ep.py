"""Expert-parallel MoE serving (ISSUE 19).

Every test runs on the suite's virtual 8-device CPU mesh.  The
contracts:

- ep=2 serving is TOKEN-IDENTICAL to the replicated (ep=1) engine —
  the capacity-bucketed a2a dispatch reorders WHERE each token's
  expert FFN runs, never its math (greedy) — with ZERO XLA compiles
  after warmup, because the dispatch is ONE fixed-shape chunked
  all_to_all whose token dim is padded to capacity.
- expert FFN weights shard over 'ep': per-device expert bytes drop
  ~ep×, the exec registry records the ep degree per executable, and
  the comm_stats fold attributes the dispatch/combine a2a to the 'ep'
  axis.
- capacity overflow is ACCOUNTED, not hidden: dropped = assigned −
  kept at every layer, identical between ep=1 and ep=2, and the
  'expert-imbalance' doctor rule turns the stats into a knob.

Tier-1 covers the corners (dense fp full observability, paged int8
churn, tp×ep, disjoint disagg groups); the exhaustive layout × dtype
× spec matrix rides the slow lane.
"""
import os

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.distributed.mesh import create_mesh
from paddle_tpu.inference import InferenceEngine
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.utils import compile_counter

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs a multi-device (CPU) mesh")

MOE = dict(vocab_size=96, hidden_size=64, num_layers=2, num_heads=4,
           max_seq_len=64, use_flash_attention=False,
           moe_num_experts=4, moe_top_k=2)


def moe_model(seed=0, **over):
    paddle.seed(seed)
    m = GPTForCausalLM(GPTConfig(**{**MOE, **over}))
    m.eval()
    return m


def _ep_mesh(ep, tp=1):
    if ep == 1 and tp == 1:
        return None
    axes = {"dp": 1, "tp": tp}
    if ep > 1:
        axes["ep"] = ep
    return create_mesh(axes)


def _mk(model, ep, tp=1, **kw):
    return InferenceEngine(model, batch_slots=2, prefill_buckets=[16],
                           mesh=_ep_mesh(ep, tp), **kw)


def _run(eng, prompts, gen=5):
    rids = [eng.add_request(p, max_new_tokens=gen) for p in prompts]
    out = eng.run()
    return [list(map(int, out[r])) for r in rids]


def _prompts(seed=0, lens=(5, 9)):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 96, (n,)).astype(np.int32) for n in lens]


@pytest.fixture(scope="module")
def model():
    return moe_model(0)


def test_ep_dense_parity_and_observability(model):
    """The dense leg carries the full contract in one pair of engines:
    ep=2 tokens ≡ ep=1, ZERO compiles after warmup, identical expert
    LOAD histograms (the dispatch moves work, not assignments),
    per-device expert bytes halved, registry entries name ep and the
    submesh, and the analysis folds ep-attributed a2a collectives."""
    from paddle_tpu.observability import exec_registry

    prompts = _prompts(0)
    base_eng = _mk(model, 1)
    base = _run(base_eng, prompts)
    eng = _mk(model, 2)
    eng.warmup(buckets=[16])
    with compile_counter.assert_no_recompiles("dense ep=2 post-warmup"):
        toks = _run(eng, prompts)
    assert toks == base

    s1, s2 = base_eng.stats, eng.stats
    assert s2["ep"] == 2 and s2["tp"] == 1
    assert s2["serving_mesh"] == {"dp": 1, "tp": 1, "ep": 2}
    assert s2["moe_num_experts"] == 4
    # routing is replicated: same per-expert assignment counts no
    # matter where the expert FFNs physically ran
    assert s2["moe_expert_load"] == s1["moe_expert_load"]
    assert s2["moe_dropped_rate"] == s1["moe_dropped_rate"]
    # the point of ep: each device holds 1/ep of the expert weights
    b1 = base_eng._moe_expert_bytes_per_device()
    b2 = eng._moe_expert_bytes_per_device()
    assert b2 * 2 == b1
    assert s2["decode_hbm_bytes_per_tok"] < s1["decode_hbm_bytes_per_tok"]

    reg = exec_registry.registry()
    reg.analyze_all(eng._exec_component)
    rows = [r for r in reg.snapshot(eng._exec_component)["executables"]
            if (r.get("meta") or {}).get("submesh")]
    assert rows, "no submesh-tagged entries for the ep engine"
    for r in rows:
        assert r["meta"]["ep"] == 2
        assert r["meta"]["submesh"]["shape"].get("ep") == 2
    decode_rows = [r for r in rows
                   if r["kind"] == "decode" and r["analyzed"]]
    assert decode_rows
    for r in decode_rows:
        coll = r.get("collectives")
        assert coll and coll["count"] > 0
        # the expert dispatch/combine must actually COMMUNICATE,
        # attributed to 'ep' by the comm_stats axis fold
        assert coll.get("by_axis", {}).get("ep", {}).get("count", 0) > 0


def test_ep_paged_int8_churn_recompile_free(model):
    """The paged leg doubles as the int8-KV (satellite: kv_dtype is
    ORTHOGONAL to MoE — only quantized COMPUTE is gated) and
    slot-churn corner: more requests than slots through a warmed ep=2
    paged int8 engine — tokens ≡ ep=1, ZERO new compiles, pool
    leak-free at drain."""
    kw = dict(kv_layout="paged", kv_block_size=8, kv_dtype="int8")
    churn = _prompts(1, lens=(4, 7, 11, 6))
    base = _run(_mk(model, 1, **kw), churn)
    eng = _mk(model, 2, **kw)
    eng.warmup(buckets=[16])
    with compile_counter.assert_no_recompiles("paged int8 ep churn"):
        toks = _run(eng, churn)
    assert toks == base
    eng.check_leak_free()


@pytest.mark.slow  # tier-1 wall budget: heaviest in file
def test_tp_ep_composition(model):
    """tp=2 × ep=2 on one mesh: attention/dense FFN shard over 'tp',
    expert FFNs over 'ep', and the tokens still match the unsharded
    engine."""
    prompts = _prompts(2)
    base = _run(_mk(model, 1), prompts)
    eng = _mk(model, 2, tp=2)
    toks = _run(eng, prompts)
    assert toks == base
    s = eng.stats
    assert s["tp"] == 2 and s["ep"] == 2
    assert s["serving_mesh"] == {"dp": 1, "tp": 2, "ep": 2}


@pytest.mark.slow
def test_serve_ep_env(model, monkeypatch):
    """PADDLE_TPU_SERVE_EP=2 builds the {'dp','tp','ep'} mesh without
    an explicit mesh argument — one env knob for the whole fleet."""
    monkeypatch.setenv("PADDLE_TPU_SERVE_EP", "2")
    eng = InferenceEngine(model, batch_slots=2, prefill_buckets=[16])
    prompts = _prompts(3, lens=(5,))
    toks = _run(eng, prompts, gen=4)
    monkeypatch.delenv("PADDLE_TPU_SERVE_EP")
    base = _run(_mk(model, 1), prompts, gen=4)
    assert toks == base
    assert eng.stats["ep"] == 2


def test_capacity_overflow_accounting(model):
    """Dropped tokens are exact accounting, not an estimate.  Unit
    half: a host reference over a hand-routed gating — every token
    beyond an expert's capacity loses its dispatch slot.  Engine half:
    a starved capacity factor drops tokens, and ep=2 reports the SAME
    drop ledger as ep=1 (the a2a dispatch pads to capacity; it never
    drops on its own)."""
    from paddle_tpu.distributed.moe import moe_capacity, top_k_gating

    # -- unit: all tokens prefer expert 0, capacity keeps only `cap`
    s, e, k = 8, 4, 1
    logits = np.zeros((1, s, e), np.float32)
    logits[..., 0] = 5.0                       # expert 0 wins every token
    cap = moe_capacity(s, e, k, capacity_factor=0.5)   # = 1
    dispatch, combine, _, _ = top_k_gating(
        jax.numpy.asarray(logits), k, cap)
    load = np.asarray(jax.numpy.sum(dispatch, axis=(0, 1, 3)))
    assert load.tolist() == [float(cap)] + [0.0] * (e - 1)
    assert float(np.asarray(combine).sum()) > 0

    # -- engine: starved capacity → drops, identical across ep
    starved = moe_model(4, moe_capacity_factor=0.25)
    prompts = _prompts(4, lens=(9, 6))
    e1 = _mk(starved, 1)
    t1 = _run(e1, prompts, gen=4)
    e2 = _mk(starved, 2)
    t2 = _run(e2, prompts, gen=4)
    assert t2 == t1
    s1, s2 = e1.stats, e2.stats
    assert s1["moe_dropped_rate"] > 0
    assert s2["moe_dropped_rate"] == s1["moe_dropped_rate"]
    assert s2["moe_expert_load"] == s1["moe_expert_load"]


def test_quantize_moe_guard():
    """Satellite: quantized COMPUTE with MoE raises (the expert
    einsums have no quantized path), but int8 KV CACHE is orthogonal —
    the config must accept it (the churn test above runs it)."""
    with pytest.raises(NotImplementedError,
                       match="quantize='int8' COMPUTE"):
        GPTConfig(**MOE, quantize="int8")
    GPTConfig(**MOE)                         # no quantize: fine


def test_a2a_chunks_divisor_error():
    """Satellite: an explicit a2a_chunks that doesn't divide the
    capacity slice names the NEAREST VALID divisors instead of a bare
    refusal — the knob is meant for A/B sweeps, and a sweep script
    needs the legal neighbours."""
    from paddle_tpu.distributed.moe import (MoELayer,
                                            nearest_chunk_divisors)

    assert nearest_chunk_divisors(12, 5) == (4, 6)
    assert nearest_chunk_divisors(12, 1) == (1, 1)
    assert nearest_chunk_divisors(12, 100) == (12, 12)

    layer = MoELayer(hidden_size=8, ffn_size=16, num_experts=4,
                     a2a_chunks=5)
    with pytest.raises(ValueError) as ei:
        layer._serve_chunks(12)
    msg = str(ei.value)
    assert "4 (below)" in msg and "6 (above)" in msg
    # None auto-clamps down to a divisor instead of raising
    layer.a2a_chunks = None
    assert 12 % layer._serve_chunks(12) == 0


def test_doctor_expert_imbalance():
    """The 'expert-imbalance' rule: silent on balanced traffic, fires
    on capacity overflow (→ raise moe_capacity_factor), fires on pure
    skew under spec decode (→ lower spec_k first: a rejected draft
    burst is the usual skew source), and stays silent below the
    minimum evidence window."""
    from paddle_tpu.observability import doctor

    base = {"moe_num_experts": 4, "moe_assigned_tokens": 1000.0,
            "moe_dropped_rate": 0.0, "moe_load_skew": 1.1,
            "moe_expert_load": [250.0, 240.0, 260.0, 250.0], "ep": 2}

    def verdicts(s):
        return [v for v in doctor.diagnose(s, kind="serve")
                if v["bottleneck"] == "expert-imbalance"]

    assert verdicts(base) == []

    over = dict(base, moe_dropped_rate=0.2,
                moe_expert_load=[700.0, 40.0, 30.0, 30.0],
                moe_load_skew=3.5)
    (v,) = verdicts(over)
    assert v["evidence"]["moe_dropped_rate"] == 0.2
    assert v["evidence"]["hottest_expert"] == 0
    assert v["action"]["param"] == "moe_capacity_factor"

    skew = dict(base, moe_load_skew=3.0, spec_k=4)
    (v,) = verdicts(skew)
    assert v["action"]["param"] == "spec_k"
    assert v["action"]["candidates"] == [2, 1]

    assert verdicts(dict(over, moe_assigned_tokens=8.0)) == []


@pytest.mark.slow
def test_loadgen_moe_columns(model):
    """Loadgen reports grow the expert-balance window columns: the
    histogram, dropped rate, and skew are WINDOW-scoped (snapshot and
    subtract), so a reused engine reports this run's balance."""
    from paddle_tpu.inference.loadgen import (SharedPrefixWorkload,
                                              run_loadtest)

    eng = _mk(model, 2)
    wl = SharedPrefixWorkload(96, prefix_len=4, tail_len=(3, 6),
                              max_new=(3, 5), seed=0)
    report = run_loadtest(eng, num_requests=3, rate_rps=1000.0,
                          workload=wl)
    assert report["moe_num_experts"] == 4 and report["ep"] == 2
    assert report["moe_assigned_tokens"] > 0
    assert report["moe_dropped_rate"] >= 0.0
    assert len(report["moe_expert_load"]) == 4
    assert sum(report["moe_expert_load"]) > 0
    assert report["moe_load_skew"] is not None


# ---- disaggregated prefill with expert parallelism --------------------
def test_disagg_disjoint_ep(model):
    """Disjoint prefill/decode groups, each with its own
    {'dp','tp','ep'} mesh: the prefill worker's executables must trace
    under the PREFILL mesh (a shared trace would bake the decode
    group's devices into the serve-ep shard_map), the KV handoff
    crosses the boundary, and tokens match the plain engine."""
    from paddle_tpu.inference.disagg import DisaggServingEngine

    prompts = _prompts(5, lens=(7, 12))
    ref = InferenceEngine(model, batch_slots=2, kv_layout="paged",
                          kv_block_size=8, seed=3)
    rids = [ref.add_request(p, max_new_tokens=5) for p in prompts]
    ref_out = ref.run()

    eng = DisaggServingEngine(model, prefill_devices=4, seed=3,
                              batch_slots=2, kv_block_size=8,
                              prefill_ep=2, decode_ep=2)
    rids2 = [eng.add_request(p, max_new_tokens=5) for p in prompts]
    out = eng.run()
    for a, b in zip(rids, rids2):
        np.testing.assert_array_equal(ref_out[a], out[b])

    s = eng.stats
    assert s["disjoint_groups"] is True
    assert s["ep"] == 2
    assert s["prefill_mesh"] == {"dp": 1, "tp": 2, "ep": 2}
    assert s["serving_mesh"] == {"dp": 1, "tp": 2, "ep": 2}
    assert s["handoff_transfers"] >= len(prompts)
    assert s["moe_dropped_rate"] == ref.stats["moe_dropped_rate"]

    # a non-dividing group is a config error, named per group
    with pytest.raises(ValueError, match="prefill_ep=2"):
        DisaggServingEngine(model, prefill_devices=3, prefill_ep=2,
                            batch_slots=2, kv_block_size=8)

    eng.decode.drain()
    eng.check_leak_free()


@pytest.mark.slow
@pytest.mark.parametrize("layout,kv_dtype,spec", [
    ("dense", "int8", False), ("paged", None, False),
    ("dense", None, True), ("paged", "int8", True),
])
def test_ep_parity_matrix_full(model, layout, kv_dtype, spec):
    """The exhaustive matrix (slow lane): every remaining layout ×
    KV-dtype × spec-decode combination, ep=2 ≡ ep=1 (the spec VERIFY
    path routes through the same fixed-shape expert dispatch)."""
    kw = dict(kv_layout=layout, kv_dtype=kv_dtype)
    if layout == "paged":
        kw.update(kv_block_size=8)
    if spec:
        draft = moe_model(1, num_layers=1, moe_num_experts=0)
        kw.update(spec_k=2, draft_model=draft)
    prompts = _prompts(6, lens=(5, 9, 3))
    base = _run(_mk(model, 1, **kw), prompts, gen=8)
    eng = _mk(model, 2, **kw)
    toks = _run(eng, prompts, gen=8)
    assert toks == base
    if spec:
        assert eng.stats["spec_ticks"] > 0
    if layout == "paged":
        eng.check_leak_free()


@pytest.mark.slow
def test_disagg_shared_pool_ep(model):
    """Shared-pool disagg (no device carve) on one ep=2 mesh: the
    prefill worker reuses the decode engine's executables — parity and
    a combined expert-load histogram."""
    from paddle_tpu.inference.disagg import DisaggServingEngine

    prompts = _prompts(7, lens=(6, 10))
    ref = InferenceEngine(model, batch_slots=2, kv_layout="paged",
                          kv_block_size=8, seed=3)
    rids = [ref.add_request(p, max_new_tokens=5) for p in prompts]
    ref_out = ref.run()

    eng = DisaggServingEngine(model, seed=3, batch_slots=2,
                              kv_block_size=8, mesh=_ep_mesh(2))
    rids2 = [eng.add_request(p, max_new_tokens=5) for p in prompts]
    out = eng.run()
    for a, b in zip(rids, rids2):
        np.testing.assert_array_equal(ref_out[a], out[b])
    s = eng.stats
    assert s["ep"] == 2 and s["moe_num_experts"] == 4
    # ONE combined histogram: worker prefills accumulate into the
    # decode engine's counters.  The disagg drive loop ticks decode
    # once more than the monolithic engine (the handoff poll), so
    # compare per-expert load within that one-tick slack rather than
    # exactly — token identity above is the strong check.
    ref_load = ref.stats["moe_expert_load"]
    assert len(s["moe_expert_load"]) == 4
    for got, want in zip(s["moe_expert_load"], ref_load):
        assert want <= got <= want + 2 * len(prompts)
