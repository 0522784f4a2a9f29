"""Pod-scale tensor-parallel serving (ISSUE 18).

Every test runs on the suite's virtual 8-device CPU mesh (conftest sets
``--xla_force_host_platform_device_count=8`` before jax initializes).
The contracts:

- tp=2 serving is TOKEN-IDENTICAL to the unsharded engine — the
  NamedSharding commit changes layout, never numerics (greedy) — with
  ZERO XLA compiles after warmup (committed weights/cache/rng key must
  not add sharding-keyed cache misses, even under slot churn).
- disaggregated prefill/decode runs on provably DISJOINT device
  groups, with device-to-device KV-block handoff, and still matches
  the plain engine token for token.
- comm_stats attributes collectives to mesh axes; exec-registry
  entries compiled against a submesh carry it and fold the per-axis
  collective breakdown into their analysis.

Tier-1 covers the matrix corners on SHARED engines (dense fp with the
full observability sweep, paged int8 under slot churn, GQA on the
paged fp pool); the exhaustive layout × dtype × spec matrix rides the
slow lane.
"""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.distributed.mesh import create_mesh
from paddle_tpu.inference import InferenceEngine
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.utils import compile_counter

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs a multi-device (CPU) mesh")

TINY = dict(vocab_size=96, hidden_size=64, num_layers=2, num_heads=4,
            max_seq_len=64, use_flash_attention=False)


def tiny_model(seed=0, **over):
    paddle.seed(seed)
    m = GPTForCausalLM(GPTConfig(**{**TINY, **over}))
    m.eval()
    return m


def _tp_mesh(tp):
    return create_mesh({"dp": 1, "tp": tp}) if tp > 1 else None


def _mk(model, tp, **kw):
    return InferenceEngine(model, batch_slots=2, prefill_buckets=[16],
                           mesh=_tp_mesh(tp), **kw)


def _run(eng, prompts, gen=5):
    rids = [eng.add_request(p, max_new_tokens=gen) for p in prompts]
    out = eng.run()
    return [list(map(int, out[r])) for r in rids]


def _prompts(seed=0, lens=(5, 9)):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 96, (n,)).astype(np.int32) for n in lens]


@pytest.fixture(scope="module")
def model():
    return tiny_model(0)


def test_tp_dense_parity_and_observability(model):
    """The dense leg carries the full contract in one pair of engines:
    tp=2 tokens ≡ tp=1, ZERO compiles after warmup, stats carry
    tp/serving_mesh, registry entries name the submesh, and the
    deferred analysis folds tp-attributed collectives into the snapshot
    row."""
    from paddle_tpu.observability import exec_registry

    prompts = _prompts(0)
    base = _run(_mk(model, 1), prompts)
    eng = _mk(model, 2)
    eng.warmup(buckets=[16])
    with compile_counter.assert_no_recompiles("dense tp=2 post-warmup"):
        toks = _run(eng, prompts)
    assert toks == base
    s = eng.stats
    assert s["tp"] == 2 and s["serving_mesh"] == {"dp": 1, "tp": 2}

    reg = exec_registry.registry()
    reg.analyze_all(eng._exec_component)
    rows = [r for r in reg.snapshot(eng._exec_component)["executables"]
            if (r.get("meta") or {}).get("submesh")]
    assert rows, "no submesh-tagged entries for the tp engine"
    for r in rows:
        assert r["meta"]["tp"] == 2
        assert r["meta"]["submesh"]["shape"].get("tp") == 2
        assert len(r["meta"]["submesh"]["devices"]) == 2
    decode_rows = [r for r in rows
                   if r["kind"] == "decode" and r["analyzed"]]
    assert decode_rows
    for r in decode_rows:
        coll = r.get("collectives")
        assert coll and coll["count"] > 0, \
            f"no collective fold on {r['name']}"
        # a tp-sharded decode step must actually COMMUNICATE (the
        # row-parallel partial-sum reduce), attributed to 'tp'
        assert coll.get("by_axis", {}).get("tp", {}).get("count", 0) > 0


def test_tp_paged_int8_churn_recompile_free(model):
    """The paged leg doubles as the int8-KV and slot-churn corner:
    more requests than slots through a warmed tp=2 paged int8 engine —
    tokens ≡ tp=1, ZERO new compiles across admit/retire/scale
    round-trips, pool leak-free at drain."""
    kw = dict(kv_layout="paged", kv_block_size=8, kv_dtype="int8")
    churn = _prompts(1, lens=(4, 7, 11, 6))
    base = _run(_mk(model, 1, **kw), churn)
    eng = _mk(model, 2, **kw)
    eng.warmup(buckets=[16])
    with compile_counter.assert_no_recompiles("paged int8 tp churn"):
        toks = _run(eng, churn)
    assert toks == base
    eng.check_leak_free()


@pytest.mark.slow  # tier-1 wall budget: heaviest in file
def test_tp_paged_gqa_parity():
    """GQA on the paged fp pool: 2 KV heads over tp=2 means ONE kv
    head per shard — the sharpest head-sharding corner."""
    model = tiny_model(2, num_kv_heads=2)
    kw = dict(kv_layout="paged", kv_block_size=8)
    prompts = _prompts(2)
    base = _run(_mk(model, 1, **kw), prompts)
    eng = _mk(model, 2, **kw)
    toks = _run(eng, prompts)
    assert toks == base
    eng.check_leak_free()


@pytest.mark.slow
@pytest.mark.parametrize("layout,kv_dtype,spec", [
    ("dense", "int8", False), ("paged", None, False),
    ("paged", "int8", False), ("dense", None, True),
    ("paged", None, True),
])
def test_tp_parity_matrix_full(model, layout, kv_dtype, spec):
    """The exhaustive matrix (slow lane): every remaining layout ×
    KV-dtype × spec-decode combination, tp=2 ≡ tp=1."""
    kw = dict(kv_layout=layout, kv_dtype=kv_dtype)
    if layout == "paged":
        kw.update(kv_block_size=8)
    if spec:
        kw.update(spec_k=2, draft_model=tiny_model(1, num_layers=1))
    prompts = _prompts(3, lens=(5, 9, 3))
    base = _run(_mk(model, 1, **kw), prompts, gen=8)
    eng = _mk(model, 2, **kw)
    toks = _run(eng, prompts, gen=8)
    assert toks == base
    if spec:
        assert eng.stats["spec_ticks"] > 0
    if layout == "paged":
        eng.check_leak_free()


# ---- disaggregated prefill on disjoint device groups ------------------
@pytest.mark.slow  # tier-1 wall budget: heaviest in file
def test_disagg_disjoint_groups(model):
    """DistServe-style split: prefill compiles against devices [0:4],
    decode against [4:8], the KV handoff crosses the group boundary,
    and tokens still match the plain single-group engine."""
    from paddle_tpu.inference.disagg import DisaggServingEngine
    from paddle_tpu.observability import exec_registry

    prompts = _prompts(4, lens=(7, 13))
    ref = InferenceEngine(model, batch_slots=2, kv_layout="paged",
                          kv_block_size=8, seed=3)
    rids = [ref.add_request(p, max_new_tokens=5) for p in prompts]
    ref_out = ref.run()

    eng = DisaggServingEngine(model, prefill_devices=4, seed=3,
                              batch_slots=2, kv_block_size=8)
    rids2 = [eng.add_request(p, max_new_tokens=5) for p in prompts]
    out = eng.run()
    for a, b in zip(rids, rids2):
        np.testing.assert_array_equal(ref_out[a], out[b])

    s = eng.stats
    assert s["disjoint_groups"] is True
    assert s["handoff_transfers"] >= len(prompts)
    p_devs, d_devs = set(s["prefill_devices"]), set(s["decode_devices"])
    assert p_devs and d_devs and not (p_devs & d_devs)

    # the observatory records WHICH submesh each half compiled
    # against: the handoff gather runs on the prefill group, the
    # scatter on the decode group — disjoint by construction
    by_key = {e.key: e for e in exec_registry.registry().entries(
        eng.decode._exec_component)}
    gather = by_key.get(("handoff_gather", 0))
    scatter = by_key.get(("handoff_scatter", 0))
    assert gather is not None and scatter is not None
    g_devs = set(gather.meta["submesh"]["devices"])
    s_devs = set(scatter.meta["submesh"]["devices"])
    assert g_devs == p_devs and s_devs == d_devs

    eng.decode.drain()
    eng.check_leak_free()


# ---- collective axis attribution (pure units) -------------------------
def test_comm_stats_axis_groups():
    """axis_groups_from_shape partitions logical device ids per axis
    in mesh-major order; _match_axis names the axis whose partition a
    collective's replica groups equal (global groups on a multi-axis
    mesh → "all", anything else → "other")."""
    from paddle_tpu.utils import comm_stats as cs

    ag = cs.axis_groups_from_shape({"dp": 2, "tp": 4})
    assert [sorted(g) for g in ag["tp"]] == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert [sorted(g) for g in ag["dp"]] == [[0, 4], [1, 5], [2, 6],
                                             [3, 7]]
    # extent-1 axes are dropped (nothing to attribute)
    assert "dp" not in cs.axis_groups_from_shape({"dp": 1, "tp": 2})

    axis_sets = {ax: set(gs) for ax, gs in ag.items()}
    assert cs._match_axis([[0, 1, 2, 3], [4, 5, 6, 7]], axis_sets,
                          8) == "tp"
    assert cs._match_axis([[0, 4], [1, 5], [2, 6], [3, 7]], axis_sets,
                          8) == "dp"
    assert cs._match_axis(None, axis_sets, 8) == "all"
    assert cs._match_axis([[0, 1], [2, 3], [4, 5], [6, 7]], axis_sets,
                          8) == "other"

    # by_axis lands in parse output when axis_groups is passed
    hlo = ('%ar = f32[16]{0} all-reduce(%x), '
           'replica_groups={{0,1,2,3},{4,5,6,7}}')
    out = cs.parse_hlo_collectives(hlo, axis_groups=ag)
    assert out["by_axis"] == {"tp": {"count": 1, "bytes": 64}}
