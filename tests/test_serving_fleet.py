"""Serving-fleet tests: prefix summaries, the router, disaggregated
prefill/decode, and the fleet load harness (ISSUE 12 tentpole pieces 2
and 3 + the summary() satellite).

No end-to-end fleet comparison is made here (whether prefix routing
beats round-robin on hit rate and p99 TTFT is a timing, and a chip's
to measure); this file covers the mechanisms deterministically —
summary/fingerprint scoring equals the real radix match, routing policy decisions, handoff block accounting,
and decode-path purity under disaggregation.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.inference import (DisaggServingEngine, InferenceEngine,
                                  Router, score_overlap)
from paddle_tpu.inference.loadgen import (MultiTenantWorkload,
                                          run_fleet_loadtest, warm_fleet)
from paddle_tpu.utils import compile_counter

TINY = dict(vocab_size=97, hidden_size=64, num_layers=2, num_heads=4,
            max_seq_len=64, use_flash_attention=False)


def tiny_model(seed=0, **over):
    paddle.seed(seed)
    cfg = GPTConfig(**{**TINY, **over})
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return tiny_model()


def paged_engine(model, **over):
    kw = dict(batch_slots=2, prefill_buckets=[16, 32],
              kv_layout="paged", kv_block_size=8)
    kw.update(over)
    return InferenceEngine(model, **kw)


# ---- prefix summary / fingerprint scoring -------------------------------

def test_summary_score_matches_real_match(model):
    """score_overlap over a replica summary() must equal what the radix
    tree's match() would find — the router's cheap probe is exact, and
    it must not touch the tree's hit counters."""
    eng = paged_engine(model)
    rng = np.random.RandomState(0)
    prefix = rng.randint(1, 97, (16,)).astype(np.int32)
    tail = rng.randint(1, 97, (5,)).astype(np.int32)
    eng.add_request(np.concatenate([prefix, tail]), max_new_tokens=4)
    eng.run()
    summ = eng.prefix_summary()
    assert summ["cached_blocks"] > 0
    q0 = eng._prefix.queries
    probe = np.concatenate([prefix, rng.randint(1, 97, (4,))
                            .astype(np.int32)])
    score = score_overlap(probe, summ)
    assert eng._prefix.queries == q0          # probe left no footprint
    blocks, matched = eng._prefix.match(probe)
    assert score == len(blocks) == matched // 8 == 2
    # a cold prompt scores zero
    assert score_overlap(rng.randint(1, 97, (20,)).astype(np.int32),
                         summ) == 0
    # summary survives eviction bookkeeping: flush drops everything
    eng.flush_prefix_cache()
    assert score_overlap(probe, eng.prefix_summary()) == 0


def test_engine_stats_expose_prefix_cache(model):
    eng = paged_engine(model)
    eng.add_request(np.arange(1, 20, dtype=np.int32), max_new_tokens=2)
    eng.run()
    pc = eng.stats["prefix_cache"]
    assert pc["block_size"] == 8
    assert isinstance(pc["fingerprints"], int)   # JSON-safe count
    assert pc["fingerprints"] == pc["cached_blocks"] > 0


# ---- router policy ------------------------------------------------------

def test_router_prefers_cached_replica(model):
    """A prompt whose prefix lives on replica 1 routes there; a cold
    prompt falls back to least-loaded; round_robin ignores both."""
    a, b = paged_engine(model), paged_engine(model)
    rng = np.random.RandomState(1)
    prefix = rng.randint(1, 97, (16,)).astype(np.int32)
    # seed replica B with the prefix directly
    b.add_request(np.concatenate([prefix, rng.randint(1, 97, (3,))
                                  .astype(np.int32)]), max_new_tokens=2)
    b.run()
    router = Router([a, b], policy="prefix")
    probe = np.concatenate([prefix,
                            rng.randint(1, 97, (4,)).astype(np.int32)])
    assert router.route(probe) == 1
    assert router.prefix_routed == 1
    assert router.prefix_blocks_routed == 2
    # cold prompt: least-loaded fallback — both idle, index 0 wins
    assert router.route(rng.randint(1, 97, (10,)).astype(np.int32)) == 0
    st = router.stats
    assert st["requests_routed"] == 2
    assert st["router_hit_rate"] == 0.5
    rr = Router([a, b], policy="round_robin")
    assert [rr.route(probe) for _ in range(4)] == [0, 1, 0, 1]


def test_router_load_gap_bounds_affinity(model):
    """Cache affinity must not chase a prefix onto a backed-up replica:
    past max_load_gap the router balances instead."""
    a, b = paged_engine(model), paged_engine(model)
    rng = np.random.RandomState(2)
    prefix = rng.randint(1, 97, (16,)).astype(np.int32)
    b.add_request(np.concatenate([prefix, rng.randint(1, 97, (3,))
                                  .astype(np.int32)]), max_new_tokens=2)
    b.run()
    # pile queued work onto B without stepping it
    for _ in range(4):
        b.add_request(rng.randint(1, 97, (6,)).astype(np.int32),
                      max_new_tokens=2)
    router = Router([a, b], policy="prefix", max_load_gap=2)
    probe = np.concatenate([prefix,
                            rng.randint(1, 97, (4,)).astype(np.int32)])
    assert router.route(probe) == 0          # balance beat affinity
    assert router.prefix_routed == 0
    relaxed = Router([a, b], policy="prefix", max_load_gap=100)
    assert relaxed.route(probe) == 1         # affinity wins when allowed
    b.run()


def test_router_end_to_end_results(model):
    """Router.run() drives every replica to completion and namespaces
    results by replica index."""
    fleet = Router([paged_engine(model), paged_engine(model)],
                   policy="least_loaded")
    rng = np.random.RandomState(3)
    keys = [fleet.add_request(rng.randint(1, 97, (5,)).astype(np.int32),
                              max_new_tokens=4) for _ in range(6)]
    out = fleet.run()
    assert set(keys) == set(out.keys())
    assert all(len(v) > 0 for v in out.values())
    for r in fleet.replicas:
        r.check_leak_free()


# ---- fleet load harness -------------------------------------------------

@pytest.mark.slow  # tier-1 wall budget: heaviest in file
def test_fleet_loadtest_report_columns(model):
    """run_fleet_loadtest on a 2-replica fleet: per-replica columns,
    router hit rate, aggregate prefix hit rate, and zero recompiles in
    the measured window with spec decoding on."""
    def mk(policy):
        reps = []
        for _ in range(2):
            e = paged_engine(model, spec_k=2, draft_model=model)
            e.warmup(buckets=e.buckets)
            reps.append(e)
        return Router(reps, policy=policy)

    wl = MultiTenantWorkload(97, seed=5, num_tenants=4, skew=1.0,
                             prefix_len=16, tail_len=(3, 8),
                             max_new=(2, 4))
    fleet = mk("prefix")
    warm_fleet(fleet, wl)
    snap = compile_counter.snapshot()
    rep = run_fleet_loadtest(fleet, 16, 100.0, workload=wl, seed=0)
    assert snap.new_compiles == 0
    assert rep["num_requests"] == 16
    assert rep["num_replicas"] == 2
    assert len(rep["replica_occupancy"]) == 2
    # router counters are snapshotted: warm_fleet traffic excluded
    assert sum(rep["requests_per_replica"]) == 16
    assert rep["prefix_hit_rate"] > 0
    assert rep["accepted_tokens_per_tick"] > 1.5
    assert rep["ttft_ms_p99"] >= rep["ttft_ms_p50"] > 0
    assert rep["tenants_seen"] <= 4
    for r in fleet.replicas:
        r.check_leak_free()


def test_multitenant_workload_skew():
    wl = MultiTenantWorkload(97, seed=0, num_tenants=4, skew=1.5)
    counts = np.zeros(4)
    for _ in range(400):
        t, prompt, mn = wl.sample()
        counts[t] += 1
        assert prompt.size > wl.prefixes[t].size
        np.testing.assert_array_equal(prompt[:16], wl.prefixes[t])
    assert counts[0] > counts[-1] * 2        # hot head, cold tail


# ---- disaggregated prefill/decode ---------------------------------------

def test_disagg_token_identity_and_leakfree(model):
    """Disaggregated engine ≡ the plain paged engine token for token;
    pools drain leak-free; zero recompiles after warmup (the worker's
    own prefill executables included)."""
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 97, (n,)).astype(np.int32)
               for n in (5, 9, 3, 12, 7)]
    ref_eng = paged_engine(model)
    for p in prompts:
        ref_eng.add_request(p, max_new_tokens=10)
    ref = ref_eng.run()
    dis = DisaggServingEngine(model, batch_slots=2,
                              prefill_buckets=[16, 32], kv_block_size=8)
    dis.warmup()
    with compile_counter.assert_no_recompiles("disagg churn"):
        for p in prompts:
            dis.add_request(p, max_new_tokens=10)
        out = dis.run()
    for rr, ss in zip(sorted(ref), sorted(out)):
        np.testing.assert_array_equal(ref[rr], out[ss])
    assert dis.stats["handoffs"] == len(prompts)
    assert dis.stats["prefill_worker_prefills"] == len(prompts)
    dis.drain()
    dis.check_leak_free()


def test_disagg_decode_steps_run_no_prefill(model):
    """The POINT of disaggregation: the decode engine's own prefill
    executables never run — admissions come exclusively through the
    worker's handoff records."""
    dis = DisaggServingEngine(model, batch_slots=2,
                              prefill_buckets=[16], kv_block_size=8)
    dis.warmup()
    rng = np.random.RandomState(4)
    for _ in range(3):
        dis.add_request(rng.randint(1, 97, (6,)).astype(np.int32),
                        max_new_tokens=6)
    dis.run()
    # every prefill was timed under a worker key, none under the decode
    # engine's own ("prefill_paged*") keys
    keys = dis.decode._first_call_keys
    assert any(k[0].startswith("disagg") for k in keys)
    assert dis.stats["prefill_worker_prefills"] == 3


@pytest.mark.slow  # tier-1 wall budget: heaviest in file
def test_disagg_spec_and_prefix_cache_compose(model):
    """Disagg + spec decode + radix prefix cache all stack: shared
    prefixes hit across handoffs, spec ticks commit >1 token, output
    stays greedy-identical."""
    rng = np.random.RandomState(6)
    prefix = rng.randint(1, 97, (16,)).astype(np.int32)
    prompts = [np.concatenate([prefix, rng.randint(1, 97, (3,))
                               .astype(np.int32)]) for _ in range(4)]
    ref_eng = paged_engine(model)
    for p in prompts:
        ref_eng.add_request(p, max_new_tokens=8)
    ref = ref_eng.run()
    dis = DisaggServingEngine(model, batch_slots=2,
                              prefill_buckets=[16, 32], kv_block_size=8,
                              spec_k=2, draft_model=model)
    dis.warmup()
    for p in prompts:
        dis.add_request(p, max_new_tokens=8)
    out = dis.run()
    for rr, ss in zip(sorted(ref), sorted(out)):
        np.testing.assert_array_equal(ref[rr], out[ss])
    st = dis.stats
    assert st["prefix_hit_queries"] >= 3
    assert st["accepted_tokens_per_tick"] > 1.5
    dis.drain()
    dis.check_leak_free()


def test_disagg_deadline_and_drain(model):
    """Wrapper-queue deadlines expire without a prefill; drain returns
    queued + parked work and leaves the pool clean."""
    dis = DisaggServingEngine(model, batch_slots=1,
                              prefill_buckets=[16], kv_block_size=8,
                              prefills_per_step=1, handoff_depth=1)
    dis.warmup()
    rng = np.random.RandomState(8)
    rid = dis.add_request(rng.randint(1, 97, (5,)).astype(np.int32),
                          max_new_tokens=4, deadline_s=0.0)
    import time
    time.sleep(0.01)
    dis.step()
    assert dis.request_stats[rid]["timed_out"]
    # now park work and drain
    for _ in range(3):
        dis.add_request(rng.randint(1, 97, (5,)).astype(np.int32),
                        max_new_tokens=4)
    dis.step()
    leftover = dis.drain()
    dis.check_leak_free()
    assert not dis.has_work
    assert all(r.slot is None for r in leftover)
