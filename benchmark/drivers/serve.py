"""Serving cells: ``InferenceEngine`` under a closed or an open loop.

One process drives the engine and generates the load (the drive loop and
the lateness correction are copied from ``inference/loadgen.py``
``run_loadtest``; its traffic, doctor, SLO monitor and watchdog are not).
Every request carries a deadline at the end of the run (window, traced
slice, grace), so that nothing expires while anything is measured and
one step past it hands back the records of the requests still in flight.

The populations: a request belongs to the window if it was due (open
loop) or sent (closed loop) inside it.  Time to first token is counted
from then.  Token rates and gaps are taken by delivery time: every output
token delivered inside the window counts, whoever's request it belongs
to.  A request of the window with no first token when the run ends has
failed.
"""
from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from .. import harness, trafficgen, weights as weights_mod
from ..harness import say


def build_engine(config: dict, flat: dict):
    from paddle_tpu.inference import InferenceEngine
    t0 = time.perf_counter()
    model = harness.build_model(config, flat)
    model.eval()
    t1 = time.perf_counter()
    opts = dict(config["driver"]["engine"])
    engine = InferenceEngine(model, **opts)
    t2 = time.perf_counter()
    engine.warmup(buckets=opts["prefill_buckets"])
    say("setup", model_build_s=t1 - t0, engine_build_s=t2 - t1,
        warmup_s=time.perf_counter() - t2)
    return engine


def release(engine) -> None:
    """Free the engine's cache and weights before the reference runs
    (copied from chip_smoke.py: the cache is the engine's own, so its
    buffers are deleted outright)."""
    import jax
    cache, engine.cache, engine.params = engine.cache, None, None
    for leaf in jax.tree_util.tree_leaves(cache):
        leaf.delete()
    engine.model = None
    gc.collect()


class Load:
    """The load generator's state for one run."""

    def __init__(self, engine, mix: dict, plan: list, t_lead: float,
                 deadline_at: float):
        self.engine, self.mix, self.plan = engine, mix, plan
        self.open_loop = mix["kind"] == "open_loop"
        self.t_lead = t_lead              # the schedule's zero
        self.deadline_at = deadline_at
        self.next = 0
        self.sent = {}                    # rid -> {due, added, plan index}
        self.records = {}                 # rid -> engine record + tokens
        self.stagger = 0.0 if self.open_loop else \
            float(mix["lead_in_s"]) / max(int(mix["clients"]), 1)
        self.started_clients = 0

    def _send(self, due: float) -> None:
        if self.next >= len(self.plan):
            raise RuntimeError("the traffic plan ran out of requests; "
                               "raise plan_requests in the mix")
        req = self.plan[self.next]
        now = time.perf_counter()
        rid = self.engine.add_request(
            req["prompt"], max_new_tokens=req["max_new"], eos_id=None,
            temperature=0.0, deadline_s=max(self.deadline_at - now, 1e-3))
        self.sent[rid] = {"due": due, "added": time.perf_counter(),
                          "index": self.next}
        self.next += 1

    def offer(self, now: float, sending: bool) -> None:
        """Send what is due: scheduled arrivals (open loop), or one
        request for every caller that has none outstanding."""
        if not sending:
            return
        if self.open_loop:
            while self.next < len(self.plan) and \
                    self.t_lead + self.plan[self.next]["due"] <= now:
                self._send(self.t_lead + self.plan[self.next]["due"])
            return
        # callers join one after another during the lead-in
        clients = int(self.mix["clients"])
        while self.started_clients < clients and \
                self.t_lead + self.started_clients * self.stagger <= now:
            self.started_clients += 1
            self._send(now)
        while self.free_clients_ready():
            self._send(now)

    def free_clients_ready(self) -> bool:
        outstanding = len(self.sent) - len(self.records)
        return outstanding < self.started_clients

    def collect(self) -> None:
        """Take finished requests' records and tokens out of the engine
        as they retire."""
        stats = self.engine.request_stats
        for rid in [r for r in self.sent
                    if r not in self.records and r in stats]:
            rec = stats.pop(rid)
            rec["tokens_out"] = np.asarray(
                self.engine.results.pop(rid, ()), np.int32)
            rec["seen"] = time.perf_counter()
            self.records[rid] = rec


def drive(ctx: dict, engine, mix: dict, plan: list, tracer) -> dict:
    """Lead-in, window, traced slice, grace.  Returns what was observed,
    on the host's clock."""
    from paddle_tpu.utils import compile_counter
    seconds = float(ctx["seconds"])
    lead = float(mix["lead_in_s"])
    trace_s = float(mix.get("trace_s", 2.0)) if tracer is not None else 0.0
    grace = float(mix["grace_s"])
    t_lead = time.perf_counter()
    t0, t_end = t_lead + lead, t_lead + lead + seconds
    t_stop = t_end + trace_s              # no request is sent after this
    load = Load(engine, mix, plan, t_lead, t_stop + grace)
    produced_in_window = 0
    outstanding = {}                      # sent and not finished, by time
    stats0 = stats1 = None
    snap = None
    tracing = False
    trace = None
    while True:
        now = time.perf_counter()
        if stats0 is None and now >= t0:
            stats0 = dict(engine.stats)
            snap = compile_counter.snapshot()
            t0_real = now
        if "mid" not in outstanding and now >= t0 + seconds / 2:
            outstanding["mid"] = len(load.sent) - len(load.records)
        if stats1 is None and now >= t_end:
            outstanding["end"] = len(load.sent) - len(load.records)
            stats1 = dict(engine.stats)
            compiles, traces = snap.new_compiles, snap.new_traces
            t_end_real = now
            if tracer is not None:
                tracer.start()
                tracing = True
        if tracing and now >= t_stop:
            trace = tracer.stop()
            tracing = False
            now = time.perf_counter()
        if now >= t_stop and not tracing and not engine.has_work:
            break
        load.offer(now, sending=now < t_stop)
        if engine.has_work:
            produced = engine.step_or_raise()
            if stats0 is not None and stats1 is None:
                produced_in_window += produced
            load.collect()
        else:
            time.sleep(0.001)
    load.collect()
    return {"load": load, "t0": t0_real, "t_end": t_end_real,
            "produced": produced_in_window, "stats0": stats0,
            "stats1": stats1, "compiles": compiles, "traces": traces,
            "outstanding": outstanding,
            "trace": trace}


def reduce_window(run: dict) -> dict:
    """The window's populations, from the records."""
    load, t0, t_end = run["load"], run["t0"], run["t_end"]
    ttft, late, admit_to_first, gaps_in = [], [], [], []
    attempted = failed = finished = 0
    for rid, sent in load.sent.items():
        rec = load.records.get(rid)
        in_window = t0 <= sent["due"] < t_end
        if in_window:
            attempted += 1
            lateness_ms = max(sent["added"] - sent["due"], 0.0) * 1e3
            late.append(lateness_ms)
            if rec is None or rec["ttft_ms"] is None:
                failed += 1
            else:
                ttft.append(rec["ttft_ms"] + lateness_ms)
                admit_to_first.append(rec["ttft_ms"] - rec["queued_ms"])
                finished += int(not rec["timed_out"])
        if rec is None or rec["ttft_ms"] is None:
            continue
        # delivery times of this request's tokens, on the host's clock
        t = sent["added"] + rec["ttft_ms"] * 1e-3
        for g in rec["itl_gaps_ms"]:
            t += g * 1e-3
            if t0 <= t < t_end:
                gaps_in.append(g)
    return {"attempted": attempted, "failed": failed, "finished": finished,
            "ttft_ms": ttft, "late_ms": late,
            "admit_to_first_ms": admit_to_first, "itl_gaps_ms": gaps_in}


def sample_for_check(load: Load, seed: int, n: int) -> list:
    """Requests the run finished on their own (not cut by the run's end),
    the longest among them and n-1 more drawn from the seed."""
    done = [rid for rid, rec in load.records.items()
            if not rec["timed_out"] and len(rec["tokens_out"]) > 0]
    if not done:
        return []
    size = lambda rid: (len(load.plan[load.sent[rid]["index"]]["prompt"])
                        + len(load.records[rid]["tokens_out"]))
    longest = max(done, key=size)
    rest = [r for r in done if r != longest]
    rng = weights_mod.host_rng(seed, 4)
    picks = list(rng.choice(rest, size=min(n - 1, len(rest)), replace=False))
    return [longest] + [int(r) for r in picks]


def logit_deficits(ref_mod, model_kw, stacked, prompt, tokens, pad_to,
                   precision="float32", against=None):
    """For every served token, how far the reference's logit of it lies
    below the reference's best, from ONE forward over prompt + tokens.
    With `against` (the float32 logits), the same gap for the token that
    THIS precision puts first: the control's reading."""
    seq = [int(t) for t in prompt] + [int(t) for t in tokens[:-1]]
    ids = np.zeros(pad_to, np.int32)       # causal: the padding is unseen
    ids[:len(seq)] = seq
    lg = np.asarray(ref_mod.logits(model_kw, stacked, ids, precision),
                    np.float32)[len(prompt) - 1:len(seq)]
    if against is None:
        best = lg.max(-1)
        return best - lg[np.arange(len(tokens)), np.asarray(tokens)], lg
    first = lg.argmax(-1)
    return against.max(-1) - against[np.arange(len(first)), first], lg


def check_served(ctx, config, flat, load: Load, check: harness.Check):
    """The served tokens of a seeded sample against the plain forward
    over the same weights (upcast), after the engine is freed."""
    ref_mod = importlib.import_module(config["reference"])
    model_kw = config["model"]["kwargs"]
    chk = config["check"]
    t_ref = time.perf_counter()
    stacked = ref_mod.stack(flat, model_kw)
    worst, checked, distinct = 0.0, 0, set()
    picks = sample_for_check(load, ctx["seed"], int(chk["sample_requests"]))
    for rid in picks:
        prompt = load.plan[load.sent[rid]["index"]]["prompt"]
        tokens = load.records[rid]["tokens_out"]
        deficits, _ = logit_deficits(ref_mod, model_kw, stacked, prompt,
                                     tokens, int(chk["pad_to"]))
        worst = max(worst, float(deficits.max()))
        checked += len(tokens)
        distinct.update(int(t) for t in tokens)
    del stacked
    check.at_most("served_logit_deficit_max", worst if picks else None,
                  chk["limits"]["logit_deficit"])
    check.at_least("distinct_token_share",
                   len(distinct) / checked if checked else None,
                   chk["limits"]["distinct_share"])
    say("reference", seconds=time.perf_counter() - t_ref,
        requests=len(picks), tokens_checked=checked)


def run(ctx: dict) -> dict:
    jax, devices = ctx["jax"], ctx["devices"]
    config, mix, seed = ctx["config"], ctx["mix"], ctx["seed"]
    from paddle_tpu.ops import kernel_paths
    model_kw = config["model"]["kwargs"]
    ref_mod = importlib.import_module(config["reference"])
    horizon = float(mix["lead_in_s"]) + float(ctx["seconds"]) + \
        float(mix.get("trace_s", 2.0)) + 1.0
    plan = trafficgen.requests(mix, model_kw["vocab_size"], seed, horizon)
    t_w = time.perf_counter()
    flat = weights_mod.make_weights(seed, ref_mod.param_spec(model_kw),
                                    config["init"], config["dtype"])
    jax.block_until_ready(flat)
    say("setup", before_weights_s=t_w - ctx["t_process_start"],
        weights_s=time.perf_counter() - t_w)
    kernel_paths.reset()
    engine = ctx.get("build_engine", build_engine)(config, flat)
    tracer = harness.Tracer(jax, ctx["workload"]) if ctx["trace"] else None

    run_ = drive(ctx, engine, mix, plan, tracer)
    setup_s = run_["t0"] - ctx["t_process_start"]
    peak = harness.memory_peak(devices)
    paths = {key: dict(v) for key, v in
             getattr(engine, "kernel_paths", {}).items()}
    win = reduce_window(run_)
    window_s = run_["t_end"] - run_["t0"]
    d = {k: run_["stats1"][k] - run_["stats0"][k]
         for k in ("decode_ms", "sync_ms", "decode_steps", "occupancy_sum",
                   "tokens_generated")}
    say("window", window_s=window_s, requests_in_window=win["attempted"],
        finished_in_window=win["finished"], ttft_samples=len(win["ttft_ms"]),
        itl_gap_samples=len(win["itl_gaps_ms"]),
        tokens_delivered=run_["produced"], decode_steps=d["decode_steps"],
        requests_sent=len(run_["load"].sent),
        outstanding_mid=run_["outstanding"].get("mid"),
        outstanding_end=run_["outstanding"].get("end"),
        ttft_ms={f"p{q}": harness.percentile(win["ttft_ms"], q)
                 for q in (50, 80, 90)} if win["ttft_ms"] else None,
        itl_ms={f"p{q}": harness.percentile(win["itl_gaps_ms"], q)
                for q in (50, 90, 95, 99)} if win["itl_gaps_ms"] else None)

    check = harness.Check()
    check.at_most("compiles_in_window", run_["compiles"], 0)
    check.at_most("failed_requests", win["failed"], 0)
    release(engine)
    del engine
    check_served(ctx, config, flat, run_["load"], check)

    e2e = {"setup_s": setup_s,
           "serve_tokens_per_s": run_["produced"] / window_s}
    if win["itl_gaps_ms"]:
        e2e["itl_p95_ms"] = harness.percentile(win["itl_gaps_ms"], 95)
    return {
        "check": check, "attempted": win["attempted"],
        "failed": win["failed"], "end_to_end": e2e,
        "memory_peak_bytes": peak, "trace": run_["trace"],
        "obs": {"kind": "serve", "window": win, "engine_delta": d,
                "traces_in_window": run_["traces"],
                "kernel_paths": paths, "memory_peak_bytes": peak,
                "trace": run_["trace"], "peaks": ctx["peaks"]},
    }
