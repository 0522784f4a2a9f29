"""Live retune tier: PADDLE_TPU_AUTOTUNE=live.

A fleet flagged by the SLO monitor should re-tune itself instead of
paging someone at 3am — but a LIVE replica is not a bench harness, so
the live tier is deliberately narrower than the offline controller:

- **edge-triggered, one episode per signal**: the SLO monitor's
  regression verdict SCHEDULES an episode; a still-regressed monitor on
  the next scrape does not schedule another (the latch resets only
  after a healthy verdict), and a cooldown bounds episode frequency
  even across distinct signals.  No retrigger storm.
- **quiesced-replica measurement**: the pending episode runs from the
  engine's tick hook only when the replica has NO active slots and an
  empty queue — trials never steal decode-step time from real traffic.
- **hot-apply, table-only knobs**: the episode re-measures the
  per-bucket prefill cost on the ALREADY-WARMED executables and
  re-merges the engine's prefill bucket list (the same pad-up rule as
  bench.py's offline sweep).  The bucket list is host-side state
  (``engine.buckets`` feeds ``_bucket_for``), and the merged list is a
  SUBSET of the warmed one — applying it is a plain attribute write:
  no restart, no retrace, no recompile.  Winners persist to the tuning
  table (op ``prefill_buckets``) with autotune provenance so the next
  process boots tuned.
- **rails**: the episode runs under the flight recorder; any failure
  inside it keeps the incumbent bucket list and dumps an
  ``autotune-rollback`` bundle.

The trainer-side sibling (:class:`TrainerRetuner`) is ADVISORY: train
knobs that matter (remat policy, quantize) retrace by nature, so a live
trainer never mutates them mid-run — on a sustained step-time
regression it runs the doctor once over the host-side timing surfaces
and ships the ranked verdicts (structured actions included) as a
flight-recorder event for the offline controller to act on.
"""
from __future__ import annotations

import time
from typing import List, Optional

import numpy as np

from ..observability import flightrec as _flightrec
from ..utils import tuning as _tuning

__all__ = ["LiveRetuner", "TrainerRetuner", "arm_engine", "arm_trainer"]

# offline sweep's merge rule (bench.py _sweep_prefill_buckets): keep a
# bucket only when using it beats padding up to the next kept bucket by
# this factor
PAD_UP_FACTOR = 1.25
# a merged list must cut the average measured prefill cost by more than
# this fraction to be applied — the live noise floor
LIVE_NOISE_FLOOR = 0.02


class LiveRetuner:
    """SLO-triggered, quiesce-gated prefill-bucket retuner for a
    serving engine (see module docstring for the contract)."""

    def __init__(self, engine, *, cooldown_s: float = 300.0,
                 noise_floor: float = LIVE_NOISE_FLOOR,
                 repeats: int = 3):
        self.engine = engine
        self.cooldown_s = float(cooldown_s)
        self.noise_floor = float(noise_floor)
        self.repeats = max(1, int(repeats))
        self.episodes = 0
        self.applied: List[dict] = []
        self._pending = False
        self._latched = False           # signal seen, not yet healthy
        self._last_episode_t: Optional[float] = None

    # -- signal side ----------------------------------------------------
    def notify_slo(self, verdict: dict) -> bool:
        """Feed one SLOMonitor.check() verdict; returns True when this
        call scheduled an episode.  Edge-triggered with a healthy-reset
        latch + wall-clock cooldown: a regressed monitor re-checked
        every scrape schedules exactly ONE episode."""
        bad = bool(verdict.get("regressed") or verdict.get("breached"))
        if not bad:
            self._latched = False
            return False
        if self._latched:
            return False
        self._latched = True
        now = time.monotonic()
        if self._last_episode_t is not None and \
                now - self._last_episode_t < self.cooldown_s:
            return False
        self._pending = True
        _flightrec.note_event("autotune_live_scheduled",
                              p99_ms=verdict.get("p99_ms"),
                              regressed=bool(verdict.get("regressed")),
                              breached=bool(verdict.get("breached")))
        return True

    # -- engine side ----------------------------------------------------
    def on_tick(self) -> bool:
        """Engine.step() hook: O(1) when nothing is pending; runs the
        scheduled episode only on a quiesced replica (no active slots,
        empty queue — trials never displace traffic)."""
        if not self._pending:
            return False
        eng = self.engine
        if eng.num_active or len(getattr(eng, "_queue", ())):
            return False
        self._pending = False
        self._last_episode_t = time.monotonic()
        try:
            self._episode()
        except Exception as e:          # a retune must NEVER kill serving
            _flightrec.dump("autotune-rollback",
                            extra={"autotune": {
                                "tier": "live",
                                "reason": "episode-error",
                                "error": f"{type(e).__name__}: {e}"}})
        return True

    def _episode(self) -> None:
        """One retune episode: time warmed prefill buckets, re-merge,
        hot-apply an improved subset, persist with provenance."""
        self.episodes += 1
        eng = self.engine
        old = list(eng.buckets)
        _flightrec.note_event("autotune_live_episode",
                              episode=self.episodes, buckets=old)
        times = self._time_buckets(old)
        kept = self._merge(old, times)
        old_cost = self._mean_cost(old, times)
        new_cost = self._mean_cost(kept, times)
        improvement = 0.0 if old_cost <= 0 else \
            (old_cost - new_cost) / old_cost
        if kept != old and improvement > self.noise_floor:
            # subset of warmed buckets -> pure host-side table write:
            # this is the hot-apply (no restart, no recompile)
            eng.buckets = kept
            rec = {"old": old, "new": kept,
                   "improvement": round(improvement, 6),
                   "times_ms": {str(b): round(t, 3)
                                for b, t in times.items()}}
            self.applied.append(rec)
            _flightrec.note_event("autotune_live_applied", **rec)
            try:
                _tuning.record(
                    "prefill_buckets",
                    (_tuning.device_kind(), eng.max_seq_len), kept,
                    source="autotune", run=f"live-{self.episodes}",
                    improvement=improvement)
            except Exception:
                pass                    # persistence is best-effort
        else:
            _flightrec.note_event("autotune_live_noop",
                                  episode=self.episodes,
                                  improvement=round(improvement, 6))

    # -- measurement ----------------------------------------------------
    def _time_buckets(self, buckets) -> dict:
        """Median wall time of each warmed bucket's prefill executable
        (mirrors bench.py's offline sweep, but on the LIVE engine's
        already-compiled functions — zero compiles by construction)."""
        import jax.numpy as jnp
        eng = self.engine
        out = {}
        for b in buckets:
            ids = jnp.zeros((1, b), jnp.int32)
            samples = []
            for _ in range(self.repeats):
                t0 = time.perf_counter()
                if eng.kv_layout == "paged":
                    from ..inference.paged_kv import blocks_for
                    n = blocks_for(b, eng.block_size)
                    blocks = eng._alloc.alloc(n)
                    if blocks is None:  # pool busier than quiesce said
                        raise RuntimeError("no free blocks for trial")
                    row = np.zeros(eng.blocks_per_slot, np.int32)
                    row[:n] = blocks
                    try:
                        logits, cache, _ = eng._prefill_paged_cold_jit(
                            eng.params, eng.cache, ids,
                            jnp.asarray(row), np.int32(1))
                        eng.cache = cache
                    finally:
                        eng._alloc.decref(blocks)
                else:
                    logits, cache, _ = eng._prefill_jit(
                        eng.params, eng.cache, ids, np.int32(0),
                        np.int32(1))
                    eng.cache = cache
                logits.block_until_ready()
                samples.append((time.perf_counter() - t0) * 1e3)
            out[b] = float(np.median(samples))
        if eng.kv_layout != "paged":
            # drop the trial garbage exactly like engine.warmup(): zero
            # every slot length so the junk written at slot 0 stays
            # masked (host-side constant, no new executable)
            eng.cache = eng.cache.with_lengths(
                jnp.zeros((eng.batch_slots,), jnp.int32))
        return out

    @staticmethod
    def _merge(buckets, times) -> list:
        """bench.py's _sweep_prefill_buckets rule: walk small→large,
        keep a bucket only when the previously-kept (smaller) bucket is
        more than PAD_UP_FACTOR cheaper — i.e. drop buckets whose
        marginal win doesn't pay for their executable."""
        order = sorted(buckets)
        kept = [order[-1]]              # the largest must stay (capacity)
        for b in reversed(order[:-1]):
            nxt = kept[0]
            if times[b] * PAD_UP_FACTOR < times[nxt]:
                kept.insert(0, b)
        return kept

    @staticmethod
    def _mean_cost(kept, times) -> float:
        """Expected prefill cost under uniform prompt lengths: each
        length pays the cheapest kept bucket that fits it, weighted by
        the fraction of lengths that land in it."""
        ks = sorted(kept)
        total, lo = 0.0, 0
        top = ks[-1]
        for b in ks:
            total += times[b] * (b - lo) / top
            lo = b
        return total


class TrainerRetuner:
    """Advisory live tier for SpmdTrainer: detect a sustained step-time
    regression from the host-side step timer (no device sync), run the
    doctor ONCE over the trainer's timing surfaces, and ship the ranked
    verdicts — structured actions included — as a flightrec event.  One
    episode per regression signal (healthy-reset latch), cooldown in
    steps."""

    def __init__(self, trainer, *, window: int = 32,
                 factor: float = 1.5, cooldown_steps: int = 256):
        self.trainer = trainer
        self.window = int(window)
        self.factor = float(factor)
        self.cooldown_steps = int(cooldown_steps)
        self.episodes = 0
        self.last_advice: Optional[list] = None
        self._recent: List[float] = []
        self._baseline_ms: Optional[float] = None
        self._steps = 0
        self._latched = False
        self._last_episode_step: Optional[int] = None

    def on_step(self, step_ms: Optional[float]) -> bool:
        """Per-step hook (host arithmetic only). Returns True when this
        step fired an advisory episode."""
        self._steps += 1
        if step_ms is None:
            return False
        self._recent.append(float(step_ms))
        if len(self._recent) > self.window:
            self._recent.pop(0)
        if len(self._recent) < self.window:
            return False
        med = float(np.median(self._recent))
        if self._baseline_ms is None:
            self._baseline_ms = med     # first full window is the record
            return False
        self._baseline_ms = min(self._baseline_ms, med)
        if med <= self._baseline_ms * self.factor:
            self._latched = False
            return False
        if self._latched:
            return False
        self._latched = True
        if self._last_episode_step is not None and \
                self._steps - self._last_episode_step < \
                self.cooldown_steps:
            return False
        self._last_episode_step = self._steps
        self._episode(med)
        return True

    def _episode(self, median_ms: float) -> None:
        self.episodes += 1
        t = dict(getattr(self.trainer, "_timings", {}) or {})
        stats = {k: t.get(k) for k in
                 ("dispatch_ms", "sync_ms", "data_wait_ms", "h2d_ms",
                  "steps_timed") if t.get(k) is not None}
        from ..observability import doctor as _doctor
        try:
            self.last_advice = _doctor.diagnose(stats, "train")
        except Exception:
            self.last_advice = []
        _flightrec.note_event(
            "autotune_train_advice", episode=self.episodes,
            median_step_ms=round(median_ms, 3),
            baseline_step_ms=round(self._baseline_ms or 0.0, 3),
            advice=self.last_advice[:3])


def arm_engine(engine) -> Optional[LiveRetuner]:
    """Construct + attach a LiveRetuner when PADDLE_TPU_AUTOTUNE=live
    (engine ctor calls this; returns the retuner or None)."""
    from . import autotune_mode
    if autotune_mode() != "live":
        return None
    return LiveRetuner(engine)


def arm_trainer(trainer) -> Optional[TrainerRetuner]:
    """Trainer-side arming under the same env tier."""
    from . import autotune_mode
    if autotune_mode() != "live":
        return None
    return TrainerRetuner(trainer)
