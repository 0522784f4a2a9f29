"""High-throughput serving engine: two executables + continuous batching.

The training side of this repo got its fast path in PRs 1-3 (fused
kernels, async dispatch, persistent compile cache); this module is the
same discipline for inference, built from three papers:

- Pope et al., *Efficiently Scaling Transformer Inference*: ONE compiled
  **prefill** executable per prompt-length bucket writing into a
  statically-shaped, preallocated KV cache, and ONE compiled **decode**
  executable appending a single token per slot and running the fused
  single-token attention kernel (``ops.decode_attention``) over the
  cache.  Nothing in the decode loop ever changes shape, so generating N
  tokens costs ZERO new XLA compiles (the contract the engine tests
  assert via utils.compile_counter, and ``benchmark/run.py`` on every
  serving run as ``compiles_in_window``).
- Yu et al., *Orca*: **continuous batching** — the decode batch is a set
  of fixed ``batch_slots``; new requests are admitted into free slots
  BETWEEN decode steps, and finished requests retire their slot
  immediately instead of making short requests wait for the longest one
  in a static batch.
- Kwon et al., *PagedAttention* (vLLM): with ``kv_layout='paged'`` the
  cache is a BLOCK POOL (``inference.paged_kv.PagedKVCache``) and each
  slot holds a block table, so a slot consumes memory proportional to
  its ACTUAL length — admission is by free-block count, not free slots,
  and short requests no longer strand ``max_seq`` rows each.  A radix
  prefix cache (``inference.prefix_cache``) shares prompt-prefix blocks
  between requests so common system prompts prefill once; on pool
  exhaustion the scheduler first evicts unpinned cache blocks, then
  PREEMPTS the youngest request back onto the queue (it resumes later
  via a prefill over prompt+generated — which usually hits the radix
  cache) instead of deadlocking.  ``kv_layout='dense'`` (default) is
  the slot-addressed ``StaticKVCache``: one head-major
  ``[slots, kv_heads, max_seq, head_dim]`` buffer per layer for k and
  for v, each donated to every executable and written in place.

Sampling (greedy / temperature / top-k / top-p) runs inside the decode
executable, so each step costs exactly one host read-back — the sampled
token ids the scheduler needs for EOS retirement and admission (counted
by distributed.async_dispatch's host-sync counter, same as training).

Both executables go through the persistent XLA compile cache
(utils.compile_cache), so a server restart deserializes instead of
recompiling.  The cache operands are donated on every backend, so the
cache updates are true in-place writes (``donate=False`` /
``PADDLE_TPU_INFER_DONATE=0`` turns it off).

Chunked prefill (ISSUE 20, Agrawal et al., *Sarathi-Serve*): the
monolithic bucketed prefill above runs BETWEEN decode ticks, so one
long admission freezes every in-flight stream — the classic
prefill/decode interference.  ``PADDLE_TPU_CHUNKED_PREFILL=<chunk>``
(engine kwarg ``prefill_chunk=``) switches admission to a token
budget: each tick advances every still-prefilling slot by up to
``chunk`` prompt tokens total through ONE fixed-shape chunk executable
(the PR-10 window-attention machinery with W = chunk), alongside —
never instead of — the decode batch.  A slot GRADUATES to decode when
its prompt completes; until then it is excluded from the decode/spec
active set.  Inter-token latency at the tail is bounded by the chunk
size instead of the longest prompt, throughput stays within noise
(same tokens, same executables count), and the zero-recompile
discipline survives because the chunk executable's shapes never
change.  Greedy output is token-identical to unchunked across
dense/paged × fp/int8 × GQA.

Knobs: ``PADDLE_TPU_DECODE_SLOTS`` (default 8),
``PADDLE_TPU_PREFILL_BUCKETS`` (comma-separated lengths; default powers
of two up to max_seq_len), ``PADDLE_TPU_KV_LAYOUT`` (dense|paged),
``PADDLE_TPU_KV_BLOCK_SIZE`` (default 128), ``PADDLE_TPU_KV_BLOCKS``
(usable pool blocks; default = dense-equivalent memory),
``PADDLE_TPU_PREFIX_CACHE`` (default on for paged),
``PADDLE_TPU_CHUNKED_PREFILL`` (chunk size; 0 = monolithic prefill,
the default), and ``PADDLE_TPU_KV_DTYPE`` (int8|fp8; quantized KV
storage with per-head scales dequantized inside the decode kernels —
half the HBM bytes per step; default full precision).
"""
from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..distributed import async_dispatch
from ..distributed import moe as _moe
from ..func import functional_apply, functional_state
from ..observability import capture as _capture
from ..observability import doctor as _doctor
from ..observability import exec_registry as _exec_registry
from ..observability import flightrec as _flightrec
from ..observability import metrics as _metrics
from ..observability import spans as _spans
from ..observability import watchdog as _watchdog
from ..ops import kernel_paths as _kernel_paths
from ..ops.decode_attention import positions_streamed as _positions_streamed
from ..utils import compile_cache, compile_counter
from .paged_kv import (BlockAllocator, blocks_for, blocks_to_extend,
                       init_paged_cache)
from .prefix_cache import RadixPrefixCache

__all__ = ["InferenceEngine", "Request", "default_prefill_buckets"]


def default_prefill_buckets(max_seq_len: int, lo: int = 16) -> List[int]:
    """Powers of two in [lo, max_seq_len], always including max_seq_len.
    ``PADDLE_TPU_PREFILL_BUCKETS="64,256,1024"`` overrides."""
    env = os.environ.get("PADDLE_TPU_PREFILL_BUCKETS", "").strip()
    if env:
        bks = sorted({int(x) for x in env.split(",") if x.strip()})
    else:
        bks = []
        b = lo
        while b < max_seq_len:
            bks.append(b)
            b *= 2
        bks.append(max_seq_len)
    return [b for b in bks if b <= max_seq_len] or [max_seq_len]


class _NotedLater:
    """Stands in for the ``tick`` span of a decode tick launched ahead
    of its own step() call: what _note_active would note is kept for the
    span of the call that reads the tick."""

    def __init__(self):
        self.args = {}
        self.occupancy = 0.0    # counted with the tick, by that call

    def note(self, **more):
        self.args.update(more)


class Request:
    """One in-flight generation request (host-side bookkeeping)."""

    _ids = itertools.count()

    def __init__(self, prompt, max_new_tokens, eos_id, temperature, top_p,
                 deadline_s: Optional[float] = None):
        self.rid = next(Request._ids)
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError("empty prompt")
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = -1 if eos_id is None else int(eos_id)
        self.temperature = float(temperature)
        self.top_p = float(top_p)
        self.generated: List[int] = []
        self.slot: Optional[int] = None
        self.done = False
        # per-request deadline (absolute perf_counter time): a request
        # past it is RETIRED — slot + blocks freed, partial tokens
        # delivered, record flagged timed_out — instead of occupying a
        # decode slot (or the queue) forever
        self.deadline: Optional[float] = None \
            if deadline_s is None \
            else time.perf_counter() + float(deadline_s)
        self.timed_out = False
        # per-request latency accounting (stats / load harness)
        self.t_enqueue = time.perf_counter()
        self.t_admit: Optional[float] = None
        self.t_first: Optional[float] = None
        self.t_finish: Optional[float] = None
        # decode wall-clock summed over ACTIVATIONS only (a preempted
        # request's requeue wait must not dilute its decode tok/s),
        # and queue wait summed over WAITS only (symmetrically, active
        # decode time must not inflate queued_ms)
        self.active_s = 0.0
        self.t_live: Optional[float] = None
        self.queued_s = 0.0
        self.t_queue_since = self.t_enqueue
        # preemption support: a preempted request resumes via a prefill
        # over prompt+generated-so-far (this field), keeping `generated`
        self.resume_prompt: Optional[np.ndarray] = None
        self.preemptions = 0
        self.admit_seq: Optional[int] = None
        # chunked prefill (ISSUE 20): a slot holds its request while
        # the prompt prefills chunk by chunk; `prefill_pos` is how many
        # prompt tokens are in the cache, `prefilling` keeps the slot
        # out of the decode/spec active set until graduation
        self.prefill_pos = 0
        self.prefilling = False
        # per-token delivery timestamps (first token + every commit):
        # the inter-token-latency record the load harness pools
        self.token_times: List[float] = []

    def effective_prompt(self) -> np.ndarray:
        return self.prompt if self.resume_prompt is None \
            else self.resume_prompt


class InferenceEngine:
    """Continuous-batching serving engine for GPTForCausalLM.

    Telemetry (ISSUE 13): every engine feeds the process metrics
    registry (labeled ``engine=eN``) and, when the span tracer is armed,
    emits the per-request lifecycle timeline — ``queued`` → ``prefill``
    → ``decode`` spans on a per-request track plus per-tick spans
    (preemptions as instants, speculative accept counts as tick args).
    All of it is host-side timestamp arithmetic: telemetry adds ZERO
    host syncs per tick and never perturbs executable shapes
    (zero-recompile preserved — proven in tests/test_telemetry.py).

    Usage::

        eng = InferenceEngine(model, batch_slots=8, kv_layout="paged")
        rid = eng.add_request(prompt_ids, max_new_tokens=64, eos_id=eos)
        outputs = eng.run()          # {rid: np.int32 generated tokens}

    or incrementally: ``eng.step()`` admits queued requests into free
    slots and decodes one token for every active slot; finished
    requests appear in ``eng.results``.  ``eng.generate(prompt)`` is the
    blocking single-request form: it goes through the same admission
    queue, so on a full engine it WAITS for capacity instead of raising.
    """

    _engine_ids = itertools.count()

    def __init__(self, model, batch_slots: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 prefill_buckets: Optional[List[int]] = None,
                 cache_dtype=None, top_k: int = 0, seed: int = 0,
                 mesh=None, donate: Optional[bool] = None,
                 kv_layout: Optional[str] = None,
                 kv_block_size: Optional[int] = None,
                 kv_num_blocks: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 kv_dtype: Optional[str] = None,
                 spec_k: Optional[int] = None, draft_model=None,
                 prefill_chunk: Optional[int] = None):
        model.eval()
        self.model = model
        cfg = model.cfg
        self.batch_slots = int(batch_slots or
                               os.environ.get("PADDLE_TPU_DECODE_SLOTS", 8))
        self.max_seq_len = int(max_seq_len or cfg.max_seq_len)
        if self.max_seq_len > cfg.max_seq_len:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} exceeds the model's "
                f"position table ({cfg.max_seq_len})")
        self.buckets = sorted(prefill_buckets or
                              default_prefill_buckets(self.max_seq_len))
        self.top_k = int(top_k)
        self.kv_layout = (kv_layout or
                          os.environ.get("PADDLE_TPU_KV_LAYOUT", "dense"))
        if self.kv_layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout must be dense|paged, got "
                             f"{self.kv_layout!r}")
        # quantized KV storage ('int8'/'fp8'; env PADDLE_TPU_KV_DTYPE):
        # halves the bytes every decode step streams from HBM.  None =
        # full-precision cache, the default and the parity oracle.
        from ..ops.quantized_matmul import resolve_kv_quant
        self.kv_dtype = resolve_kv_quant(kv_dtype)
        # chunked prefill (ISSUE 20; env PADDLE_TPU_CHUNKED_PREFILL):
        # 0/unset keeps the monolithic bucketed admission prefill
        if prefill_chunk is None:
            env = os.environ.get("PADDLE_TPU_CHUNKED_PREFILL",
                                 "").strip()
            prefill_chunk = int(env) if env else 0
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0, got "
                             f"{self.prefill_chunk}")
        self._chunked = self.prefill_chunk > 0
        from .spec_decode import SpecDecoder, resolve_spec_k
        # what the model's cache holds, asked of the cache itself (its
        # shapes alone: nothing is allocated): rows of keys and values,
        # a per-slot recurrent state, or layers of both
        held = jax.eval_shape(lambda: model.init_kv_cache(
            self.batch_slots, self.max_seq_len))
        self._cache_has_rows = bool(getattr(held, "has_rows", True))
        self._cache_holds_state = bool(getattr(held, "holds_state", False))
        if self._cache_holds_state:
            # a recurrent state is valid at one position: nothing to
            # page, to share by prefix, to roll back or to extend a
            # window over, no rows to quantize, no KV heads to shard
            asked = {"kv_layout='paged'": self.kv_layout == "paged",
                     "prefix_cache": bool(prefix_cache),
                     "spec_k": resolve_spec_k(spec_k) > 0,
                     "prefill_chunk": self._chunked,
                     "kv_dtype": self.kv_dtype is not None,
                     "mesh": mesh is not None}
            for option, on in asked.items():
                if on:
                    raise ValueError(
                        f"{type(model).__name__} serves from a per-slot "
                        f"recurrent state"
                        f"{' beside its' if self._cache_has_rows else ' with no'}"
                        f" rows of keys and values: {option} is not "
                        f"supported for it")

        # persistent compile cache: a restarted server deserializes its
        # prefill/decode executables instead of recompiling them
        compile_cache.ensure_compile_cache()
        compile_counter.install()

        self.params, _ = functional_state(model)
        # serving mesh (ISSUE 18): explicit arg, else PADDLE_TPU_SERVE_TP=N
        # builds a {"dp": 1, "tp": N} mesh.  Every serving executable then
        # compiles SPMD over it — weights column/row-split by the pspecs
        # the training-side parallel layers already mark, KV heads over
        # 'tp', dense batch slots over 'dp' — with no model-code changes:
        # GSPMD follows the committed operand shardings.
        if mesh is None:
            env_tp = os.environ.get("PADDLE_TPU_SERVE_TP", "").strip()
            # expert parallelism (ISSUE 19): PADDLE_TPU_SERVE_EP=N adds
            # an 'ep' axis — MoE expert FFN weights shard over it and
            # the MoE serving dispatch routes tokens with explicit
            # chunked all-to-all (distributed.moe._fn_serve_ep)
            env_ep = os.environ.get("PADDLE_TPU_SERVE_EP", "").strip()
            tp = int(env_tp) if env_tp else 1
            ep = int(env_ep) if env_ep else 1
            if tp > 1 or ep > 1:
                from ..distributed.mesh import create_mesh
                axes = {"dp": 1, "tp": tp}
                if ep > 1:
                    axes["ep"] = ep
                mesh = create_mesh(axes)
        self.mesh = mesh
        self.tp_degree = int(mesh.shape["tp"]) \
            if mesh is not None and "tp" in mesh.axis_names else 1
        self.ep_degree = int(mesh.shape["ep"]) \
            if mesh is not None and "ep" in mesh.axis_names else 1
        self._shard_warned = False
        # a mesh engine's cache is born on the HOST and goes straight to
        # its shards: built on the default device first, the whole
        # unsharded cache would have to fit one chip beside the weights
        # (gpt3-1.3b float32 at tp=4: 6 GiB beside 5 GiB — it did not,
        # PR 21's four-chip run)
        born_on = jax.default_device(jax.devices("cpu")[0]) \
            if mesh is not None else contextlib.nullcontext()
        with born_on:
            if self.kv_layout == "paged":
                self._init_paged(cache_dtype, kv_block_size,
                                 kv_num_blocks, prefix_cache)
            else:
                self.cache = model.init_kv_cache(
                    self.batch_slots, self.max_seq_len, cache_dtype,
                    kv_dtype=self.kv_dtype)
                self._alloc = None
                self._prefix = None
        if mesh is not None:
            self._shard_over_mesh(mesh)

        if donate is None:
            donate = os.environ.get("PADDLE_TPU_INFER_DONATE", "1") != "0"
        self._donate = bool(donate)
        dargs = (1,) if self._donate else ()
        self._prefill_jit = jax.jit(self._prefill_fn, donate_argnums=dargs)
        self._decode_jit = jax.jit(self._decode_fn, donate_argnums=dargs)
        self._prefill_paged_cold_jit = jax.jit(
            self._prefill_paged_cold_fn, donate_argnums=dargs)
        self._prefill_paged_ext_jit = jax.jit(
            self._prefill_paged_ext_fn, donate_argnums=dargs)
        self._decode_paged_jit = jax.jit(
            self._decode_paged_fn, donate_argnums=dargs)
        self._prefill_chunk_jit = jax.jit(
            self._prefill_chunk_fn, donate_argnums=dargs)
        self._prefill_chunk_paged_jit = jax.jit(
            self._prefill_chunk_paged_fn, donate_argnums=dargs)
        self._sample_jit = jax.jit(self._sample_from_logits)
        self._place_jit = jax.jit(self._place_token_fn)

        # speculative decoding (inference.spec_decode): a draft model +
        # K>0 replace the single-token decode step with a propose/verify
        # tick committing ~K+1 tokens per host sync.  Greedy slots use
        # the temperature-0 acceptance rule (token-identical to the
        # non-speculative rollout); temperature>0 slots run the full
        # rejection-sampling residual (ISSUE 18 satellite), so sampled
        # traffic rides the spec path too.
        sk = resolve_spec_k(spec_k)
        self._spec = None
        if sk > 0:
            if draft_model is None:
                raise ValueError(
                    "spec_k/PADDLE_TPU_SPEC_K set but no draft_model "
                    "given — speculation needs a draft (the target "
                    "model itself is a valid, if pointless-on-paper, "
                    "draft for harnesses)")
            self._spec = SpecDecoder(self, draft_model, sk)
        self.spec_k = self._spec.k if self._spec else 0

        self._key = jax.random.PRNGKey(int(seed))
        if self.mesh is not None:
            # commit the sampling key to the mesh (replicated) at init:
            # the steady-state key is a mesh-committed jit output, and a
            # host-resident warmup key would recompile every key
            # consumer (split/sample/decode) on the first real step —
            # the jit cache keys on committed-vs-uncommitted shardings
            try:
                self._key = self._put(self.mesh, self._key, (None,))
            except Exception as e:
                self._shard_failed("rng_key", e)

        # scheduler state
        self._queue: deque = deque()
        self._slots: List[Optional[Request]] = [None] * self.batch_slots
        self._next_token = np.zeros(self.batch_slots, np.int32)
        self._slot_len = np.zeros(self.batch_slots, np.int64)
        self._temps = np.zeros(self.batch_slots, np.float32)
        self._top_ps = np.ones(self.batch_slots, np.float32)
        self._admit_counter = itertools.count()
        # head-of-line admission memo (ISSUE 20 bugfix): once the queue
        # head fails paged admission, remember (rid, free-block count,
        # release epoch) and skip re-running the whole radix-match +
        # alloc dance every tick until blocks could actually have come
        # free — the epoch catches frees that don't change num_free
        # (a retirement whose blocks are all radix-pinned still makes
        # them EVICTABLE, which a pure free-count gate would miss)
        self._hol_block: Optional[tuple] = None
        self._release_epoch = 0
        # chunk-tick expert-stats folds parked until the next real host
        # sync (folding per chunk tick would add a sync per tick)
        self._moe_pending: List = []
        self.results: Dict[int, np.ndarray] = {}
        self.request_stats: Dict[int, dict] = {}
        self._request_stats_cap = 4096     # bounded per-request history
        self._results_cap = 65536          # results eviction safety net

        # stats machinery (same shape as SpmdTrainer._timings/stats)
        self._timings = {
            "prefill_ms": 0.0, "decode_ms": 0.0, "sync_ms": 0.0,
            # decode-tick wall time lost to monolithic admission
            # prefills while other streams sat waiting — the
            # interference signal the 'prefill-stall' doctor rule reads
            # (identically 0 in chunked mode, where admission never
            # stalls the decode batch)
            "prefill_stall_ms": 0.0,
            "compile_ms_cold": 0.0, "prefills": 0, "prefill_tokens": 0,
            "decode_steps": 0, "tokens_generated": 0,
            # how often the chip was spared the host (_tick): decode
            # ticks dispatched while an earlier executable was unread
            # (ahead of a tick, or behind a prefill), and admissions
            # whose first token was read after the next tick's dispatch
            "ticks_launched_unread": 0, "admissions_read_late": 0,
            # decode ticks in which some slot had temperature > 0: the
            # sampler's own predicate (a retired slot's temps is 0)
            "sampled_ticks": 0,
            "occupancy_sum": 0.0, "block_occupancy_sum": 0.0,
            "preemptions": 0, "memory_capped_retirements": 0,
            "deadline_retirements": 0, "drain_forced_retirements": 0,
            "spec_ticks": 0, "spec_tokens_committed": 0,
            "spec_slot_ticks": 0, "spec_capacity_retirements": 0,
            "moe_assigned_tokens": 0.0, "moe_dropped_tokens": 0.0,
        }
        # expert-balance accumulators (ISSUE 19): the per-expert load
        # histogram summed over every executed step/prefill/tick, host
        # float64 so a long-lived server never loses counts to f32
        self._is_moe = int(getattr(cfg, "moe_num_experts", 0) or 0) > 0
        self._moe_load: Optional[np.ndarray] = None
        # graceful drain / preemption hookup (SIGTERM'd server finishes
        # what it started): while draining, admission is closed
        self._draining = False
        self._guard = None
        self._guard_timeout: Optional[float] = None
        # the decode tick launched one step ahead of its read, if any
        # (see _tick): what _launch_decode returned for it
        self._ahead = None
        # admissions of this call bound to their slots whose first token
        # is still on the device: (request, slot, token [1])
        self._fresh: List[tuple] = []
        self.undelivered: List[Request] = []
        self._first_call_keys: set = set()
        # executable key -> {kernel entry point: {"kernel": n,
        # "composite": m}} as traced for that executable (ops.kernel_paths)
        self.kernel_paths: dict = {}
        self._counters0 = compile_counter.snapshot()

        # unified telemetry (observability/): registry children bound
        # ONCE per engine (per-tick cost = attribute arithmetic), the
        # span tracer handle (gated on .active — one attr read when
        # off), and the PADDLE_TPU_PROFILE window keyed on decode ticks.
        self.telemetry_label = f"e{next(InferenceEngine._engine_ids)}"
        lbl = dict(engine=self.telemetry_label)
        # executable observatory + HBM ledger (ISSUE 15): every compiled
        # executable this engine builds joins the process registry under
        # this component label (see _timed_exec), and the resident state
        # — params, KV pool, draft cache — is tracked in the ledger
        # (host-side shape math, weakref'd to this engine so a retired
        # replica's pool drops out of the accounting)
        self._exec_component = f"engine:{self.telemetry_label}"
        _exec_registry.track_bytes(
            self, "params", self.telemetry_label,
            _exec_registry.tree_bytes(self.params))
        _exec_registry.track_bytes(
            self, "kv_cache", self.telemetry_label,
            _exec_registry.tree_bytes(self.cache),
            layout=self.kv_layout, kv_dtype=self.kv_dtype or "dense")
        if self._spec is not None:
            _exec_registry.track_bytes(
                self, "spec_draft", self.telemetry_label,
                _exec_registry.tree_bytes(self._spec.draft_params) +
                _exec_registry.tree_bytes(self._spec.draft_cache))
        if self._is_moe:
            # expert-parallel HBM win as a ledger line (ISSUE 19): the
            # "params" entry above is GLOBAL-shape math; this one is the
            # PER-DEVICE expert-weight residency, read off the committed
            # arrays' shard shapes — under ep>1 it drops ~ep× vs
            # replicated, and the acceptance test asserts exactly that
            _exec_registry.track_bytes(
                self, "moe_experts", self.telemetry_label,
                self._moe_expert_bytes_per_device(),
                ep=self.ep_degree,
                num_experts=int(cfg.moe_num_experts))
        self._tracer = _spans.tracer()
        self._profile = _capture.ProfileWindow.from_env(kind="serve")
        self._m_ticks = _metrics.counter(
            "serve_decode_ticks_total", "decode steps/ticks",
            labels=("engine",)).labels(**lbl)
        self._m_tokens = _metrics.counter(
            "serve_tokens_total", "generated tokens",
            labels=("engine",)).labels(**lbl)
        self._m_prefills = _metrics.counter(
            "serve_prefills_total", "admission prefills",
            labels=("engine",)).labels(**lbl)
        self._m_preempts = _metrics.counter(
            "serve_preemptions_total", "requests preempted to queue",
            labels=("engine",)).labels(**lbl)
        self._m_req_ok = _metrics.counter(
            "serve_requests_total", "finished requests",
            labels=("engine", "outcome")).labels(outcome="ok", **lbl)
        self._m_req_to = _metrics.counter(
            "serve_requests_total", "finished requests",
            labels=("engine", "outcome")).labels(outcome="timed_out",
                                                 **lbl)
        self._m_ttft = _metrics.histogram(
            "serve_ttft_ms", "enqueue -> first token",
            labels=("engine",)).labels(**lbl)
        self._m_queue = _metrics.gauge(
            "serve_queue_depth", "queued requests",
            labels=("engine",)).labels(**lbl)
        self._m_active = _metrics.gauge(
            "serve_active_slots", "occupied decode slots",
            labels=("engine",)).labels(**lbl)
        if self._cache_holds_state:
            _metrics.gauge(
                "serve_recurrent_state_bytes",
                "per-slot recurrent state held, as laid out",
                labels=("engine",)).labels(**lbl).set(
                    self.cache.held_state_bytes)
        # flight recorder + stall watchdog (observability): crash hooks
        # once per process; the watchdog thread appears on the first
        # tick only when PADDLE_TPU_WATCHDOG_S arms it, and an engine
        # with no work parks it (an idle server is not a stall)
        _flightrec.install()
        self.watchdog: Optional[_watchdog.Watchdog] = None
        self._wd_checked = False

    # ---- paged layout setup -------------------------------------------
    def _init_paged(self, cache_dtype, kv_block_size, kv_num_blocks,
                    prefix_cache):
        """Block pool + allocator + host block tables + radix cache.
        Default pool size is DENSE-EQUIVALENT memory (batch_slots ×
        ceil(max_seq/bs) blocks) so the layouts A/B at equal footprint;
        real deployments size it to the HBM actually available
        (``PADDLE_TPU_KV_BLOCKS``)."""
        bs = int(kv_block_size or
                 os.environ.get("PADDLE_TPU_KV_BLOCK_SIZE", 128))
        if bs < 1:
            raise ValueError(f"kv_block_size must be >= 1, got {bs}")
        self.block_size = bs
        self._cache_dtype = cache_dtype   # disagg worker pool mirrors it
        self.blocks_per_slot = blocks_for(self.max_seq_len, bs)
        usable = int(kv_num_blocks or
                     os.environ.get("PADDLE_TPU_KV_BLOCKS", 0)) or \
            self.batch_slots * self.blocks_per_slot
        self.num_blocks = usable
        # +1: block 0 is the reserved null block unused table entries
        # point at (paged_kv module docstring)
        self.cache = init_paged_cache(self.model, usable + 1, bs,
                                      cache_dtype,
                                      kv_dtype=self.kv_dtype)
        self._alloc = BlockAllocator(usable + 1, bs)
        self._tables = np.zeros((self.batch_slots, self.blocks_per_slot),
                                np.int32)
        self._slot_blocks: List[List[int]] = \
            [[] for _ in range(self.batch_slots)]
        if prefix_cache is None:
            prefix_cache = os.environ.get("PADDLE_TPU_PREFIX_CACHE",
                                          "1") != "0"
        self._prefix = RadixPrefixCache(self._alloc, bs) \
            if prefix_cache else None

    # ---- sharding -----------------------------------------------------
    def _spec_for(self, mesh, arr, dims):
        """NamedSharding for ``arr`` from a per-dimension axis-name
        tuple.  A dimension degrades to replicated when the axis is
        missing from the mesh, has extent 1, or does not divide the
        array dimension (GSPMD would otherwise pad) — so every caller
        can name its IDEAL layout and let the mesh decide."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        out = []
        for d, ax in enumerate(dims):
            ok = (isinstance(ax, str) and ax in mesh.axis_names
                  and int(mesh.shape[ax]) > 1
                  and arr.shape[d] % int(mesh.shape[ax]) == 0)
            out.append(ax if ok else None)
        # canonical form: trailing Nones dropped.  GSPMD reports a
        # fully-replicated executable OUTPUT as P() — committing inputs
        # as P(None,...) would be semantically identical but a
        # DIFFERENT jit cache key, costing one spurious recompile on
        # the first post-warmup call whose operand came back from
        # another executable (seen on an ep-only mesh, where the KV
        # cache is fully replicated end to end)
        while out and out[-1] is None:
            out.pop()
        return NamedSharding(mesh, P(*out))

    def _put(self, mesh, arr, dims):
        return jax.device_put(arr, self._spec_for(mesh, arr, dims))

    def _shard_failed(self, what: str, err: Exception):
        """A mis-sharded pod must read as DEGRADED, not silently
        replicate (ISSUE 18 satellite): warn once per engine, count
        every failure in ``engine_sharding_failures_total``."""
        import warnings
        _metrics.counter(
            "engine_sharding_failures_total",
            "serving-state placements that fell back to replicated"
        ).inc()
        if not self._shard_warned:
            self._shard_warned = True
            warnings.warn(
                f"serving-mesh sharding failed for {what}: {err!r} — "
                f"the engine continues with replicated state (slower, "
                f"more HBM per device, never wrong)", RuntimeWarning,
                stacklevel=3)

    def _shard_params_over(self, mesh, params, module):
        """Commit a functional_state params dict to ``mesh`` by the
        pspecs the training-side parallel layers marked on their
        parameters (ColumnParallelLinear W: P(None,'tp'),
        RowParallelLinear W: P('tp',None), VocabParallelEmbedding:
        P('tp',None)); unmarked parameters replicate.  Committed
        weights are what makes every downstream jit compile SPMD —
        GSPMD follows the operands, no model-code changes."""
        marked = dict(module.named_parameters())
        out = {}
        for name, arr in params.items():
            pspec = getattr(marked.get(name), "pspec", None)
            dims = [None] * arr.ndim
            if pspec is not None:
                for d, ax in enumerate(tuple(pspec)[:arr.ndim]):
                    dims[d] = ax
            out[name] = self._put(mesh, arr, dims)
        return out

    def _shard_dense_cache_arrays(self, mesh, cache):
        """StaticKVCache layout on the mesh: every layer's k/v buffer
        [B, Hkv, S, D] (scale planes [B, Hkv, S]) with batch slots over
        'dp' and KV heads over 'tp'; lengths follow the slots.  Returns
        a new cache of the same type."""
        def put(layers, dims):
            return tuple(self._put(mesh, a, dims) for a in layers)

        scales = ()
        if cache.quantized:
            scales = (put(cache.k_scale, ("dp", "tp", None)),
                      put(cache.v_scale, ("dp", "tp", None)))
        return type(cache)(
            put(cache.k, ("dp", "tp", None, None)),
            put(cache.v, ("dp", "tp", None, None)),
            self._put(mesh, cache.lengths, ("dp",)),
            *scales)

    def _shard_paged_cache_arrays(self, mesh, cache):
        """Paged pool layout on the mesh: k/v [L, NB, Hkv, bs, D] —
        KV heads over 'tp', block/position dims REPLICATED so host-side
        allocation, the radix prefix cache and zero-recompile slot
        churn never see the mesh (block tables stay plain host int32)."""
        scales = ()
        if cache.quantized:
            scales = (self._put(mesh, cache.k_scale,
                                (None, None, "tp", None)),
                      self._put(mesh, cache.v_scale,
                                (None, None, "tp", None)))
        return type(cache)(
            self._put(mesh, cache.k, (None, None, "tp", None, None)),
            self._put(mesh, cache.v, (None, None, "tp", None, None)),
            *scales)

    def _shard_over_mesh(self, mesh):
        """Commit the engine's resident state (weights + KV cache) to
        the serving mesh.  Failures route through _shard_failed
        (warn-once + metric) instead of a silent pass: the engine still
        serves correct tokens replicated, but the operator can see it."""
        try:
            self.params = self._shard_params_over(mesh, self.params,
                                                  self.model)
        except Exception as e:
            self._shard_failed("params", e)
        try:
            if self.kv_layout == "paged":
                self.cache = self._shard_paged_cache_arrays(mesh,
                                                            self.cache)
            else:
                self.cache = self._shard_dense_cache_arrays(mesh,
                                                            self.cache)
        except Exception as e:
            self._shard_failed("kv_cache", e)

    # ---- compiled functions -------------------------------------------
    # Every model-running executable opens the MoE expert-stats
    # collector around its trace (ISSUE 19): MoE layers record their
    # per-expert dispatch load INSIDE the jitted program, the fold
    # rides out as one extra [num_experts]-sized output fetched at the
    # step's existing host sync — zero extra syncs, and a dense model
    # folds to None (an empty pytree leaf group), so non-MoE engines
    # compile byte-identical programs.
    def _prefill_fn(self, params, cache, ids, slot, prompt_len):
        with _moe.collect_expert_stats() as b:
            logits, cache = functional_apply(self.model, "prefill",
                                             params, ids, cache, slot,
                                             prompt_len)
        return logits, cache, _moe.fold_expert_stats(b)

    def _prefill_paged_cold_fn(self, params, cache, ids, table_row,
                               suffix_len):
        # prefix_len is a STATIC Python 0: the cold path compiles with
        # the exact flash/composite attention of the dense prefill
        with _moe.collect_expert_stats() as b:
            logits, cache = functional_apply(self.model, "prefill_paged",
                                             params, ids, cache,
                                             table_row, 0, suffix_len)
        return logits, cache, _moe.fold_expert_stats(b)

    def _prefill_paged_ext_fn(self, params, cache, ids, table_row,
                              prefix_len, suffix_len):
        with _moe.collect_expert_stats() as b:
            logits, cache = functional_apply(self.model, "prefill_paged",
                                             params, ids, cache,
                                             table_row, prefix_len,
                                             suffix_len)
        return logits, cache, _moe.fold_expert_stats(b)

    def _warp_sorted(self, logits, temps, top_ps):
        """The sampler's warp (temperature, static top-k, per-slot
        top-p), in sorted space: (s_logits, sort_idx), both [N, V], the
        warped logits in descending order (-1e30 where cut) and the
        token each came from.  The sorted logits are the sort's own
        keys, so nothing is gathered over the vocabulary."""
        logits = logits.astype(jnp.float32)
        v = logits.shape[-1]
        scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
        if self.top_k and self.top_k < v:
            kth = jax.lax.top_k(scaled, self.top_k)[0][:, -1:]
            scaled = jnp.where(scaled < kth, -1e30, scaled)
        neg, sort_idx = jax.lax.sort(
            (-scaled, jax.lax.broadcasted_iota(jnp.int32, scaled.shape, 1)),
            dimension=1, num_keys=1, is_stable=True)
        s_logits = -neg
        # top-p in sorted space: keep tokens whose PRECEDING cumulative
        # mass is < p (the first token always survives)
        probs = jax.nn.softmax(s_logits, axis=-1)
        csum = jnp.cumsum(probs, axis=-1)
        s_logits = jnp.where(csum - probs < top_ps[:, None],
                             s_logits, -1e30)
        return s_logits, sort_idx

    def _sample_from_logits(self, logits, key, temps, top_ps):
        """Greedy when temps<=0, else temperature + (static) top-k +
        (per-slot) top-p sampling. logits [N, V] f32.  The
        vocabulary-wide work runs only when a row of this call samples:
        the program branches on its own ``temps`` operand."""
        with jax.named_scope("sample"):
            logits = logits.astype(jnp.float32)
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

            def sampling():
                s_logits, sort_idx = self._warp_sorted(logits, temps,
                                                       top_ps)
                choice = jax.random.categorical(key, s_logits, axis=-1)
                sampled = jnp.take_along_axis(
                    sort_idx, choice[:, None],
                    axis=-1)[:, 0].astype(jnp.int32)
                return jnp.where(temps > 0, sampled, greedy)

            return jax.lax.cond(jnp.any(temps > 0), sampling,
                                lambda: greedy)

    def _place_token_fn(self, tokens, tok, slot):
        """The tick's token vector with a fresh slot's entry taken from
        its admission's sampler, device to device (_launch_decode)."""
        with jax.named_scope("sample"):
            return tokens.at[slot].set(tok[0])

    def _decode_fn(self, params, cache, tokens, active, key, temps,
                   top_ps):
        with _moe.collect_expert_stats() as b:
            logits, cache = functional_apply(self.model, "decode_step",
                                             params, tokens, cache,
                                             active)
        with jax.named_scope("sample"):
            key, sub = jax.random.split(key)
        nxt = self._sample_from_logits(logits, sub, temps, top_ps)
        return nxt, key, cache, _moe.fold_expert_stats(b)

    def _decode_paged_fn(self, params, cache, tokens, tables, lengths,
                         key, temps, top_ps):
        with _moe.collect_expert_stats() as b:
            logits, cache = functional_apply(self.model,
                                             "decode_step_paged",
                                             params, tokens, cache,
                                             tables, lengths)
        with jax.named_scope("sample"):
            key, sub = jax.random.split(key)
        nxt = self._sample_from_logits(logits, sub, temps, top_ps)
        return nxt, key, cache, _moe.fold_expert_stats(b)

    def _prefill_chunk_fn(self, params, cache, tokens, lengths, advance):
        # chunked prefill (ISSUE 20): one fixed-shape [B, chunk] window
        # over ALL batch slots — rows with advance=0 write masked
        # garbage above their valid length, exactly the spec-verify
        # convention.  `lengths` is the HOST scheduler mirror, so the
        # executable rewrites every row's in-graph length from it
        # (retired slots can't leave stale lengths behind).
        with _moe.collect_expert_stats() as b:
            logits, cache = functional_apply(
                self.model, "prefill_chunk", params, tokens, cache,
                lengths, advance)
        return logits, cache, _moe.fold_expert_stats(b)

    def _prefill_chunk_paged_fn(self, params, cache, tokens, tables,
                                lengths, advance):
        with _moe.collect_expert_stats() as b:
            logits, cache = functional_apply(
                self.model, "prefill_chunk_paged", params, tokens,
                cache, tables, lengths, advance)
        return logits, cache, _moe.fold_expert_stats(b)

    # ---- timing helpers -----------------------------------------------
    # executable-observatory kind per _timed key family (ISSUE 15): the
    # registry groups rooflines by these
    _EXEC_KIND = {"prefill": "prefill", "prefill_paged": "prefill",
                  "prefill_paged_ext": "prefill", "disagg": "prefill",
                  "disagg_ext": "prefill", "draft_prefill": "prefill",
                  "prefill_chunk": "prefill",
                  "prefill_chunk_paged": "prefill",
                  "decode": "decode", "spec_tick": "spec_verify",
                  "sample": "sample", "place_token": "sample",
                  "handoff_gather": "handoff",
                  "handoff_scatter": "handoff"}

    def _register_exec(self, key, jitfn, args, mesh=None):
        """Join the process exec registry at compile time (the first
        call of this key): shape structs are captured BEFORE the call
        runs, so donation never invalidates what analyze() re-lowers
        from.  Registration is dict writes only — the XLA cost/memory
        analysis stays deferred until something asks for it."""
        fam = key[0] if isinstance(key, tuple) else str(key)
        kind = self._EXEC_KIND.get(fam, str(fam))
        meta = {"kv_layout": self.kv_layout,
                "kv_dtype": self.kv_dtype or "dense"}
        # pod-scale serving (ISSUE 18): the entry records WHICH devices
        # it compiled against and the tp degree, so the observatory can
        # tell a tp-sharded decode from a single-chip one (and the
        # disagg prefill submesh from the decode submesh)
        if mesh is not None:
            meta["tp"] = int(dict(mesh.shape).get("tp", 1))
            # expert parallelism (ISSUE 19): the submesh shape below
            # already carries every axis — recording ep explicitly lets
            # the observatory (and comm_stats' per-axis collective
            # fold) tell an expert-parallel decode apart at a glance
            meta["ep"] = int(dict(mesh.shape).get("ep", 1))
            meta["submesh"] = {
                "shape": {ax: int(n) for ax, n in mesh.shape.items()},
                "devices": [int(d.id) for d in
                            np.asarray(mesh.devices).flat]}
        if kind == "decode":
            meta["batch_slots"] = self.batch_slots
        elif kind == "spec_verify":
            meta["spec_k"] = self.spec_k
        if isinstance(key, tuple) and len(key) > 1 and key[1]:
            meta["bucket"] = int(key[1])
        # donation per family, matching the jax.jit construction: the
        # sampler never donates, the spec tick donates both caches
        # (spec_decode.py argnums 2+3), everything else donates its
        # cache operand 1 — the registry's donation evidence must be
        # what the executable actually does
        if not self._donate or kind == "sample":
            donate = ()
        elif kind == "spec_verify":
            donate = (2, 3)
        else:
            donate = (1,)
        _exec_registry.register(
            self._exec_component, key, kind, jitfn=jitfn, args=args,
            donate_argnums=donate, meta=meta)

    _MESH_DEFAULT = object()   # sentinel: "use self.mesh"

    def _timed_exec(self, kind, key, jitfn, *args, mesh=_MESH_DEFAULT):
        """_timed with observatory wiring: the jitted callable and its
        args are visible here, so the first call registers the
        executable and steady-state calls pair their wall time with the
        registry entry (one dict lookup + two adds — zero syncs).
        ``mesh`` overrides the compile mesh for this key (the disagg
        PrefillWorker traces against its OWN submesh); the default is
        the engine's serving mesh."""
        if mesh is self._MESH_DEFAULT:
            mesh = self.mesh
        if key not in self._first_call_keys and _exec_registry.enabled():
            self._register_exec(key, jitfn, args, mesh=mesh)
        return self._timed(kind, key, lambda: jitfn(*args), mesh=mesh)

    def _timed(self, kind, key, fn, mesh=_MESH_DEFAULT):
        if mesh is self._MESH_DEFAULT:
            mesh = self.mesh
        if mesh is not None and key not in self._first_call_keys:
            # first call per key = the trace: publish the mesh on BOTH
            # channels (ambient + compile) so trace-time decisions —
            # the decode kernels' shard_map wrapper — see the serving
            # mesh.  Steady-state calls skip the guard entirely (zero
            # per-tick overhead).
            from ..distributed.mesh import compile_mesh_guard
            with compile_mesh_guard(mesh):
                return self._timed_inner(kind, key, fn)
        return self._timed_inner(kind, key, fn)

    # first-call traces are serialized PROCESS-WIDE: two replicas of
    # the same model driven from different threads (the RPC fleet
    # loadtest, a multi-replica router) would otherwise trace jax
    # programs concurrently over the SHARED module tree and leak
    # tracers into each other's traces.  Steady-state calls never take
    # the lock — only the one cold call per executable key does.
    _trace_lock = threading.RLock()

    def _timed_inner(self, kind, key, fn):
        t0 = time.perf_counter()
        if key not in self._first_call_keys:
            # first call per executable = trace + compile/deserialize
            self._first_call_keys.add(key)
            with self._trace_lock:
                before = _kernel_paths.counts()
                out = fn()
                # which kernel entry points traced their Pallas kernel
                # and which their composite, for THIS executable
                self.kernel_paths[key] = {
                    op: {path: n - before.get(op, {}).get(path, 0)
                         for path, n in per_op.items()}
                    for op, per_op in _kernel_paths.counts().items()
                    if per_op != before.get(op)}
            dt = (time.perf_counter() - t0) * 1e3
            self._timings["compile_ms_cold"] += dt
            _exec_registry.registry().note_compile(
                self._exec_component, key, dt)
        else:
            out = fn()
            dt = (time.perf_counter() - t0) * 1e3
            self._timings[kind] += dt
            _exec_registry.note_runtime(self._exec_component, key, dt)
        return out

    # ---- public API ---------------------------------------------------
    def add_request(self, prompt, max_new_tokens: int = 32,
                    eos_id: Optional[int] = None,
                    temperature: float = 0.0, top_p: float = 1.0,
                    deadline_s: Optional[float] = None) -> int:
        """Queue a generation request; returns its id. Admitted into a
        free slot (dense) / free blocks (paged) at the next step().
        deadline_s (seconds from NOW, queueing included): past it the
        request is retired with whatever it generated and reported
        timed_out, instead of holding a decode slot forever."""
        req = Request(prompt, max_new_tokens, eos_id, temperature, top_p,
                      deadline_s=deadline_s)
        if req.prompt.size > self.buckets[-1]:
            raise ValueError(
                f"prompt of {req.prompt.size} tokens exceeds the largest "
                f"prefill bucket ({self.buckets[-1]})")
        if req.prompt.size >= self.max_seq_len:
            raise ValueError(
                f"prompt of {req.prompt.size} tokens leaves no room to "
                f"generate within max_seq_len={self.max_seq_len}")
        if self.kv_layout == "paged":
            # can this request EVER run alone on an empty pool?  (its
            # transient bucket-padded prefill, then its steady state)
            bs = self.block_size
            worst = max(
                blocks_for(self._bucket_for(req.prompt.size), bs),
                # spec ticks write a K+1 window before the scheduler
                # knows how much of it commits, so the steady-state
                # extent carries that margin
                blocks_for(min(req.prompt.size + req.max_new_tokens
                               + self.spec_k, self.max_seq_len), bs))
            if worst > self._alloc.capacity:
                raise ValueError(
                    f"request needs {worst} KV blocks but the pool only "
                    f"has {self._alloc.capacity} — raise "
                    f"PADDLE_TPU_KV_BLOCKS or shrink the request")
        self._queue.append(req)
        return req.rid

    def generate(self, prompt, max_new_tokens: int = 32,
                 eos_id: Optional[int] = None, temperature: float = 0.0,
                 top_p: float = 1.0,
                 deadline_s: Optional[float] = None) -> np.ndarray:
        """Blocking single-request generation THROUGH the admission
        queue: on a busy/full engine this waits for capacity (driving
        step() retires slots and frees blocks) instead of raising.
        In-flight requests keep decoding while it waits.  With
        deadline_s the wait is bounded: past the deadline the partial
        generation (possibly empty) is returned."""
        rid = self.add_request(prompt, max_new_tokens=max_new_tokens,
                               eos_id=eos_id, temperature=temperature,
                               top_p=top_p, deadline_s=deadline_s)
        while rid not in self.results:
            if self._guard is not None and self._guard.preempted:
                # server preempted while we were queued: drain and hand
                # back whatever exists (empty if never admitted)
                self.undelivered.extend(self.drain(self._guard_timeout))
                return self.results.get(rid, np.zeros(0, np.int32))
            self.step_or_raise()
        return self.results[rid]

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        return self.buckets[-1]

    # ---- paged block accounting ---------------------------------------
    def _alloc_blocks(self, n: int) -> Optional[List[int]]:
        """Allocate n blocks, evicting unpinned radix-cache blocks if
        the free list alone cannot cover it."""
        if n <= 0:
            return []
        out = self._alloc.alloc(n)
        if out is None and self._prefix is not None:
            self._prefix.evict(n - self._alloc.num_free)
            out = self._alloc.alloc(n)
        return out

    def _free_slot_blocks(self, slot: int):
        self._alloc.decref(self._slot_blocks[slot])
        self._slot_blocks[slot] = []
        self._tables[slot, :] = 0
        self._slot_len[slot] = 0

    def _release_slot(self, req: Request):
        """Shared slot teardown for retirement AND preemption — every
        per-slot sampling field is reset in exactly one place."""
        slot = req.slot
        if self.kv_layout == "paged":
            self._free_slot_blocks(slot)
        self._slots[slot] = None
        self._temps[slot] = 0.0
        self._top_ps[slot] = 1.0
        if self._spec is not None:
            self._spec.on_release(slot)
        req.slot = None
        req.prefilling = False
        req.prefill_pos = 0
        # any release can make blocks free OR evictable — wake the
        # head-of-line admission memo (see _hol_block)
        self._release_epoch += 1

    def _preempt(self, req: Request):
        """Kick an active request back onto the queue head: free its
        blocks now, resume later via a prefill over prompt+generated
        (which usually hits the radix cache for the original prompt).
        The sampled-but-unwritten last token is re-derived by that
        prefill, so no state is lost."""
        req.resume_prompt = np.concatenate(
            [req.prompt, np.asarray(req.generated, np.int32)])
        req.preemptions += 1
        now = time.perf_counter()
        # a still-PREFILLING victim (chunked mode) never went live:
        # it has no decode activation to account or close
        if req.t_live is not None:
            req.active_s += now - req.t_live
        req.t_queue_since = now
        self._timings["preemptions"] += 1
        self._m_preempts.inc()
        if self._tracer.active:
            tr = self._tracer
            if req.t_live is not None:
                t_live = tr.to_us(req.t_live)
                tr.complete("decode", t_live, tr.to_us(now) - t_live,
                            pid=_spans.PID_REQUESTS, tid=req.rid,
                            cat="request",
                            args={"tokens": len(req.generated),
                                  "preempted": True})
            else:
                t_adm = tr.to_us(req.t_admit)
                tr.complete("prefill", t_adm, tr.to_us(now) - t_adm,
                            pid=_spans.PID_REQUESTS, tid=req.rid,
                            cat="request",
                            args={"chunk_pos": req.prefill_pos,
                                  "preempted": True})
            tr.instant("preempt", pid=_spans.PID_REQUESTS, tid=req.rid,
                       cat="request", ts_us=tr.to_us(now))
        req.t_live = None
        self._release_slot(req)
        self._queue.appendleft(req)

    def _preempt_for_blocks(self, n: int,
                            exclude: Request) -> Optional[List[int]]:
        """Pool is dry mid-decode: preempt the YOUNGEST other active
        request(s) until n blocks come free (vLLM's recompute-style
        preemption).  Only victims whose resume prefill fits a bucket
        qualify — with default buckets that is everyone."""
        while True:
            out = self._alloc_blocks(n)
            if out is not None:
                return out
            # a victim must be RESUMABLE: its prompt+generated fits a
            # prefill bucket AND that bucket's cold admission fits the
            # pool (else it could never re-admit and the queue stalls)
            victims = [
                r for r in self._slots
                if r is not None and r is not exclude
                and len(r.prompt) + len(r.generated) <= self.buckets[-1]
                and blocks_for(
                    self._bucket_for(len(r.prompt) + len(r.generated)),
                    self.block_size) <= self._alloc.capacity]
            if not victims:
                return None
            self._preempt(max(victims, key=lambda r: r.admit_seq))

    # ---- admission ----------------------------------------------------
    def _try_admit(self, req: Request, slot: int) -> bool:
        """Admit into `slot` if capacity allows; False leaves the
        request at the queue head (head-of-line order is FIFO)."""
        if self.kv_layout == "dense":
            self._admit_dense(req, slot)
            return True
        return self._admit_paged(req, slot)

    def _record_admission(self, req: Request, slot: int, plen: int,
                          logits):
        """Shared tail of both admission paths, in two halves.  This
        one is what the host knows when it dispatches: the first token's
        sampler goes behind the prefill and the request is bound to its
        slot.  What the host learns from the token is
        ``_finish_admission``: where the host can tell that the token
        ends nothing (``_reads_can_wait``), that half is left to
        ``_tick``, which launches the decode tick behind the prefill
        first; everywhere else it follows at once."""
        self._key, sub = jax.random.split(self._key)
        # np (not list) literals: a python-float list would lower an
        # extra convert_element_type executable on the admission path
        tok = self._timed_exec(
            "prefill_ms", ("sample", 1), self._sample_jit,
            logits, sub,
            np.asarray([req.temperature], np.float32),
            np.asarray([req.top_p], np.float32))
        req.queued_s += req.t_admit - req.t_queue_since
        self._timings["prefills"] += 1
        self._m_prefills.inc()
        req.slot = slot
        req.admit_seq = next(self._admit_counter)
        self._slots[slot] = req
        self._slot_len[slot] = plen
        self._temps[slot] = req.temperature
        self._top_ps[slot] = req.top_p
        if self._reads_can_wait([(slot, req)]):
            self._fresh.append((req, slot, tok))
        else:
            self._finish_admission(req, slot, tok)

    def _finish_admission(self, req: Request, slot: int, tok):
        """The half of an admission that needs the first token's VALUE
        on the host: the read, the request's first timestamps, the
        token into the stream and into the next tick's host vector."""
        tok = int(np.asarray(tok)[0])
        async_dispatch.record_host_sync()
        if req.done or self._slots[slot] is not req:
            # retired since the dispatch (a deadline, a drain): it has
            # what it gets, and the slot may be another's already
            return
        now = time.perf_counter()
        if req.t_first is None:
            req.t_first = now
            self._m_ttft.observe((now - req.t_enqueue) * 1e3)
        req.t_live = now
        req.token_times.append(now)
        if self._tracer.active:
            # request-lifecycle timeline: close the queued span, record
            # the prefill span (host timestamps already on hand — no
            # extra clock reads beyond `now` above)
            tr = self._tracer
            t_q = tr.to_us(req.t_queue_since)
            t_adm = tr.to_us(req.t_admit)
            tr.complete("queued", t_q, t_adm - t_q,
                        pid=_spans.PID_REQUESTS, tid=req.rid,
                        cat="request",
                        args={"prompt_tokens": int(req.prompt.size),
                              "resume": req.resume_prompt is not None})
            tr.complete("prefill", t_adm, tr.to_us(now) - t_adm,
                        pid=_spans.PID_REQUESTS, tid=req.rid,
                        cat="request", args={"slot": slot})
        req.generated.append(tok)
        self._next_token[slot] = tok
        self._retire_if_done(req, tok)
        if self._spec is not None and self._slots[slot] is req:
            # the draft prefills the same (full) prompt and the first
            # sampled token seeds its catch-up window
            self._spec.on_admit(req, slot, tok)

    def _admit_dense(self, req: Request, slot: int):
        prompt = req.effective_prompt()
        bucket = self._bucket_for(prompt.size)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :prompt.size] = prompt
        plen = prompt.size
        req.t_admit = time.perf_counter()
        self._timings["prefill_tokens"] += bucket
        with _spans.span("prefill", "serve", bucket=bucket,
                         prompt_tokens=plen):
            logits, cache, moe = self._timed_exec(
                "prefill_ms", ("prefill", bucket), self._prefill_jit,
                self.params, self.cache, jnp.asarray(ids),
                np.int32(slot), np.int32(plen))
            self.cache = cache
            self._accum_moe(moe)
            self._record_admission(req, slot, plen, logits)

    def _admit_paged(self, req: Request, slot: int) -> bool:
        """Paged admission: one in-engine prefill, then the same slot
        adoption a disaggregated handoff uses."""
        rec = self._paged_prefill(req, self._prefill_paged_cold_jit,
                                  self._prefill_paged_ext_jit,
                                  "prefill_paged")
        if rec is None:
            return False                      # stay queued; retry later
        blocks, _plen, logits = rec
        self.admit_handoff(req, slot, blocks, logits)
        return True

    def _paged_prefill(self, req: Request, cold_jit, ext_jit,
                       key_prefix: str, domain=None):
        """The paged prefill body: match the radix cache, allocate
        blocks for the divergent suffix's bucket, prefill ONLY the
        suffix, then trim the bucket-padding blocks and adopt the
        prompt into the radix tree.  Returns ``(blocks, plen, logits)``
        with the slot-lifetime refcounts TAKEN (the caller installs the
        block table and finishes admission), or None when the pool
        cannot hold the request yet.  Parameterized over the compiled
        executables AND the state ``domain`` (params / cache / block
        allocator / radix cache / mesh) so the in-engine admission path
        and the disaggregated PrefillWorker — which under disjoint
        disaggregation owns a SEPARATE pool on its own device group —
        share one implementation.  ``domain=None`` means self."""
        dom = domain if domain is not None else self
        bs = self.block_size
        prompt = req.effective_prompt()
        pc_stats0 = None
        if dom._prefix is not None:
            # a blocked head-of-line request re-matches on every retry;
            # roll the hit counters back on failure so the reported hit
            # rate counts admissions, not retries
            pc_stats0 = (dom._prefix.queries, dom._prefix.hit_queries,
                         dom._prefix.hit_blocks)
            shared, prefix_len = dom._prefix.match(prompt)
        else:
            shared, prefix_len = [], 0
        # the bucket-padded extent must fit BOTH the slot's block table
        # (coarse bucket sets can push prefix+bucket past max_seq) AND
        # the whole pool (a large prefix hit on a shrunk pool can
        # demand more blocks than exist — and the matched blocks are
        # pinned by our own incref, so eviction could never save it):
        # shed cached prefix blocks (recompute those tokens) until it
        # does — prefix_len=0 always fits, because add_request already
        # guaranteed blocks_for(bucket_for(prompt)) <= capacity
        fit = min(self.blocks_per_slot, dom._alloc.capacity)
        shed = 0
        while shared and blocks_for(
                prefix_len + self._bucket_for(prompt.size - prefix_len),
                bs) > fit:
            shared = shared[:-1]
            prefix_len -= bs
            shed += 1
        if shed and pc_stats0 is not None:
            # shed blocks were never reused — keep the hit counters
            # honest (a fully-shed match is not a hit at all)
            dom._prefix.hit_blocks -= shed
            if not shared:
                dom._prefix.hit_queries -= 1
        suffix = prompt[prefix_len:]
        bucket = self._bucket_for(suffix.size)
        need_total = blocks_for(prefix_len + bucket, bs)
        # the slot's OWN reference on the shared prefix blocks, taken
        # BEFORE any allocation: _alloc_blocks may evict radix leaves,
        # and a matched block whose only reference is the tree's
        # (refcount 1) would otherwise be freed and re-handed out as
        # this same request's "fresh" suffix block — aliasing the block
        # table and corrupting the shared prefix KV
        dom._alloc.incref(shared)
        new_blocks = dom._alloc_blocks(need_total - len(shared))
        if new_blocks is None:
            dom._alloc.decref(shared)
            if pc_stats0 is not None:
                (dom._prefix.queries, dom._prefix.hit_queries,
                 dom._prefix.hit_blocks) = pc_stats0
            return None                       # stay queued; retry later
        blocks = list(shared) + new_blocks
        req.t_admit = time.perf_counter()
        # the prefix-cache win in one number: a hit admission prefills
        # only the divergent suffix's bucket, not the whole prompt's
        self._timings["prefill_tokens"] += bucket

        ids = np.zeros((1, bucket), np.int32)
        ids[0, :suffix.size] = suffix
        row = np.zeros(self.blocks_per_slot, np.int32)
        row[:len(blocks)] = blocks
        with _spans.span("prefill", "serve", bucket=bucket,
                         prompt_tokens=int(suffix.size)):
            if prefix_len == 0:
                logits, cache, moe = self._timed_exec(
                    "prefill_ms", (key_prefix, bucket), cold_jit,
                    dom.params, dom.cache, jnp.asarray(ids),
                    jnp.asarray(row), np.int32(suffix.size),
                    mesh=dom.mesh)
            else:
                logits, cache, moe = self._timed_exec(
                    "prefill_ms", (key_prefix + "_ext", bucket), ext_jit,
                    dom.params, dom.cache, jnp.asarray(ids),
                    jnp.asarray(row), np.int32(prefix_len),
                    np.int32(suffix.size), mesh=dom.mesh)
        dom.cache = cache
        self._accum_moe(moe)

        # trim: blocks past the REAL prompt extent only ever held bucket
        # padding — return them to the pool immediately
        plen = int(prefix_len + suffix.size)          # == prompt.size
        keep = blocks_for(plen, bs)
        if len(blocks) > keep:
            dom._alloc.decref(blocks[keep:])
            blocks = blocks[:keep]
        # adopt the prompt's full blocks into the radix tree so the NEXT
        # request sharing this prefix skips its prefill
        if dom._prefix is not None:
            n_full = prompt.size // bs
            if n_full:
                dom._prefix.insert(prompt[:n_full * bs],
                                   blocks[:n_full])
        return blocks, plen, logits

    def admit_handoff(self, req: Request, slot: int, blocks, logits):
        """Adopt a request whose prefill ALREADY ran elsewhere (the
        disaggregated prefill worker — inference.disagg): install its
        block table and finish admission from the handed-off last-token
        logits.  The blocks arrive trimmed, radix-adopted and owned by
        this slot (the worker took the slot's refcounts); no prefill
        executable runs on the decode side — that is the point."""
        if self.kv_layout != "paged":
            raise ValueError("admit_handoff needs the paged layout — "
                             "the KV handoff travels through the block "
                             "pool")
        plen = int(req.effective_prompt().size)
        self._slot_blocks[slot] = list(blocks)
        self._tables[slot, :] = 0
        self._tables[slot, :len(blocks)] = blocks
        self._record_admission(req, slot, plen, logits)

    # ---- chunked prefill (ISSUE 20) -----------------------------------
    def _try_admit_chunked(self, req: Request, slot: int) -> bool:
        """Chunked admission: bind the request to a slot and let
        _chunk_tick feed its prompt through the chunk executable a
        budget at a time — NO prefill executable runs here, so the
        decode batch never stalls behind it.  Paged, the slot starts
        with blocks covering its radix-matched prefix plus the first
        chunk; False (pool dry) leaves it at the queue head."""
        prompt = req.effective_prompt()
        plen0 = 0
        if self.kv_layout == "paged":
            bs = self.block_size
            pc_stats0 = None
            if self._prefix is not None:
                pc_stats0 = (self._prefix.queries,
                             self._prefix.hit_queries,
                             self._prefix.hit_blocks)
                shared, prefix_len = self._prefix.match(prompt)
            else:
                shared, prefix_len = [], 0
            # the match can't exceed the slot's table (coarse pools):
            # shed cached blocks until it fits, same as _paged_prefill
            fit = min(self.blocks_per_slot, self._alloc.capacity)
            shed = 0
            while shared and len(shared) > fit:
                shared = shared[:-1]
                prefix_len -= bs
                shed += 1
            if shed and pc_stats0 is not None:
                self._prefix.hit_blocks -= shed
                if not shared:
                    self._prefix.hit_queries -= 1
            first = min(self.prefill_chunk, prompt.size - prefix_len)
            need = blocks_for(prefix_len + first, bs)
            # slot's own reference on the shared prefix BEFORE any
            # allocation (the aliasing hazard _paged_prefill documents)
            self._alloc.incref(shared)
            new_blocks = self._alloc_blocks(need - len(shared))
            if new_blocks is None:
                self._alloc.decref(shared)
                if pc_stats0 is not None:
                    (self._prefix.queries, self._prefix.hit_queries,
                     self._prefix.hit_blocks) = pc_stats0
                return False                  # stay queued; retry later
            blocks = list(shared) + new_blocks
            self._slot_blocks[slot] = blocks
            self._tables[slot, :] = 0
            self._tables[slot, :len(blocks)] = blocks
            plen0 = prefix_len
        now = time.perf_counter()
        req.t_admit = now
        req.queued_s += now - req.t_queue_since
        req.prefilling = True
        req.prefill_pos = plen0
        req.slot = slot
        req.admit_seq = next(self._admit_counter)
        self._slots[slot] = req
        self._slot_len[slot] = plen0
        self._temps[slot] = req.temperature
        self._top_ps[slot] = req.top_p
        if self._tracer.active:
            tr = self._tracer
            t_q = tr.to_us(req.t_queue_since)
            tr.complete("queued", t_q, tr.to_us(now) - t_q,
                        pid=_spans.PID_REQUESTS, tid=req.rid,
                        cat="request",
                        args={"prompt_tokens": int(req.prompt.size),
                              "resume": req.resume_prompt is not None})
        return True

    def _ensure_chunk_room(self, req: Request, adv: int) -> int:
        """Grow ``req``'s block extent to cover its next ``adv`` chunk
        tokens (free list → radix eviction → preempt-youngest) —
        _ensure_decode_room made chunk-granular.  Returns the advance
        that is actually safe: 0 when the requester itself had to be
        preempted (a still-prefilling requester is ALWAYS resumable —
        its prompt fits a bucket by add_request and generated is
        empty — so the degrade path preempts, never retires)."""
        slot = req.slot
        while (self._slots[slot] is req and blocks_to_extend(
                len(self._slot_blocks[slot]),
                req.prefill_pos + adv, self.block_size) > 0):
            nb = self._alloc_blocks(1)
            if nb is None:
                nb = self._preempt_for_blocks(1, exclude=req)
            if nb is None:
                self._preempt(req)
                break
            idx = len(self._slot_blocks[slot])
            self._slot_blocks[slot].append(nb[0])
            self._tables[slot, idx] = nb[0]
        return adv if self._slots[slot] is req else 0

    def _chunk_tick(self) -> int:
        """Advance every still-prefilling slot by up to
        ``prefill_chunk`` prompt tokens TOTAL (oldest admission first
        — FIFO inside the tick too) through ONE fixed-shape chunk
        executable, then graduate slots whose prompt completed.
        Returns the number of first tokens sampled (graduations) —
        the same thing monolithic admission counts as produced."""
        pre = [(s, r) for s, r in enumerate(self._slots)
               if r is not None and r.prefilling]
        if not pre:
            return 0
        pre.sort(key=lambda sr: sr[1].admit_seq)
        c = self.prefill_chunk
        budget = c
        tokens = np.zeros((self.batch_slots, c), np.int32)
        advance = np.zeros(self.batch_slots, np.int32)
        tick_wall0 = time.perf_counter()
        for slot, req in pre:
            if budget <= 0:
                break
            prompt = req.effective_prompt()
            adv = min(prompt.size - req.prefill_pos, budget)
            if self.kv_layout == "paged":
                # may preempt OTHER prefilling slots (their batch rows
                # become no-ops: the exec reads tables/lengths at call
                # time, and a freed slot's zeroed table row routes its
                # writes into the null block)
                adv = self._ensure_chunk_room(req, adv)
            if self._slots[slot] is not req or adv <= 0:
                continue
            tokens[slot, :adv] = prompt[req.prefill_pos:
                                        req.prefill_pos + adv]
            advance[slot] = adv
            budget -= adv
        if not advance.any():
            return 0
        advanced = int(advance.sum())
        self._timings["prefill_tokens"] += advanced
        with _spans.span("prefill", "serve", bucket=c,
                         prompt_tokens=advanced):
            if self.kv_layout == "paged":
                logits, cache, moe = self._timed_exec(
                    "prefill_ms", ("prefill_chunk_paged", c),
                    self._prefill_chunk_paged_jit,
                    self.params, self.cache, jnp.asarray(tokens),
                    jnp.asarray(self._tables),
                    jnp.asarray(self._slot_len.astype(np.int32)),
                    jnp.asarray(advance))
            else:
                logits, cache, moe = self._timed_exec(
                    "prefill_ms", ("prefill_chunk", c),
                    self._prefill_chunk_jit,
                    self.params, self.cache, jnp.asarray(tokens),
                    jnp.asarray(self._slot_len.astype(np.int32)),
                    jnp.asarray(advance))
        self.cache = cache
        if moe is not None:
            # park the fold: np.asarray'ing it here would cost a host
            # sync per chunk tick — it drains at the next real sync
            self._moe_pending.append(moe)
        grads = []
        for slot, req in pre:
            if self._slots[slot] is not req:
                continue
            adv = int(advance[slot])
            if adv <= 0:
                continue
            req.prefill_pos += adv
            self._slot_len[slot] = req.prefill_pos
            prompt = req.effective_prompt()
            if self.kv_layout == "paged" and self._prefix is not None:
                # progressive adoption: completed blocks join the radix
                # tree NOW, so a same-prefix request admitted while
                # this one is mid-prefill already shares them (insert
                # is idempotent — existing nodes win)
                n_full = req.prefill_pos // self.block_size
                if n_full:
                    self._prefix.insert(
                        prompt[:n_full * self.block_size],
                        self._slot_blocks[slot][:n_full])
            if req.prefill_pos >= prompt.size:
                grads.append((slot, req))
        produced = 0
        if grads:
            # batch-wide sampling at a FIXED (sample, batch_slots) key:
            # slicing per graduating slot would compile per slot index
            self._key, sub = jax.random.split(self._key)
            tok = self._timed_exec(
                "prefill_ms", ("sample", self.batch_slots),
                self._sample_jit, logits, sub,
                jnp.asarray(self._temps), jnp.asarray(self._top_ps))
            t0 = time.perf_counter()
            tok_np = np.asarray(tok)
            self._flush_moe()
            async_dispatch.record_host_sync()
            self._timings["sync_ms"] += \
                (time.perf_counter() - t0) * 1e3
            for slot, req in grads:
                self._graduate(req, slot, int(tok_np[slot]))
                produced += 1
        _flightrec.record(
            "chunk_tick",
            dur_ms=(time.perf_counter() - tick_wall0) * 1e3,
            prefilling=len(pre), tokens=advanced, graduated=produced)
        return produced

    def _graduate(self, req: Request, slot: int, tok: int):
        """A slot's prompt completed its last chunk: commit the first
        sampled token and flip the slot into the decode active set.
        Mirrors _record_admission's tail — when chunked, the request's
        first token and lifecycle spans come from here."""
        now = time.perf_counter()
        if req.t_first is None:
            req.t_first = now
            self._m_ttft.observe((now - req.t_enqueue) * 1e3)
        req.t_live = now
        req.prefilling = False
        req.token_times.append(now)
        self._timings["prefills"] += 1
        self._m_prefills.inc()
        if self._tracer.active:
            tr = self._tracer
            t_adm = tr.to_us(req.t_admit)
            tr.complete("prefill", t_adm, tr.to_us(now) - t_adm,
                        pid=_spans.PID_REQUESTS, tid=req.rid,
                        cat="request",
                        args={"slot": slot, "chunked": True})
        req.generated.append(tok)
        self._next_token[slot] = tok
        self._retire_if_done(req, tok)
        if self._spec is not None and self._slots[slot] is req:
            # the draft catches up over the full prompt now — its
            # (small-model) bucketed prefill runs once per request,
            # exactly as in monolithic admission
            self._spec.on_admit(req, slot, tok)

    def _flush_moe(self):
        """Fold the chunk-tick expert stats parked since the last real
        host sync (see _chunk_tick) — called wherever the scheduler
        already blocks on device results, so it adds zero syncs."""
        for moe in self._moe_pending:
            self._accum_moe(moe)
        self._moe_pending.clear()

    def _ensure_decode_room(self, need_tokens: int = 1):
        """Before a decode step every active slot whose next
        ``need_tokens`` writes would fall past its block extent gets
        fresh blocks — by free list, then radix-cache eviction, then
        preemption of the youngest other request.  This is the
        no-deadlock path ISSUE'd as preempt-to-queue: the dense engine
        could never run out mid-request, the paged one can.
        ``need_tokens`` > 1 is the spec-decode tick, which scatters a
        K+1-token window before knowing how much of it commits."""
        for slot in range(self.batch_slots):
            req = self._slots[slot]
            # still-prefilling slots don't decode — their room is
            # chunk-granular (_ensure_chunk_room); the decode exec's
            # write on their row lands in masked garbage / null block
            if req is None or req.prefilling:
                continue
            need_blocks = blocks_for(
                int(self._slot_len[slot]) + need_tokens, self.block_size)
            while (self._slots[slot] is req
                   and len(self._slot_blocks[slot]) < need_blocks):
                nb = self._alloc_blocks(1)
                if nb is None:
                    nb = self._preempt_for_blocks(1, exclude=req)
                if nb is None:
                    # every OTHER active request has outgrown the
                    # largest bucket (un-resumable victims — possible
                    # with custom coarse bucket lists): degrade the
                    # requester, never the engine.  Preempt it if it
                    # can itself resume; otherwise retire it with the
                    # tokens it has (a memory-capped finish beats
                    # killing every request).
                    total = len(req.prompt) + len(req.generated)
                    if (total <= self.buckets[-1] and blocks_for(
                            self._bucket_for(total), self.block_size)
                            <= self._alloc.capacity):
                        self._preempt(req)
                    else:
                        self._timings["memory_capped_retirements"] += 1
                        self._retire(req)
                    break
                idx = len(self._slot_blocks[slot])
                self._slot_blocks[slot].append(nb[0])
                self._tables[slot, idx] = nb[0]

    def _retire_if_done(self, req: Request, last_tok: int):
        """EOS / max-new-tokens / capacity retirement; frees the slot
        (and, paged, its blocks — minus any the radix cache pins)."""
        full = self._slot_len[req.slot] + 1 >= self.max_seq_len
        if (last_tok == req.eos_id
                or len(req.generated) >= req.max_new_tokens or full):
            self._retire(req)

    def _deliver(self, req: Request):
        """The one place results/request_stats are written — every
        finished request (normal, deadline-expired, drain-forced) goes
        through the same bounded-history caps: a long-running server
        must not grow state per request forever.  results is the
        DELIVERY channel — a step()-driven server is expected to pop
        what it consumes (loadgen does) — so its safety cap is generous
        enough that no realistic single run() batch ever hits it."""
        self.results[req.rid] = np.asarray(req.generated, np.int32)
        self.request_stats[req.rid] = self._request_record(req)
        (self._m_req_to if req.timed_out else self._m_req_ok).inc()
        while len(self.request_stats) > self._request_stats_cap:
            self.request_stats.pop(next(iter(self.request_stats)))
        while len(self.results) > self._results_cap:
            self.results.pop(next(iter(self.results)))

    def _retire(self, req: Request):
        req.done = True
        req.t_finish = time.perf_counter()
        # a deadline/drain retirement can hit a still-prefilling slot
        # (chunked mode) that never went live — nothing to account
        if req.t_live is not None:
            req.active_s += req.t_finish - req.t_live
        if self._tracer.active and req.t_live is not None:
            # close the request track: the decode span of this (final)
            # activation — together with queued/prefill/earlier decode
            # spans this is the full lifecycle timeline
            tr = self._tracer
            t_live = tr.to_us(req.t_live)
            tr.complete("decode", t_live,
                        tr.to_us(req.t_finish) - t_live,
                        pid=_spans.PID_REQUESTS, tid=req.rid,
                        cat="request",
                        args={"tokens": len(req.generated),
                              "preemptions": req.preemptions,
                              "timed_out": req.timed_out})
        self._deliver(req)
        self._release_slot(req)

    def _request_record(self, req: Request) -> dict:
        n = len(req.generated)
        # inter-token latency: gaps between delivery timestamps (first
        # token included) — the per-request tail the load harness pools
        # and coordinated-omission-corrects, same contract as TTFT
        gaps = (np.diff(np.asarray(req.token_times)) * 1e3
                if len(req.token_times) > 1
                else np.zeros(0, np.float64))
        return {
            "prompt_tokens": int(req.prompt.size),
            "tokens": n,
            # a queue-expired request never produced a token: no TTFT
            "ttft_ms": round((req.t_first - req.t_enqueue) * 1e3, 3)
            if req.t_first is not None else None,
            "queued_ms": round(req.queued_s * 1e3, 3),
            # over ACTIVE decode time only — requeue waits excluded
            "decode_tokens_per_sec": round((n - 1) / req.active_s, 2)
            if n > 1 and req.active_s > 0 else None,
            "itl_ms_p50": round(float(np.percentile(gaps, 50)), 3)
            if gaps.size else None,
            "itl_ms_p99": round(float(np.percentile(gaps, 99)), 3)
            if gaps.size else None,
            # raw gaps (bounded) so the harness can correct the first
            # gap for scheduled-arrival lateness and pool across
            # requests
            "itl_gaps_ms": [round(float(g), 3) for g in gaps[:512]],
            "preemptions": req.preemptions,
            "timed_out": req.timed_out,
        }

    def expire_queued_request(self, req: Request, now: float):
        """Deliver a QUEUED request as deadline-expired (it never took
        a slot, so there is nothing to free) — the one place this
        bookkeeping lives; the engine's own sweep and the disaggregated
        wrapper's queue both route here."""
        req.timed_out = True
        req.done = True
        req.t_finish = now
        req.queued_s += now - req.t_queue_since
        self._timings["deadline_retirements"] += 1
        self._deliver(req)

    def _retire_expired(self):
        """Deadline sweep (per step): queued requests past their
        deadline are delivered empty without ever taking a slot; active
        ones are retired mid-generation — slot and paged blocks freed —
        with the tokens they produced so far."""
        now = time.perf_counter()
        expired = [r for r in self._queue
                   if r.deadline is not None and now >= r.deadline]
        for r in expired:
            self._queue.remove(r)
            self.expire_queued_request(r, now)
        for req in list(self._slots):
            if req is not None and req.deadline is not None \
                    and now >= req.deadline:
                req.timed_out = True
                self._timings["deadline_retirements"] += 1
                self._retire(req)

    @property
    def num_active(self) -> int:
        return sum(1 for r in self._slots if r is not None)

    @property
    def blocks_in_use(self) -> Optional[int]:
        return self._alloc.num_in_use if self._alloc else None

    @property
    def _admitting(self) -> bool:
        """Admission gate: closed while draining (engine.drain or a
        fired PreemptionGuard) — in-flight slots finish, the queue
        waits/returns."""
        return not self._draining and (
            self._guard is None or not self._guard.preempted)

    def _watchdog_beat(self):
        """Arm the stall watchdog on the first tick when
        PADDLE_TPU_WATCHDOG_S is set, then heartbeat it."""
        if not self._wd_checked:
            self._wd_checked = True
            t = _watchdog.watchdog_seconds()
            if t is not None:
                self.watchdog = _watchdog.Watchdog(
                    t, label=f"decode_{self.telemetry_label}").arm()
        if self.watchdog is not None:
            self.watchdog.beat()

    def _watchdog_idle_if_empty(self):
        """Park the watchdog when the engine leaves this tick with no
        work — a quiet server between arrivals is not a stall."""
        if self.watchdog is not None and not self.has_work:
            self.watchdog.idle()

    def step(self) -> int:
        """Admit queued requests into free slots, then decode one token
        for every active slot. Returns the number of tokens produced
        this step (admission prefills included)."""
        self._watchdog_beat()
        if self._profile is not None:
            # PADDLE_TPU_PROFILE=start:stop over DECODE TICKS
            self._profile.on_step(self._timings["decode_steps"])
        n = self._timings["decode_steps"] + 1
        with _spans.step_span("tick", "serve", step_num=n, tick=n) as tick:
            return self._tick(tick, n)

    def _tick(self, tick, n: int) -> int:
        """step()'s body inside its ``tick`` span; the four children
        ``tick/admit``, ``tick/launch``, ``tick/read`` and
        ``tick/commit`` tile it.  `n` numbers the decode tick that this
        call commits, if it commits one.

        The chip never waits for the host where the host can tell what
        the next executable's inputs are WITHOUT reading a token
        (``_reads_can_wait``).  When no active request can end at this
        tick (none has an EOS, none reaches its budget or the cache's
        end), tick n + 1 is launched on the device's own tokens BEFORE
        tick n is read, and the next call finds it in flight.  When a
        fresh request cannot end at its first token, the tick behind
        its prefill is launched with that token taken from the sampler
        on the device, and the host reads it while the tick runs.
        Every tick still gets the inputs the serial order gives it, so
        the tokens are the same; a token that may end a request is read
        before anything is launched behind it, as before."""
        tick_wall0 = time.perf_counter()
        with _spans.span("tick/admit", "serve", tick=n):
            produced = self._admit_queued()
        fresh, self._fresh = self._fresh, []
        launched, self._ahead = self._ahead, None
        if launched is not None:
            launched, later = launched
            tick.note(**later.args)   # on the span of the call that reads it
            self._timings["occupancy_sum"] += later.occupancy
            if fresh and self._may_run_ahead(
                    launched[4] + [(slot, req) for req, slot, _ in fresh]):
                self._launch_ahead(n, launched, fresh)
        else:
            with _spans.span("tick/launch", "serve", tick=n):
                launched = self._launch_decode(tick, fresh=fresh)
        if fresh:
            # returns when the prefills end, the tick behind them running
            with _spans.span("tick/read", "serve", tick=n):
                t0 = time.perf_counter()
                froze = self.num_active > len(fresh)
                for admission in fresh:
                    self._finish_admission(*admission)
                if froze:
                    self._timings["prefill_stall_ms"] += \
                        (time.perf_counter() - t0) * 1e3
        if launched is None:
            self._watchdog_idle_if_empty()
            return produced
        if self._spec is not None:
            produced += self._commit_spec(n, *launched)
            self._watchdog_idle_if_empty()
            return produced
        n_active, sampled, nxt, moe, bound = launched
        if self._ahead is None and self._may_run_ahead(bound):
            self._launch_ahead(n, launched)
        # the ONE host sync of the decode step: the scheduler needs the
        # sampled ids for EOS retirement and admission (the expert-load
        # fold, when present, is a sibling output of the same executable
        # — fetching it here rides the same sync)
        with _spans.span("tick/read", "serve", tick=n):
            t0 = time.perf_counter()
            nxt_np = np.asarray(nxt)
            self._flush_moe()    # parked chunk-tick folds ride this sync
            self._accum_moe(moe)
            async_dispatch.record_host_sync()
            self._timings["sync_ms"] += (time.perf_counter() - t0) * 1e3
        with _spans.span("tick/commit", "serve", tick=n):
            self._timings["decode_steps"] += 1
            self._timings["sampled_ticks"] += sampled
            self._m_ticks.inc()
            self._m_tokens.inc(n_active)
            commit_now = time.perf_counter()
            for slot, req in bound:
                # the slots that were active when the tick was launched
                # (a prefilling row's sampled token and cache write are
                # masked garbage, not a commit); one retired since by a
                # deadline or a drain has its tokens already
                if req.done or self._slots[slot] is not req:
                    continue
                tok = int(nxt_np[slot])
                self._slot_len[slot] += 1    # the token we just appended
                req.generated.append(tok)
                req.token_times.append(commit_now)
                self._next_token[slot] = tok
                produced += 1
                self._timings["tokens_generated"] += 1
                self._retire_if_done(req, tok)
            # flight-recorder ring (host counters only — zero extra
            # syncs) + deterministic stall injection for the watchdog
            # tests
            _flightrec.record(
                "decode_tick",
                dur_ms=(time.perf_counter() - tick_wall0) * 1e3,
                tick=self._timings["decode_steps"], active=n_active,
                tokens=produced)
            from ..testing import faults as _faults
            _faults.maybe_hang(self._timings["decode_steps"])
            self._watchdog_idle_if_empty()
        return produced

    def _launch_ahead(self, n: int, launched, fresh=()):
        """Tick n + 1 behind `launched`, tick n, which is not read yet;
        the next call finds it in ``_ahead`` and notes it on its span."""
        with _spans.span("tick/launch", "serve", tick=n + 1):
            later = _NotedLater()
            self._ahead = (self._launch_decode(later, after=launched,
                                               fresh=fresh), later)

    def _reads_can_wait(self, bound) -> bool:
        """Whether the next executable may be dispatched before the host
        reads the token that each of `bound`'s (slot, request) pairs is
        about to get: its inputs must not depend on the tokens, so none
        of them may end at that token (no EOS, budget and cache room
        for one more).  Dense single-device decoding only: the paged
        tick makes room from the host's lengths, the speculative one
        commits a count the host must read and seeds its draft with the
        first token, a mesh commits its operands."""
        if (self.kv_layout != "dense" or self._chunked
                or self._spec is not None or self.mesh is not None
                or not self._admitting):
            return False
        for slot, req in bound:
            if (req.done or self._slots[slot] is not req
                    or req.eos_id >= 0
                    or len(req.generated) + 1 >= req.max_new_tokens
                    or self._slot_len[slot] + 2 >= self.max_seq_len):
                return False
        return True

    def _may_run_ahead(self, bound) -> bool:
        """Whether the tick after the one that serves `bound` may be
        launched before the host has read `bound`'s tokens: they can
        wait, and `bound` is still all that is active."""
        return self._reads_can_wait(bound) and \
            len(bound) == int(self._active_mask().sum())

    def _admit_queued(self) -> int:
        """``tick/admit``: expire, admit queued requests into free slots
        (running their prefills) and advance chunked prefills.  Returns
        the first tokens produced."""
        produced = 0
        self._m_queue.set(len(self._queue))
        self._retire_expired()
        stall_t0 = time.perf_counter()
        had_active = any(r is not None and not r.prefilling
                         for r in self._slots)
        admitted = 0
        for slot in range(self.batch_slots):
            if not self._admitting:
                break
            if self._slots[slot] is not None or not self._queue:
                continue
            head = self._queue[0]
            # head-of-line memo (ISSUE 20 bugfix): the blocked head's
            # failed radix-match/alloc is NOT re-run until blocks came
            # free (num_free grew) or became evictable (release epoch
            # moved) — deadline expiry above still applies to it
            if (self._alloc is not None and self._hol_block is not None
                    and self._hol_block[0] == head.rid
                    and self._alloc.num_free <= self._hol_block[1]
                    and self._release_epoch == self._hol_block[2]):
                break
            # paged admission is by FREE BLOCKS, not just a free slot;
            # head-of-line FIFO: if the head can't fit, nobody jumps it
            ok = (self._try_admit_chunked(head, slot) if self._chunked
                  else self._try_admit(head, slot))
            if not ok:
                if self._alloc is not None:
                    self._hol_block = (head.rid, self._alloc.num_free,
                                       self._release_epoch)
                break
            self._hol_block = None
            self._queue.popleft()
            admitted += 1
            if not self._chunked:
                produced += 1
        if not self._chunked and admitted and had_active:
            # monolithic admission ran its prefill(s) while live decode
            # streams sat frozen — the interference chunking removes
            self._timings["prefill_stall_ms"] += \
                (time.perf_counter() - stall_t0) * 1e3
        if self._chunked:
            # NOT gated on _admitting: a draining engine must finish
            # the prompts already bound to slots
            produced += self._chunk_tick()
        return produced

    def _active_mask(self):
        return np.asarray(
            [1 if (r is not None and not r.prefilling) else 0
             for r in self._slots], np.int32)

    def _launch_decode(self, tick, after=None, fresh=()):
        """``tick/launch``: the active mask, the uploads and the dispatch
        of the decode (or speculative) step.  None when no slot is
        active; else what the read and the commit need.  `after` is a
        launched tick not yet read (_tick): this one then takes the
        device's own tokens and counts that tick's slots one token
        longer.  `fresh` are admissions whose first token is not read
        yet: each slot's entry of the token vector is placed from its
        sampler's output, device to device."""
        active_np = self._active_mask()
        if not active_np.any():
            return None
        if self._spec is not None:
            return self._launch_spec(tick)
        sampled_in_flight, tokens, in_flight = 0, None, None
        if after is not None:
            sampled_in_flight, tokens = after[1], after[2]
            in_flight = np.zeros_like(active_np)
            in_flight[[slot for slot, _ in after[4]]] = 1
        if self.kv_layout == "paged":
            self._ensure_decode_room()
            # a preemption/memory-capped retirement may have emptied
            # slots; refresh the mask BEFORE accumulating occupancy so
            # the stats describe the decode step that actually runs
            active_np = self._active_mask()
            if not active_np.any():
                return None
            self._timings["block_occupancy_sum"] += \
                self._alloc.num_in_use / self._alloc.capacity
        sampled = int((self._temps > 0).any())
        n_active = self._note_active(
            tick, active_np, 1, in_flight=in_flight,
            sampled_ticks=self._timings["sampled_ticks"] + sampled
            + sampled_in_flight)
        if self.kv_layout == "paged":
            nxt, self._key, cache, moe = self._timed_exec(
                "decode_ms", ("decode", 0), self._decode_paged_jit,
                self.params, self.cache,
                jnp.asarray(self._next_token),
                jnp.asarray(self._tables),
                jnp.asarray(self._slot_len.astype(np.int32)),
                self._key, jnp.asarray(self._temps),
                jnp.asarray(self._top_ps))
        else:
            if tokens is None:
                tokens = jnp.asarray(self._next_token)
            for _, slot, tok in fresh:
                tokens = self._timed_exec(
                    "prefill_ms", ("place_token", 0), self._place_jit,
                    tokens, tok, np.int32(slot))
            if after is not None or fresh:
                self._timings["ticks_launched_unread"] += 1
                self._timings["admissions_read_late"] += len(fresh)
            nxt, self._key, cache, moe = self._timed_exec(
                "decode_ms", ("decode", 0), self._decode_jit,
                self.params, self.cache, tokens,
                jnp.asarray(active_np), self._key,
                jnp.asarray(self._temps), jnp.asarray(self._top_ps))
        self.cache = cache
        bound = [(slot, self._slots[slot])
                 for slot in np.flatnonzero(active_np)]
        return n_active, sampled, nxt, moe, bound

    def _note_active(self, tick, active_np, window: int,
                     in_flight=None, **more) -> int:
        """Occupancy counters of the tick about to launch, and on its
        ``tick`` span the active slots and what the cache says the tick
        has to read (``tick_reads``: ``kv_positions``, the cache
        positions its attention reads, the active slots' lengths with
        the `window` new tokens; a recurrent state's ``state_bytes``),
        and for a cache of rows ``kv_positions_read``, what the tick's
        kernel streams.  `in_flight` marks the slots of a launched tick
        not yet read, behind which this one goes.  Host arithmetic, no
        sync."""
        lens = self._slot_len
        if in_flight is not None:
            # noted, with its occupancy, by the call that reads the tick;
            # it finds every slot of the tick in flight one token longer
            tick.occupancy = float(active_np.mean())
            lens = lens + in_flight
        else:
            self._timings["occupancy_sum"] += float(active_np.mean())
        n_active = int(active_np.sum())
        self._m_active.set(n_active)
        reads = self.cache.tick_reads(active_np, lens, window)
        if self._cache_has_rows:
            reads["kv_positions_read"] = self._kv_positions_read(
                active_np, lens, window)
        tick.note(active=n_active, **reads, **more)
        return n_active

    def _kv_positions_read(self, active_np, lens, window: int) -> int:
        """Cache positions the tick's attention STREAMS, beside the
        ``kv_positions`` it has to read.  Where the decode executable
        traced the dense kernel's length-bounded body
        (``kernel_paths``), the active slots' lengths rounded up as the
        op rounds them (a retired slot's stale in-graph length is not
        the host's to know: what the kernel reads of it is left out);
        on every other path every slot to its capacity."""
        if window == 1 and "decode_attention.bounded" in \
                self.kernel_paths.get(("decode", 0), ()):
            return int(np.dot(active_np, _positions_streamed(
                lens + window, self.max_seq_len)))
        return self.batch_slots * self.max_seq_len

    def _launch_spec(self, tick):
        """The launch of one speculative tick for every active slot:
        draft proposes K, target verifies K+1 in one executable.  Still
        exactly ONE host sync — it just pays for ~K+1 tokens now."""
        k = self._spec.k
        # capacity: a slot without room for the whole K+1 window
        # retires now (the window writes at slot_len..slot_len+K).
        # NB: this is up to K tokens EARLIER than a non-spec engine
        # would stop — the token-identity contract therefore requires
        # prompt + max_new + K <= max_seq (counted below so a
        # mis-sized deployment shows up in stats, not in silence)
        for req in list(self._slots):
            if req is not None and not req.prefilling \
                    and int(self._slot_len[req.slot]) + k + 1 \
                    > self.max_seq_len:
                self._timings["spec_capacity_retirements"] += 1
                self._retire(req)
        if self.kv_layout == "paged":
            self._ensure_decode_room(need_tokens=k + 1)
        # still-prefilling slots (chunked mode) sit the tick out: the
        # verify window's garbage writes on their rows land above their
        # valid length and the next chunk scatters over them first
        active_np = self._active_mask()
        if not active_np.any():
            return None
        if self.kv_layout == "paged":
            self._timings["block_occupancy_sum"] += \
                self._alloc.num_in_use / self._alloc.capacity
        n_active = self._note_active(tick, active_np, k + 1, k=k)
        return n_active, self._spec.tick(active_np)

    def _commit_spec(self, n: int, n_active: int, out) -> int:
        """The read and the commit of a speculative tick: the scheduler
        commits the accepted prefix + bonus token of every slot."""
        k = self._spec.k
        # the ONE host sync of the tick: K+1 target-greedy tokens + the
        # committed count per slot, one int32 readback
        with _spans.span("tick/read", "serve", tick=n):
            t0 = time.perf_counter()
            out_np = np.asarray(out)
            self._flush_moe()    # parked chunk-tick folds ride this sync
            async_dispatch.record_host_sync()
            self._timings["sync_ms"] += (time.perf_counter() - t0) * 1e3
        with _spans.span("tick/commit", "serve", tick=n) as commit:
            self._timings["decode_steps"] += 1
            self._timings["sampled_ticks"] += int((self._temps > 0).any())
            self._timings["spec_ticks"] += 1
            self._timings["spec_slot_ticks"] += n_active
            produced = 0
            commit_now = time.perf_counter()
            for slot, req in enumerate(list(self._slots)):
                if req is None or req.prefilling:
                    continue
                n_emit = int(out_np[slot, k + 1])
                toks = out_np[slot, :k + 1]
                # host mirrors the in-graph length advance (dense) / owns
                # it (paged); EOS/max-new truncation below RETIRES the
                # slot, so the un-truncated advance never leaks into a
                # later tick
                self._slot_len[slot] += n_emit
                emitted = []
                retired = False
                for i in range(n_emit):
                    tok = int(toks[i])
                    req.generated.append(tok)
                    req.token_times.append(commit_now)
                    emitted.append(tok)
                    produced += 1
                    self._timings["tokens_generated"] += 1
                    if tok == req.eos_id or \
                            len(req.generated) >= req.max_new_tokens:
                        retired = True
                        self._retire(req)
                        break
                # count what actually reached the stream — an EOS/max-new
                # truncation must not inflate accepted_tokens_per_tick
                self._timings["spec_tokens_committed"] += len(emitted)
                if not retired and emitted:
                    self._next_token[slot] = emitted[-1]
                    self._spec.after_commit(
                        slot, np.asarray(emitted, np.int32))
            self._m_ticks.inc()
            self._m_tokens.inc(produced)
            commit.note(committed=produced)     # the tick's accept count
            _flightrec.record("spec_tick",
                              tick=self._timings["decode_steps"],
                              active=n_active, committed=produced, k=k)
            from ..testing import faults as _faults
            _faults.maybe_hang(self._timings["decode_steps"])
        return produced

    def step_or_raise(self) -> int:
        """step(), turning a wedged scheduler into an error: zero
        progress with nothing active to retire but a non-empty queue
        can never resolve on its own.  All blocking drivers (run /
        generate / the load harness) share this one stall check."""
        if self._guard is not None and self._guard.preempted \
                and not self._draining:
            # drivers that only know step_or_raise (the load harness)
            # must not busy-spin a preempted engine forever: perform
            # the graceful drain here — in-flight slots finish, the
            # queue parks in undelivered, has_work goes False
            self.undelivered.extend(self.drain(self._guard_timeout))
            return 0
        produced = self.step()
        if produced == 0 and self.num_active == 0 and self._queue \
                and self._admitting:
            raise RuntimeError(
                "admission stalled: queued requests but no free "
                "capacity and nothing active to retire")
        return produced

    @property
    def has_work(self) -> bool:
        return bool(self._queue) or self.num_active > 0

    def run(self) -> Dict[int, np.ndarray]:
        """Drive step() until every queued request finished; returns
        {request_id: generated token ids}.  With a PreemptionGuard
        attached, a SIGTERM mid-run switches to a graceful drain:
        in-flight slots finish, still-queued requests land in
        ``engine.undelivered`` for the operator to hand back."""
        while self.has_work:
            if self._guard is not None and self._guard.preempted:
                self.undelivered.extend(self.drain(self._guard_timeout))
                break
            self.step_or_raise()
        return self.results

    def attach_preemption_guard(self, guard,
                                drain_timeout_s: Optional[float] = None):
        """Hook a resilience.PreemptionGuard: once it fires (SIGTERM/
        SIGINT), run()/generate() stop admitting, finish in-flight
        slots (bounded by drain_timeout_s), and return — the serving
        analogue of the trainer's drain-then-checkpoint."""
        self._guard = guard
        self._guard_timeout = drain_timeout_s
        return self

    def drain(self, timeout_s: Optional[float] = None) -> List[Request]:
        """Graceful shutdown: stop admission, decode until every
        in-flight slot retires (or timeout_s passes — stragglers are
        then force-retired with their partial output and flagged
        timed_out), and return the still-queued Requests so the caller
        can re-enqueue them elsewhere.  Paged pools are verified
        leak-free: with the slots empty and the radix cache flushed,
        every block's refcount must be back on the free list."""
        self._draining = True
        t0 = time.perf_counter()
        try:
            while self.num_active > 0:
                if timeout_s is not None and \
                        time.perf_counter() - t0 > timeout_s:
                    for req in [r for r in self._slots if r is not None]:
                        self._timings["drain_forced_retirements"] += 1
                        req.timed_out = True
                        self._retire(req)
                    break
                self.step()
            leftover = list(self._queue)
            self._queue.clear()
            self.check_leak_free()     # slots empty + queue cleared
            return leftover
        finally:
            self._draining = False

    def prefix_summary(self) -> Optional[dict]:
        """The radix cache's router-facing digest (block-granular
        fingerprint set + hit/evict counters), or None when this engine
        runs without a prefix cache.  Cheap: the fingerprint set is
        maintained incrementally, no tree walk happens here."""
        return self._prefix.summary() if self._prefix is not None else None

    def flush_prefix_cache(self) -> int:
        """Drop every radix-cache node (slot-held blocks survive under
        the slots' own references). Returns blocks released."""
        released = self._prefix.flush() if self._prefix is not None \
            else 0
        if released:
            # freed blocks must wake a memoised blocked head-of-line
            # request (see _hol_block)
            self._release_epoch += 1
        return released

    def set_prefill_chunk(self, chunk: int) -> bool:
        """Hot-apply the chunked-prefill budget.  The scheduler reads
        ``self._chunked`` / ``self.prefill_chunk`` fresh every tick,
        so this is a host-side flag flip — no restart.  A chunk width
        never run before costs one executable compile, paid here when
        the replica is quiesced and lazily at the next chunk tick
        otherwise.  Slots currently mid-prefill pin the switch: returns
        False without changing anything — retry after they graduate."""
        chunk = int(chunk)
        if chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0, got {chunk}")
        if chunk == self.prefill_chunk:
            return True
        if any(r is not None and r.prefilling for r in self._slots):
            return False
        self.prefill_chunk = chunk
        self._chunked = chunk > 0
        if self._chunked and self.num_active == 0 and not self._queue:
            self._warmup_chunked()
        return True

    def check_leak_free(self):
        """Drained-engine invariant: with no active slots, no queue and
        a flushed prefix cache, every pool block must be free."""
        assert self.num_active == 0 and not self._queue, \
            "leak check requires a drained engine"
        if self._alloc is not None:
            self.flush_prefix_cache()
            self._alloc.check_leak_free()

    def warmup(self, buckets: Optional[List[int]] = None):
        """Compile (or deserialize from the persistent cache) the decode
        + sampling executables and the given prefill buckets before
        traffic arrives.  Uses slot 0 (dense) / transient pool blocks
        (paged) with throwaway tokens; lengths are reset afterwards so
        the garbage stays masked.  Paged engines with a prefix cache
        also compile the traced-prefix prefill executable per bucket."""
        assert self.num_active == 0 and not self._queue, \
            "warmup() must run before traffic"
        if self._chunked:
            # chunked mode never runs the bucketed prefill executables
            # — admission binds slots and the chunk executable does all
            # prompt work, so that is what warmup compiles
            self._warmup_chunked()
        elif self.kv_layout == "paged":
            self._warmup_paged(buckets)
        else:
            self._warmup_dense(buckets)
        if self._spec is not None:
            # draft prefill per bucket + the spec tick executable; both
            # caches' lengths are zeroed afterwards (inside)
            self._spec.warmup()
        return self

    def _warmup_dense(self, buckets):
        for b in (buckets or [self.buckets[0]]):
            ids = jnp.zeros((1, b), jnp.int32)
            # warmup runs throwaway tokens — its expert-load fold is
            # discarded so the balance stats describe real traffic only
            logits, cache, _ = self._timed_exec(
                "prefill_ms", ("prefill", b), self._prefill_jit,
                self.params, self.cache, ids, np.int32(0), np.int32(1))
            self.cache = cache
        self._key, sub = jax.random.split(self._key)
        # numpy operands, exactly as _record_admission passes them: jit's
        # fast path keys on the operand kind, so a jax.Array here would
        # leave the tick's first sample call to re-trace
        tok = self._timed_exec(
            "prefill_ms", ("sample", 1), self._sample_jit,
            logits, sub, np.zeros((1,), np.float32),
            np.ones((1,), np.float32))
        nxt, self._key, cache, _ = self._timed_exec(
            "decode_ms", ("decode", 0), self._decode_jit,
            self.params, self.cache,
            jnp.zeros(self.batch_slots, jnp.int32),
            jnp.zeros(self.batch_slots, jnp.int32), self._key,
            jnp.asarray(self._temps), jnp.asarray(self._top_ps))
        if self.mesh is None:
            # and once on the tokens a tick takes where the host's read
            # can wait (_tick): the last tick's own, with a fresh slot's
            # entry placed from its sampler's output, over the host's
            # vector too.  jit's fast path keys on the operand's kind.
            # The key chain stays where the first call left it.
            tokens = nxt
            if self._spec is None:
                for base in (jnp.asarray(self._next_token), nxt):
                    tokens = self._timed_exec(
                        "prefill_ms", ("place_token", 0), self._place_jit,
                        base, tok, np.int32(0))
            _, _, cache, _ = self._timed_exec(
                "decode_ms", ("decode", 0), self._decode_jit,
                self.params, cache, tokens,
                jnp.zeros(self.batch_slots, jnp.int32), self._key,
                jnp.asarray(self._temps), jnp.asarray(self._top_ps))
        # drop the warmup garbage: zero every slot's length (host-side
        # constant, so no extra executable rides the hot path).  On a
        # serving mesh the zeros are COMMITTED like the originals —
        # an uncommitted lengths operand would recompile the first
        # real prefill (jit keys on committed-vs-uncommitted shardings)
        zeros = jnp.zeros((self.batch_slots,), jnp.int32)
        if self.mesh is not None:
            try:
                zeros = self._put(self.mesh, zeros, ("dp",))
            except Exception as e:
                self._shard_failed("warmup_lengths", e)
        self.cache = cache.with_lengths(zeros)
        return self

    def _warmup_paged(self, buckets):
        logits = None
        for b in (buckets or [self.buckets[0]]):
            n = blocks_for(b, self.block_size)
            if n > self._alloc.capacity:
                # a bucket bigger than the whole pool is unadmittable
                # (add_request guard) — nothing will ever run it, so
                # there is nothing to warm
                continue
            blocks = self._alloc.alloc(n)
            assert blocks is not None, "warmup needs an empty pool"
            row = np.zeros(self.blocks_per_slot, np.int32)
            row[:n] = blocks
            ids = jnp.zeros((1, b), jnp.int32)
            logits, cache, _ = self._timed_exec(
                "prefill_ms", ("prefill_paged", b),
                self._prefill_paged_cold_jit,
                self.params, self.cache, ids, jnp.asarray(row),
                np.int32(1))
            self.cache = cache
            if self._prefix is not None:
                logits, cache, _ = self._timed_exec(
                    "prefill_ms", ("prefill_paged_ext", b),
                    self._prefill_paged_ext_jit,
                    self.params, self.cache, ids, jnp.asarray(row),
                    np.int32(0), np.int32(1))
                self.cache = cache
            self._alloc.decref(blocks)
        if logits is not None:
            self._key, sub = jax.random.split(self._key)
            # numpy operands, as _record_admission passes them
            self._timed_exec("prefill_ms", ("sample", 1),
                             self._sample_jit, logits, sub,
                             np.zeros((1,), np.float32),
                             np.ones((1,), np.float32))
        # decode over all-null tables: every write lands in the null
        # block, every slot length is 0 — pure compile fodder
        nxt, self._key, cache, _ = self._timed_exec(
            "decode_ms", ("decode", 0), self._decode_paged_jit,
            self.params, self.cache,
            jnp.zeros(self.batch_slots, jnp.int32),
            jnp.asarray(self._tables),
            jnp.zeros(self.batch_slots, jnp.int32), self._key,
            jnp.asarray(self._temps), jnp.asarray(self._top_ps))
        self.cache = cache
        return self

    def _warmup_chunked(self):
        """Compile the chunked-mode serving set: the chunk executable,
        the batch-wide graduation sampler, and the decode executable.
        All-zero tokens/advance/lengths over the real cache — the
        garbage writes land above length 0 / in the null block, so
        nothing needs resetting afterwards."""
        c = self.prefill_chunk
        toks = jnp.zeros((self.batch_slots, c), jnp.int32)
        adv = jnp.zeros((self.batch_slots,), jnp.int32)
        lens = jnp.zeros((self.batch_slots,), jnp.int32)
        if self.kv_layout == "paged":
            logits, cache, _ = self._timed_exec(
                "prefill_ms", ("prefill_chunk_paged", c),
                self._prefill_chunk_paged_jit,
                self.params, self.cache, toks,
                jnp.asarray(self._tables), lens, adv)
        else:
            logits, cache, _ = self._timed_exec(
                "prefill_ms", ("prefill_chunk", c),
                self._prefill_chunk_jit,
                self.params, self.cache, toks, lens, adv)
        self.cache = cache
        self._key, sub = jax.random.split(self._key)
        self._timed_exec(
            "prefill_ms", ("sample", self.batch_slots),
            self._sample_jit, logits, sub,
            jnp.asarray(self._temps), jnp.asarray(self._top_ps))
        if self.kv_layout == "paged":
            nxt, self._key, cache, _ = self._timed_exec(
                "decode_ms", ("decode", 0), self._decode_paged_jit,
                self.params, self.cache,
                jnp.zeros(self.batch_slots, jnp.int32),
                jnp.asarray(self._tables),
                jnp.zeros(self.batch_slots, jnp.int32), self._key,
                jnp.asarray(self._temps), jnp.asarray(self._top_ps))
        else:
            nxt, self._key, cache, _ = self._timed_exec(
                "decode_ms", ("decode", 0), self._decode_jit,
                self.params, self.cache,
                jnp.zeros(self.batch_slots, jnp.int32),
                jnp.zeros(self.batch_slots, jnp.int32), self._key,
                jnp.asarray(self._temps), jnp.asarray(self._top_ps))
        self.cache = cache
        return self

    # ---- MoE expert-balance plumbing (ISSUE 19) -----------------------
    def _accum_moe(self, moe):
        """Fold one executable's expert-stats output (or None, the
        dense-model case) into the host counters.  Called at the step's
        existing host-sync point — the arrays are siblings of the
        sampled ids, so fetching them costs no extra sync."""
        if moe is None:
            return
        load = np.asarray(moe["load"], np.float64)
        assigned = float(np.asarray(moe["assigned"]))
        if self._moe_load is None:
            self._moe_load = np.zeros_like(load)
        self._moe_load += load
        self._timings["moe_assigned_tokens"] += assigned
        # capacity overflow: gating assigned top_k slots per token, the
        # capacity buckets kept load.sum() of them — the shortfall is
        # exactly the dropped (overflowed) expert assignments
        self._timings["moe_dropped_tokens"] += max(
            0.0, assigned - float(load.sum()))

    def _moe_expert_param_names(self) -> List[str]:
        """Parameter names of the expert FFN weights (the arrays the
        'ep' axis shards).  The '.experts.' segment is the
        MoELayer/ExpertParallelFFN naming contract; the replicated gate
        is deliberately excluded."""
        return [n for n in self.params if ".experts." in n]

    def _moe_expert_bytes_per_device(self) -> int:
        """PER-DEVICE resident bytes of the expert FFN weights, read
        off the committed arrays' shard shapes (falls back to the
        global shape for host-resident/unsharded arrays)."""
        total = 0
        for name in self._moe_expert_param_names():
            arr = self.params[name]
            shape = arr.shape
            try:
                shape = arr.sharding.shard_shape(arr.shape)
            except Exception:
                pass
            total += int(np.prod(shape)) * jnp.dtype(arr.dtype).itemsize
        return total

    def _decode_hbm_bytes_per_tok(self) -> int:
        """The decode loop's HBM read traffic per generated token, from
        the live shapes: every step streams the parameters once
        (amortized over the batch_slots tokens it produces) plus each
        slot's full KV extent
        — int8-aware, counting the 8-bit values AND the f32 scale
        planes the kernels stream alongside them.  Under a tp-sharded
        serving mesh the number is PER SHARD (ISSUE 18): each device
        streams its weight shard and its slice of the KV heads — the
        whole point of tensor-parallel decode is this denominator.
        Expert FFN weights divide by 'ep', not 'tp' (ISSUE 19): a
        device streams only its own expert shard."""
        tp = max(self.tp_degree, 1)
        ep = max(self.ep_degree, 1)
        expert_names = set(self._moe_expert_param_names()) \
            if self._is_moe else set()
        pbytes = 0
        ebytes = 0
        for name, leaf in self.params.items():
            b = int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
            if name in expert_names:
                ebytes += b
            else:
                pbytes += b
        pbytes //= tp
        # mirror the sharding helpers: experts replicate when ep does
        # not divide them, and the traffic number must say what runs
        if ep > 1 and self.model.cfg.moe_num_experts % ep == 0:
            ebytes //= ep
        pbytes += ebytes
        if self.kv_layout == "paged":
            per_slot_pos = self.blocks_per_slot * self.block_size
        else:
            per_slot_pos = self.max_seq_len
        kv = self.cache.step_bytes_per_slot(per_slot_pos, tp)
        return int(pbytes / self.batch_slots + kv)

    @property
    def stats(self) -> dict:
        """Cumulative serving stats (SpmdTrainer.stats convention):
        prefill/decode wall-clock, compile_ms_cold (first call per
        executable), host sync time, tokens/sec over decode wall-clock,
        mean slot occupancy, the process-wide XLA compile/trace deltas
        since engine construction — plus, paged, block-pool occupancy,
        preemptions and radix-cache hit rates, and PER-REQUEST records
        (TTFT / decode tokens/sec) the load harness consumes."""
        t = self._timings
        s = {k: (round(v, 3) if isinstance(v, float) else v)
             for k, v in t.items()}
        steps = max(t["decode_steps"], 1)
        s["slot_occupancy"] = round(t["occupancy_sum"] / steps, 4)
        decode_s = t["decode_ms"] / 1e3
        s["decode_tokens_per_sec"] = round(
            t["tokens_generated"] / decode_s, 2) if decode_s > 0 else None
        s["xla_compiles"] = self._counters0.new_compiles
        s["jaxpr_traces"] = self._counters0.new_traces
        s["compile_cache_dir"] = compile_cache.compile_cache_dir()
        s["batch_slots"] = self.batch_slots
        s["buckets"] = list(self.buckets)
        s["donate"] = self._donate
        s["kv_layout"] = self.kv_layout
        s["kv_dtype"] = self.kv_dtype or "dense"
        # chunked prefill (ISSUE 20): mode + chunk size ride every
        # snapshot (loadgen reports, the doctor's 'prefill-stall'
        # rule gates itself off when chunking is on)
        s["chunked_prefill"] = self._chunked
        s["prefill_chunk"] = self.prefill_chunk
        # pod-scale serving (ISSUE 18): tp degree + mesh layout ride
        # every stats snapshot (and through it, loadgen reports)
        s["tp"] = self.tp_degree
        s["ep"] = self.ep_degree
        if self.mesh is not None:
            s["serving_mesh"] = {str(ax): int(n)
                                 for ax, n in self.mesh.shape.items()}
        # expert-balance observability (ISSUE 19): the load histogram,
        # the capacity-overflow rate, and the max/mean skew the
        # 'expert-imbalance' doctor rule reads.  Dense models drop the
        # moe_* accumulator keys entirely (same convention as spec).
        if self._is_moe:
            s["moe_num_experts"] = int(self.model.cfg.moe_num_experts)
            load = self._moe_load
            s["moe_expert_load"] = (
                [round(float(v), 1) for v in load]
                if load is not None else None)
            assigned = t["moe_assigned_tokens"]
            s["moe_dropped_rate"] = round(
                t["moe_dropped_tokens"] / assigned, 4) if assigned else 0.0
            if load is not None and float(load.sum()) > 0:
                s["moe_load_skew"] = round(
                    float(load.max()) / max(float(load.mean()), 1e-9), 3)
            else:
                s["moe_load_skew"] = None
        else:
            s.pop("moe_assigned_tokens", None)
            s.pop("moe_dropped_tokens", None)
        s["decode_hbm_bytes_per_tok"] = self._decode_hbm_bytes_per_tok()
        if self._spec is not None:
            s["spec_k"] = self._spec.k
            # per (tick × active slot): 1.0 is what plain decode pays a
            # host sync for, K+1 is the ceiling
            ticks = t["spec_slot_ticks"]
            per_tick = t["spec_tokens_committed"] / ticks if ticks else 0.0
            s["accepted_tokens_per_tick"] = round(per_tick, 3)
            s["spec_acceptance_rate"] = round(
                (t["spec_tokens_committed"] - ticks)
                / max(ticks * self._spec.k, 1), 4)
            if ticks:
                # one tick streams the target once (the window pass is
                # byte-wise one decode step) + the draft ~K times, and
                # emits per_tick tokens: the amortized read traffic is
                # the number the ISSUE wants to see drop
                s["decode_hbm_bytes_per_tok"] = int(
                    (s["decode_hbm_bytes_per_tok"]
                     + self._spec.k * self._spec.step_hbm_bytes())
                    / max(per_tick, 1.0))
        else:
            s.pop("spec_ticks", None)
            s.pop("spec_tokens_committed", None)
            s.pop("spec_slot_ticks", None)
            s.pop("spec_capacity_retirements", None)
        if self.kv_layout == "paged":
            s["kv_block_size"] = self.block_size
            s["kv_blocks_total"] = self._alloc.capacity
            s["kv_blocks_in_use"] = self._alloc.num_in_use
            s["block_occupancy"] = round(
                t["block_occupancy_sum"] / steps, 4)
            if self._prefix is not None:
                s.update(self._prefix.stats)
                # the router-facing digest, JSON-safe (fingerprints as a
                # count; the raw set rides prefix_summary())
                s["prefix_cache"] = {
                    k: (len(v) if k == "fingerprints" else v)
                    for k, v in self._prefix.summary().items()}
            s.pop("block_occupancy_sum", None)    # internal accumulator
        else:
            s.pop("block_occupancy_sum", None)
            s.pop("preemptions", None)
            s.pop("memory_capped_retirements", None)
        # per-request latency records, not just aggregates (satellite:
        # the load harness computes its percentiles from these)
        s["per_request"] = dict(self.request_stats)
        # queue-expired (deadline) requests never produced a token and
        # have no TTFT — they are counted, not averaged
        ttfts = [r["ttft_ms"] for r in self.request_stats.values()
                 if r["ttft_ms"] is not None]
        if ttfts:
            p50, p99 = np.percentile(ttfts, [50, 99])
            s["ttft_ms_p50"] = round(float(p50), 3)
            s["ttft_ms_p99"] = round(float(p99), 3)
        # inter-token latency pooled across finished requests — the
        # number chunked prefill exists to fix at the tail (the load
        # harness recomputes these with coordinated-omission lateness
        # folded into each request's first gap)
        gaps = [g for r in self.request_stats.values()
                for g in r.get("itl_gaps_ms") or ()]
        if gaps:
            p50, p99 = np.percentile(gaps, [50, 99])
            s["itl_ms_p50"] = round(float(p50), 3)
            s["itl_ms_p99"] = round(float(p99), 3)
        # executable observatory (ISSUE 15): the per-kind roofline
        # digest for THIS engine's executables — populated once
        # something ran the deferred analyses (the report CLI,
        # exec_registry.analyze_all); None until then.  Reading
        # stats never compiles and never syncs.
        s["exec_profile"] = _exec_registry.profile(self._exec_component)
        s["hbm"] = _exec_registry.ledger().snapshot()
        # perf-doctor verdict over the serving signals above
        # (observability.doctor): ranked [{bottleneck, evidence, knob}]
        s["doctor"] = _doctor.diagnose(s, kind="serve")
        return s
