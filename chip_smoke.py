#!/usr/bin/env python3
"""First contact with the chip: one train run and one served workload
through the entry points a user calls, checked by the repo's own means.

    python chip_smoke.py              # one TPU chip: train + dense/paged serving
    python chip_smoke.py --chips 4    # four chips: dp2 x tp2 ZeRO-2 training
                                      # and tp=4 serving against one-device
                                      # runs, and nothing else
    python chip_smoke.py --rehearse [--chips 4]
                                      # CPU rehearsal: tiny sizes, kernels
                                      # interpreted; never a chip result

One process drives the chip(s): nothing here starts a child, and this is
the only process that imports JAX.  Weights are random from a fixed seed;
no network, no data.  Any failed check raises, so the script cannot exit
0 with a phase that failed.  The last line of stdout is the verdict:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``
on a chip; a rehearsal's last line also carries ``"rehearsal": true`` and
the platform it really ran on (cpu).

Timings printed on the per-phase lines are smoke timings (set-up and a
handful of steps), not results.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

PHASES = ("train", "serve", "train4", "serve4")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): the one-chip phases; 4: only the "
                         "four-chip phases and what they are compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at a tiny size with kernels "
                         "interpreted (a rehearsal, never a chip result)")
    ap.add_argument("--fail-phase", choices=PHASES, default=None,
                    help="raise inside the named phase (tests the "
                         "non-zero exit)")
    return ap.parse_args(argv)


def say(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke: {msg}")


# Serving weights are drawn three times wider than the config's default
# 0.02.  At 0.02 a random-weight GPT-3 attends almost uniformly over a few
# hundred random positions, the attention output averages out, and greedy
# decoding settles on repeating one token whatever the prompt was: token
# equality then says little about the KV cache.  At 0.06 attention is
# peaked and every generated token depends on the whole context (changing
# one early prompt token changes all of them), so a cache or attention
# fault shows in the tokens.  Width, depth and every shape are untouched.
SERVE_INIT_RANGE = 0.06
# How far below the reference's maximum the reference's logit of the
# engine's token may lie: a real near-tie of the reference's top two is
# accepted, anything else is not.  The logits have a standard deviation of
# about 2.7, and noise of 1e-6 on the embeddings moved them by about 1e-5
# in a CPU run of the plain forward.
LOGIT_TOL = 2e-3


class Sizes:
    """What runs: published width and depth on the chip, a toy in the
    rehearsal."""

    def __init__(self, rehearse: bool):
        from dataclasses import replace
        from paddle_tpu.models import GPTConfig
        from paddle_tpu.models.gpt import gpt_configs
        cfgs = gpt_configs()
        self.rehearse = rehearse
        if rehearse:
            tiny = GPTConfig(vocab_size=512, hidden_size=256, num_layers=2,
                             num_heads=4, max_seq_len=128)
            self.train_cfg, self.train_name = tiny, "rehearsal-tiny"
            self.serve_cfg = replace(tiny, max_seq_len=256,
                                     initializer_range=SERVE_INIT_RANGE)
            self.serve_name = "rehearsal-tiny"
            self.seq, self.batch, self.steps = 128, 4, 4
            self.slots, self.bucket, self.gen = 2, 128, 6
            self.prompt_lens = (20, 33, 47)
            self.kv_blocks = None
        else:
            self.train_cfg, self.train_name = cfgs["gpt3-350m"], "gpt3-350m"
            self.serve_cfg = replace(cfgs["gpt3-1.3b"],
                                     initializer_range=SERVE_INIT_RANGE)
            self.serve_name = "gpt3-1.3b"
            self.seq, self.batch, self.steps = 2048, 4, 5
            self.slots, self.bucket, self.gen = 8, 512, 48
            self.prompt_lens = (200, 257, 311, 364, 402, 230)
            # the paged pool is sized to the traffic (6 blocks = 768
            # positions a slot), as a deployment sizes it to its HBM: the
            # dense-equivalent default (8 x 16 blocks, 6 GiB in f32) does
            # not fit beside the weights, because the paged decode step
            # holds a second copy of the pool while it runs (ROADMAP S4)
            self.kv_blocks = self.slots * 6


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------
def run_train(sz: Sizes, mesh_axes: dict, devices, steps: int,
              zero2: bool = False):
    """`steps` train steps of the default recipe (bf16 AMP, flash
    attention, fused CE, scan over layers, selective remat) through
    SpmdTrainer.train_step on the given mesh.  Returns (losses, info,
    trainer, per-device bytes in use before the trainer was built)."""
    from dataclasses import replace
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed import SpmdTrainer, create_mesh
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
    from paddle_tpu.observability import exec_registry
    from paddle_tpu.ops import kernel_paths
    from paddle_tpu.utils import compile_counter

    cfg = replace(sz.train_cfg, max_seq_len=sz.seq,
                  use_flash_attention=True, fused_ce=True)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.Adam(learning_rate=3e-4,
                                parameters=model.parameters())
    crit = GPTPretrainingCriterion()
    st = DistributedStrategy()
    st.amp = True
    st.recompute = True
    st.recompute_configs = {"policy": "dots_no_batch", "scan_layers": True}
    if zero2:
        st.sharding = True
        st.sharding_configs = {"stage": 2}
    mesh = create_mesh(mesh_axes, devices=devices)
    # the host-built model stays on the first device; what the trainer
    # places on the mesh is measured from here
    bytes_before = device_bytes(devices)
    trainer = SpmdTrainer(model, opt, lambda o, l: crit(o, l), mesh=mesh,
                          strategy=st)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (sz.batch, sz.seq)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)

    kernel_paths.reset()
    t0 = time.perf_counter()
    losses = [float(trainer.train_step(ids, labels))]
    first_s = time.perf_counter() - t0
    snap = compile_counter.snapshot()
    step_ms = []
    for _ in range(steps - 1):
        t0 = time.perf_counter()
        losses.append(float(trainer.train_step(ids, labels)))
        step_ms.append((time.perf_counter() - t0) * 1e3)
    compiles, traces = snap.new_compiles, snap.new_traces

    check(all(np.isfinite(losses)), f"train loss not finite: {losses}")
    check(compiles == 0 and traces == 0,
          f"train compiled after the first step: {compiles} compiles, "
          f"{traces} traces")
    flash = kernel_paths.counts().get("flash_attention",
                                      {"kernel": 0, "composite": 0})
    check(flash["kernel"] > 0 and flash["composite"] == 0,
          f"flash attention took its composite in the train step: "
          f"{flash} ({kernel_paths.last_reason('flash_attention')})")
    custom_calls = None
    if not sz.rehearse:
        # the compiled step itself: the Pallas kernel is a
        # tpu_custom_call in the program text (served from the
        # persistent cache, so this is a deserialize)
        entry = [e for e in exec_registry.registry().entries(
            trainer._exec_component) if e.kind == "train_step"][0]
        check(exec_registry.registry().analyze(entry),
              f"train step analysis failed: {entry.analysis_error}")
        custom_calls = entry.analysis["tpu_custom_calls"]
        check(custom_calls > 0,
              "no tpu_custom_call in the compiled train step")
    info = {"first_step_s": round(first_s, 2),
            "smoke_step_ms": [round(x, 1) for x in step_ms],
            "losses": [round(x, 4) for x in losses],
            "compiles_after_first": compiles, "traces_after_first": traces,
            "flash_paths": flash, "tpu_custom_calls": custom_calls}
    return losses, info, trainer, bytes_before


def phase_train(sz: Sizes, fail: bool) -> None:
    import jax
    if fail:
        raise RuntimeError("forced failure in phase train (--fail-phase)")
    losses, info, _, _ = run_train(sz, {"dp": 1}, jax.devices()[:1],
                                   sz.steps)
    check(losses[-1] < losses[0],
          f"train loss did not fall: {losses[0]} -> {losses[-1]}")
    say("train", model=sz.train_name, seq=sz.seq, batch=sz.batch, **info)


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------
def reference_forward(cfg):
    """(params, ids [S]) -> next-token logits after every position
    [S, V]: a plain jax.numpy forward of the same weights, with no KV
    cache, no kernels and none of the model's code."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    h, nh, eps = cfg.hidden_size, cfg.num_heads, cfg.layer_norm_epsilon
    d = h // nh

    def ln(x, w, b):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * w + b

    def forward(p, ids):
        s = ids.shape[0]
        x = p["gpt.wte.weight"][ids] + p["gpt.wpe.weight"][:s]
        causal = jnp.tril(jnp.ones((s, s), bool))
        for i in range(cfg.num_layers):
            pre = f"gpt.blocks.{i}."
            a = ln(x, p[pre + "ln_1.weight"], p[pre + "ln_1.bias"])
            qkv = a @ p[pre + "attn.qkv_proj.weight"] + \
                p[pre + "attn.qkv_proj.bias"]
            q, k, v = (t.reshape(s, nh, d).transpose(1, 0, 2)
                       for t in jnp.split(qkv, 3, axis=-1))
            sc = jnp.einsum("hqd,hkd->hqk", q, k) / np.sqrt(d)
            sc = jnp.where(causal[None], sc, -1e30)
            o = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(sc, -1), v)
            o = o.transpose(1, 0, 2).reshape(s, h)
            x = x + o @ p[pre + "attn.out_proj.weight"] + \
                p[pre + "attn.out_proj.bias"]
            m = ln(x, p[pre + "ln_2.weight"], p[pre + "ln_2.bias"])
            m = jax.nn.gelu(m @ p[pre + "mlp.up_proj.weight"] +
                            p[pre + "mlp.up_proj.bias"], approximate=True)
            x = x + m @ p[pre + "mlp.down_proj.weight"] + \
                p[pre + "mlp.down_proj.bias"]
        x = ln(x, p["gpt.ln_f.weight"], p["gpt.ln_f.bias"])
        return x @ p["gpt.wte.weight"].T

    return jax.jit(forward)


def check_against_reference(what: str, params, cfg, prompts, toks,
                            pad_to: int):
    """Every token the engine generated, for every request, against the
    plain forward run over the same sequence (prompt + the engine's own
    tokens, so one forward a request and no divergence to chase): token
    j must be the reference's argmax after position len(prompt)+j-1, or
    within LOGIT_TOL of it where the reference's top two nearly tie.
    Returns the summary that goes on the parity line."""
    import numpy as np
    t0 = time.perf_counter()
    forward = reference_forward(cfg)
    checked = exact = 0
    worst, min_gap, distinct = 0.0, float("inf"), set()
    for r, (prompt, gen) in enumerate(zip(prompts, toks)):
        seq = [int(t) for t in prompt] + list(gen[:-1])
        ids = np.zeros(pad_to, np.int32)    # causal: the padding is unseen
        ids[:len(seq)] = seq
        logits = np.asarray(forward(params, ids), np.float32)[:len(seq)]
        check(np.isfinite(logits).all(),
              f"{what}: reference logits not finite (request {r})")
        for j, tok in enumerate(gen):
            row = logits[len(prompt) - 1 + j]
            top2 = np.sort(row)[-2:]
            deficit = float(top2[1] - row[tok])
            check(deficit <= LOGIT_TOL,
                  f"{what}: request {r} token {j}: engine chose {tok} "
                  f"(reference logit {row[tok]:.5f}), the plain forward "
                  f"chooses {int(np.argmax(row))} ({top2[1]:.5f}); "
                  f"{deficit:.5f} > {LOGIT_TOL}")
            checked += 1
            exact += int(tok == int(np.argmax(row)))
            worst = max(worst, deficit)
            min_gap = min(min_gap, float(top2[1] - top2[0]))
        distinct.update(gen)
    # the check is only as good as the tokens are varied: a model that
    # repeats one token would pass it with a broken cache
    check(len(distinct) >= checked // 4,
          f"{what}: only {len(distinct)} distinct tokens in {checked}: "
          f"the outputs do not depend on the context")
    return {"tokens_checked": checked, "argmax_equal": exact,
            "max_logit_deficit": round(worst, 6), "logit_tol": LOGIT_TOL,
            "min_top2_gap": round(min_gap, 5),
            "distinct_tokens": len(distinct),
            "reference_seconds": round(time.perf_counter() - t0, 2)}


def run_serve(sz: Sizes, model, layout: str, prompts, mesh=None):
    """Serve `prompts` greedily through InferenceEngine with the given
    KV layout; returns (tokens per request, info, engine)."""
    from paddle_tpu.inference import InferenceEngine
    from paddle_tpu.observability import exec_registry
    from paddle_tpu.utils import compile_counter

    eng = InferenceEngine(model, batch_slots=sz.slots,
                          max_seq_len=sz.serve_cfg.max_seq_len,
                          kv_layout=layout, prefill_buckets=[sz.bucket],
                          kv_num_blocks=sz.kv_blocks
                          if layout == "paged" else None, mesh=mesh)
    check(eng._donate, "serving executables must donate their cache")
    t0 = time.perf_counter()
    eng.warmup(buckets=[sz.bucket])
    warm_s = time.perf_counter() - t0
    with compile_counter.assert_no_recompiles(f"serving ({layout})") as snap:
        rids = [eng.add_request(p, max_new_tokens=sz.gen) for p in prompts]
        t0 = time.perf_counter()
        out = eng.run()
        wall_s = time.perf_counter() - t0
    # read at the window's end: the analysis below lowers again
    compiles, traces = snap.new_compiles, snap.new_traces
    toks = [[int(t) for t in out[r]] for r in rids]
    check(all(len(t) == sz.gen for t in toks),
          f"{layout}: wrong number of generated tokens "
          f"{[len(t) for t in toks]}")

    op = "paged_decode_attention" if layout == "paged" \
        else "decode_attention"
    paths = eng.kernel_paths.get(("decode", 0), {}).get(
        op, {"kernel": 0, "composite": 0})
    check(paths["kernel"] > 0 and paths["composite"] == 0,
          f"{layout} decode executable traced {op} as {paths}")
    custom_calls = None
    if not sz.rehearse:
        entry = [e for e in exec_registry.registry().entries(
            eng._exec_component) if e.kind == "decode"][0]
        check(exec_registry.registry().analyze(entry),
              f"{layout} decode analysis failed: {entry.analysis_error}")
        custom_calls = entry.analysis["tpu_custom_calls"]
        check(custom_calls > 0,
              f"no tpu_custom_call in the compiled {layout} decode")
    stats = eng.stats
    info = {"layout": layout, "warmup_s": round(warm_s, 2),
            "smoke_wall_s": round(wall_s, 2),
            # dispatch plus the tick's one host read-back, which is where
            # the device time shows on the host's clock
            "smoke_decode_tick_ms": round(
                (stats["decode_ms"] + stats["sync_ms"]) /
                max(stats["decode_steps"], 1), 2),
            "decode_steps": stats["decode_steps"],
            "compiles_after_warmup": compiles,
            "traces_after_warmup": traces,
            "decode_kernel_paths": paths, "tpu_custom_calls": custom_calls,
            "donate": eng._donate}
    return toks, info, eng


def build_serve_model(sz: Sizes):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM
    paddle.seed(1)
    model = GPTForCausalLM(sz.serve_cfg)
    model.eval()
    return model


def make_prompts(sz: Sizes):
    import numpy as np
    rng = np.random.RandomState(7)
    return [rng.randint(1, sz.serve_cfg.vocab_size, (n,)).astype(np.int32)
            for n in sz.prompt_lens]


def describe_difference(a, b) -> str:
    """Where two runs' tokens first differ, and what each chose."""
    return next((f"request {i} token {j}: {x} != {y}"
                 for i, (ra, rb) in enumerate(zip(a, b))
                 for j, (x, y) in enumerate(zip(ra, rb)) if x != y),
                "lengths differ")


def free_trainer(trainer) -> None:
    """Delete a finished trainer's device state (and with it the model it
    was built from, whose arrays it may share): the next phase needs the
    memory, and the collector gives no date."""
    import jax
    for leaf in jax.tree_util.tree_leaves(
            (trainer.params, trainer.opt_state, trainer.buffers)):
        if not leaf.is_deleted():
            leaf.delete()
    trainer.model = None
    gc.collect()


def release(eng) -> None:
    """Free an engine's KV cache before the next engine is built (two
    full-size caches do not fit one chip together).  The cache is the
    engine's own, so its buffers are deleted outright, not left to the
    collector; weights are only un-referenced (a mesh engine's replicated
    weights may share buffers with the model's arrays)."""
    import jax
    cache, eng.cache, eng.params = eng.cache, None, None
    for leaf in jax.tree_util.tree_leaves(cache):
        leaf.delete()
    gc.collect()


def phase_serve(sz: Sizes, fail: bool) -> None:
    import jax
    from paddle_tpu.func import functional_state
    if fail:
        raise RuntimeError("forced failure in phase serve (--fail-phase)")
    # parity is checked at full f32 matmul precision, the way the repo's
    # own tests compare engines: at the default (bf16-pass) precision two
    # correct paths may break near-ties of random-weight logits apart
    jax.config.update("jax_default_matmul_precision", "highest")
    model = build_serve_model(sz)
    prompts = make_prompts(sz)
    params, _ = functional_state(model)

    dense, info_d, eng = run_serve(sz, model, "dense", prompts)
    say("serve", model=sz.serve_name, slots=sz.slots,
        prompt_lens=list(sz.prompt_lens), new_tokens=sz.gen, **info_d)
    release(eng)
    del eng
    paged, info_p, eng = run_serve(sz, model, "paged", prompts)
    say("serve", model=sz.serve_name, slots=sz.slots,
        prompt_lens=list(sz.prompt_lens), new_tokens=sz.gen, **info_p)
    release(eng)
    del eng

    ref = check_against_reference("dense", params, sz.serve_cfg, prompts,
                                  dense, sz.bucket)
    check(dense == paged, "dense and paged serving disagree: "
          f"{describe_difference(dense, paged)}")
    say("serve_parity", dense_equals_paged=True,
        equals_plain_forward=True, requests=len(prompts),
        first_request_tokens=dense[0][:8], **ref)


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------
def device_bytes(devices):
    out = []
    for d in devices:
        ms = d.memory_stats()
        out.append(None if ms is None else int(ms["bytes_in_use"]))
    return out


def check_spread(what: str, before, after, factor: float):
    """Every device's share of what was just placed is within `factor`
    of every other's: 'everything on the first device' fails."""
    if any(b is None for b in after):
        say(what + "_memory", per_device_bytes="not reported by this "
                                               "backend")
        return
    delta = [a - b for a, b in zip(after, before)]
    say(what + "_memory", per_device_bytes_in_use=after,
        per_device_bytes_added=delta, allowed_factor=factor)
    check(min(delta) > 0 and max(delta) <= factor * min(delta),
          f"{what}: device memory is not spread: {delta}")


def check_tp_sharded(what: str, arr, n: int):
    devs = {s.device for s in arr.addressable_shards}
    shard_shape = arr.addressable_shards[0].data.shape
    check(len(devs) == n and shard_shape != arr.shape,
          f"{what}: weight {arr.shape} has shards {shard_shape} on "
          f"{len(devs)} devices, expected a split over {n}")
    say(what + "_sharding", weight_shape=list(arr.shape),
        shard_shape=list(shard_shape), devices=len(devs))


LOSS_RTOL = 3e-2   # bf16 AMP: sharded vs one-device loss, per step


def phase_train4(sz: Sizes, fail: bool) -> None:
    import jax
    import numpy as np
    if fail:
        raise RuntimeError("forced failure in phase train4 (--fail-phase)")
    devs = jax.devices()[:4]
    # tp > 1 keeps the full-logits loss (models.gpt: the blocked CE's
    # vocab slices would all-gather the sharded LM head)
    l4, info4, tr4, before = run_train(sz, {"dp": 2, "tp": 2}, devs, 3,
                                       zero2=True)
    check_spread("train4", before, device_bytes(devs), 1.5)
    check_tp_sharded("train4",
                     tr4.params["gpt.blocks.0.attn.qkv_proj.weight"], 4)
    say("train4", model=sz.train_name, mesh={"dp": 2, "tp": 2}, zero=2,
        **info4)
    free_trainer(tr4)
    del tr4
    l1, info1, tr1, _ = run_train(sz, {"dp": 1}, devs[:1], 3)
    say("train4_reference", mesh={"dp": 1}, **info1)
    free_trainer(tr1)
    del tr1
    rel = [abs(a - b) / abs(b) for a, b in zip(l4, l1)]
    check(max(rel) <= LOSS_RTOL,
          f"dp2xtp2 ZeRO-2 losses {l4} differ from one device {l1} by "
          f"{max(rel):.4f} > {LOSS_RTOL}")
    say("train4_parity", losses_4chip=[round(x, 4) for x in l4],
        losses_1chip=[round(x, 4) for x in l1],
        max_rel_diff=round(float(np.max(rel)), 5), rtol=LOSS_RTOL)


def phase_serve4(sz: Sizes, fail: bool) -> None:
    import jax
    from paddle_tpu.distributed import create_mesh
    from paddle_tpu.func import functional_state
    if fail:
        raise RuntimeError("forced failure in phase serve4 (--fail-phase)")
    jax.config.update("jax_default_matmul_precision", "highest")
    model = build_serve_model(sz)
    prompts = make_prompts(sz)
    devs = jax.devices()[:4]
    before = device_bytes(devs)
    mesh = create_mesh({"dp": 1, "tp": 4}, devices=devs)
    tp4, info4, eng = run_serve(sz, model, "dense", prompts, mesh=mesh)
    # the engine's committed weights and cache, on top of the replicated
    # host-built model that sits on device 0
    check_spread("serve4", before, device_bytes(devs), 1.5)
    check_tp_sharded("serve4",
                     eng.params["gpt.blocks.0.attn.qkv_proj.weight"], 4)
    check_tp_sharded("serve4_cache", eng.cache.k[0], 4)
    say("serve4", model=sz.serve_name, tp=4, **info4)
    release(eng)
    del eng
    tp1, info1, eng = run_serve(sz, model, "dense", prompts)
    say("serve4_reference", model=sz.serve_name, tp=1, **info1)
    release(eng)
    del eng
    params, _ = functional_state(model)
    ref = check_against_reference("tp=4", params, sz.serve_cfg, prompts,
                                  tp4, sz.bucket)
    check(tp4 == tp1, "tp=4 and tp=1 serving disagree: "
          f"{describe_difference(tp4, tp1)}")
    say("serve4_parity", tp4_equals_tp1=True, equals_plain_forward=True,
        requests=len(prompts), new_tokens=sz.gen,
        first_request_tokens=tp4[0][:8], **ref)


# --------------------------------------------------------------------------
def main(argv=None) -> int:
    args = parse_args(argv)
    if args.rehearse:
        # the rehearsal is a CPU program by construction
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips == 4:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                " --xla_force_host_platform_device_count=4").strip()
    t_start = time.perf_counter()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this script measures nothing on a CPU. Use --rehearse for "
              f"the CPU rehearsal.", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX reports {len(devices)}", file=sys.stderr)
        return 2

    from paddle_tpu.ops import set_interpret_mode
    from paddle_tpu.utils import compile_cache
    if args.rehearse:
        set_interpret_mode(True)
    cache_dir = compile_cache.ensure_compile_cache()
    say("start", platform=dev.platform, kind=dev.device_kind,
        devices=len(devices), chips=args.chips, rehearsal=args.rehearse,
        compile_cache_dir=cache_dir,
        cache_entries_at_start=len(os.listdir(cache_dir))
        if cache_dir and os.path.isdir(cache_dir) else 0)

    sz = Sizes(args.rehearse)
    phases = {"train": phase_train, "serve": phase_serve} \
        if args.chips == 1 else \
        {"train4": phase_train4, "serve4": phase_serve4}
    if args.fail_phase and args.fail_phase not in phases:
        print(f"chip_smoke: --fail-phase {args.fail_phase} does not run "
              f"with --chips {args.chips}", file=sys.stderr)
        return 2
    compile_s = {}
    for name, fn in phases.items():
        t0 = time.perf_counter()
        fn(sz, fail=(args.fail_phase == name))
        compile_s[name] = round(time.perf_counter() - t0, 1)
        gc.collect()
    say("done", phase_seconds=compile_s,
        total_seconds=round(time.perf_counter() - t_start, 1),
        compile_cache_dir=cache_dir)

    verdict = {"ok": True}
    if args.rehearse:
        verdict["rehearsal"] = True
    # the chips this run drove, which is what JAX reports on the machines
    # the script is meant for (one chip, or four with --chips 4)
    verdict["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                         "count": args.chips}
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
