#!/usr/bin/env python3
"""Record the small traces that ``test_account.py`` reads, on one TPU v5e:

    chiprun -- python3 benchmark/tests/phase_probe.py

(``--rehearse`` walks the same control flow on the CPU.)

``chiprun_out/phase_probe.xplane.pb``: a 2-layer GPT (2 heads of 128)
behind ``InferenceEngine`` on 4 slots, five ticks with one admission
among them and a pause of the caller between two ticks, so that the trace
holds ``tick`` spans with all four children, a ``prefill``, the decode
program's scoped operations and idle gaps of every phase.
``chiprun_out/remat_probe.xplane.pb``: two steps of the same model through
``SpmdTrainer`` with per-layer remat under a scan, for the recompute
marker.  Both are printed as read by ``readers/account.py``, for reading
by hand.  Copy the first to ``benchmark/tests/data/``.
"""
import glob
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out")
SMALL = dict(vocab_size=512, hidden_size=256, num_layers=2, num_heads=2,
             ffn_hidden_size=1024, max_seq_len=256)


def traced(jax, name, body):
    from benchmark import harness
    tracer = harness.Tracer(jax, name)
    tracer.start()
    body()
    tracer.stop()
    src = glob.glob(os.path.join(tracer.dir, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    os.makedirs(OUT, exist_ok=True)
    dst = os.path.join(OUT, name + ".xplane.pb")
    with open(src, "rb") as f, open(dst, "wb") as g:
        g.write(slim(f.read()))
    return dst


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, wire: int, value) -> bytes:
    key = _varint(number << 3 | wire)
    if wire == 0:
        return key + _varint(value)
    body = bytes(value)
    return key + (_varint(len(body)) if wire == 2 else b"") + body


def slim(xspace: bytes) -> bytes:
    """The trace cut down to what the readers read: the first chip's
    plane with its ``XLA Ops`` and ``XLA Modules`` lines and the event
    metadata those use, and of the host's plane the thread that holds the
    program's spans.  Nothing that is kept is rewritten."""
    from benchmark.readers import account, nemotron
    fields, text = nemotron.fields, nemotron._text
    out = bytearray()
    for number, wire, plane in fields(xspace):
        if number != 1:
            continue
        parts = list(fields(plane))
        name = next(text(v) for n, _, v in parts if n == 2)
        if name not in ("/device:TPU:0", account.HOST_PLANE):
            continue
        spans = {key for key, meta in (
            nemotron._map_entry(v) for n, _, v in parts if n == 4)
            if any(n == 2 and text(t) in account.PHASE_OF
                   for n, _, t in fields(meta))}
        kept, used = bytearray(), set()
        for n, w, v in parts:
            if n != 3:
                continue
            line = list(fields(v))
            label = next((text(t) for m, _, t in line if m == 2), "")
            ids = {next(t for k, kw, t in fields(ev) if k == 1)
                   for m, _, ev in line if m == 4}
            if label in ("XLA Ops", "XLA Modules") or ids & spans:
                kept += _field(3, 2, v)
                used |= ids
        body = bytearray()
        for n, w, v in parts:
            if n == 3:
                continue
            if n == 4 and nemotron._map_entry(v)[0] not in used:
                continue
            body += _field(n, w, v)
        out += _field(1, 2, bytes(body) + bytes(kept))
    return bytes(out)


def show(path):
    from benchmark.readers import account, nemotron
    with open(path, "rb") as f:
        ops = nemotron.device_ops(f.read())
    by_path = {}
    for p, own in account.own_times(ops):
        rec = by_path.setdefault(p, [0, 0])
        rec[0] += 1
        rec[1] += own
    for p, (n, ps) in sorted(by_path.items()):
        print(f"  {ps * 1e-6:10.3f} us x{n:<4d} {account.pass_of(p):5s} "
              f"{account.leaf_of(p) or '-':16s} {p}")
    line = account.timeline(path)
    for s, e, n, stats in line["spans"]:
        print(f"  span {n:18s} {s * 1e-3:14.1f} us +{(e - s) * 1e-3:10.1f} "
              f"{stats}")
    print(json.dumps({
        "file": os.path.basename(path), "bytes": os.path.getsize(path),
        "ops": len(ops), "busy_intervals": len(line["busy"]),
        "gaps_ns": account.cut_gaps(line["busy"], line["spans"]),
        "account_ps": account.account(ops)}))


def serve(jax):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.inference import InferenceEngine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(**SMALL))
    for _, p in model.named_parameters():    # bf16 weights, as served
        p.data = p.data.astype("bfloat16")
    model.eval()
    eng = InferenceEngine(model, batch_slots=4, max_seq_len=256,
                          kv_layout="dense", prefill_buckets=[128])
    eng.warmup(buckets=[128])
    rng = np.random.RandomState(0)
    for n in (40, 70, 100):
        eng.add_request(rng.randint(1, 500, (n,)).astype(np.int32),
                        max_new_tokens=24)
    for _ in range(4):
        eng.step_or_raise()

    def body():
        for i in range(5):
            if i == 2:
                eng.add_request(rng.randint(1, 500, (55,)).astype(np.int32),
                                max_new_tokens=24)
            eng.step_or_raise()
            if i == 3:
                time.sleep(0.002)           # the caller's loop
    return traced(jax, "phase_probe", body)


def train(jax):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed import SpmdTrainer, create_mesh
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(**SMALL, use_flash_attention=True,
                                     fused_ce=True))
    opt = paddle.optimizer.Adam(learning_rate=1e-4,
                                parameters=model.parameters())
    crit = GPTPretrainingCriterion()
    st = DistributedStrategy()
    st.amp = True
    st.recompute = True
    st.recompute_configs = {"policy": "dots_no_batch", "scan_layers": True}
    trainer = SpmdTrainer(model, opt, lambda o, l: crit(o, l),
                          mesh=create_mesh({"dp": 1},
                                           devices=jax.devices()[:1]),
                          strategy=st)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 512, (2, 256)).astype(np.int32)
    step = lambda: float(trainer.train_step(ids, np.roll(ids, -1, 1)))
    step()
    step()
    return traced(jax, "remat_probe", lambda: (step(), step()))


def main() -> int:
    from benchmark import harness
    jax, _ = harness.start_jax(1, "--rehearse" in sys.argv)
    for record in (serve, train):
        path = record(jax)
        print(f"== {path}")
        show(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
