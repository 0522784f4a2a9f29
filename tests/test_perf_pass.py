"""PR-1 step-time performance pass: blocked cross-entropy parity,
flash-attention block-size lookup, scan-over-layers parity.

The contract under test (ISSUE 1): the fused LM loss must match
`cross_entropy` values AND gradients without ever materializing the
[N, V] logits tensor; the block-size lookup must return tabled tiles
with a safe fallback, whatever files and switches the machine holds; the scanned block stack must be numerically identical
to the unrolled loop (loss + grads) both standalone and through
SpmdTrainer's recompute_configs={'scan_layers': True} knob.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.ops import get_block_sizes, pick_vocab_block
from paddle_tpu.ops.fused_cross_entropy import fused_linear_cross_entropy


# ---------------------------------------------------------------------------
# blocked cross-entropy: value + gradient parity vs the reference op
# ---------------------------------------------------------------------------
def _ref_loss(x, w, lab, ignore_index=-100):
    """Reference: full-logits softmax CE, mean over non-ignored rows."""
    logits = x.astype(jnp.float32) @ w.astype(jnp.float32).T
    lse = jax.scipy.special.logsumexp(logits, axis=1)
    picked = jnp.take_along_axis(
        logits, jnp.clip(lab, 0, w.shape[0] - 1)[:, None], axis=1)[:, 0]
    valid = lab != ignore_index
    loss = jnp.where(valid, lse - picked, 0.0)
    return jnp.sum(loss) / jnp.maximum(
        jnp.sum(valid.astype(jnp.float32)), 1.0)


def _problem(n=48, h=24, v=103, dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, h).astype(dtype))
    w = jnp.asarray(rng.randn(v, h).astype(dtype))
    lab = jnp.asarray(rng.randint(0, v, (n,)).astype(np.int32))
    return x, w, lab


@pytest.mark.parametrize("block", [16, 32, 128])  # 103 vocab: pad + partial
def test_fused_ce_matches_reference_fp32(block):
    x, w, lab = _problem()
    lab = lab.at[5].set(-100).at[11].set(-100)

    fused = lambda a, b: fused_linear_cross_entropy(a, b, lab,
                                                    block_size=block)
    ref = lambda a, b: _ref_loss(a, b, lab)
    assert float(fused(x, w)) == pytest.approx(float(ref(x, w)), abs=1e-5)
    gf = jax.grad(fused, argnums=(0, 1))(x, w)
    gr = jax.grad(ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(gf[0], gr[0], atol=1e-5)
    np.testing.assert_allclose(gf[1], gr[1], atol=1e-5)


def test_fused_ce_reductions_and_all_ignored():
    x, w, lab = _problem(n=8, v=50)
    none = fused_linear_cross_entropy(x, w, lab, reduction="none",
                                      block_size=16)
    assert none.shape == (8,)
    s = fused_linear_cross_entropy(x, w, lab, reduction="sum",
                                   block_size=16)
    assert float(s) == pytest.approx(float(jnp.sum(none)), rel=1e-6)
    # every row ignored: loss 0, no NaN from the 0-count denominator
    ig = jnp.full_like(lab, -100)
    m = fused_linear_cross_entropy(x, w, ig, block_size=16)
    assert float(m) == 0.0


def test_fused_ce_bf16_keeps_fp32_accumulation():
    x, w, lab = _problem(n=32, h=32, v=96, dtype=np.float32)
    xb, wb = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    got = float(fused_linear_cross_entropy(xb, wb, lab, block_size=32))
    want = float(_ref_loss(xb, wb, lab))
    assert got == pytest.approx(want, rel=2e-2)
    gx, gw = jax.grad(
        lambda a, b: fused_linear_cross_entropy(a, b, lab, block_size=32),
        argnums=(0, 1))(xb, wb)
    assert gx.dtype == jnp.bfloat16 and gw.dtype == jnp.bfloat16
    assert bool(jnp.all(jnp.isfinite(gx.astype(jnp.float32))))


def test_fused_ce_never_materializes_logits():
    """The point of the op: no [N, V] (or [N, V_padded]) buffer in the
    compiled fwd+bwd. Checked against the lowered HLO text — the
    reference formulation demonstrably contains the tensor, the fused
    one must not."""
    n, h, v, block = 128, 16, 512, 128
    x, w, lab = _problem(n=n, h=h, v=v)
    full = f"{n}x{v}x"          # tensor<128x512xf32> etc.

    ref_txt = jax.jit(jax.grad(lambda a: _ref_loss(a, w, lab))) \
        .lower(x).as_text()
    assert full in ref_txt      # the probe string actually detects it

    fused_txt = jax.jit(jax.grad(
        lambda a, b: fused_linear_cross_entropy(a, b, lab,
                                                block_size=block),
        argnums=(0, 1))).lower(x, w).as_text()
    assert full not in fused_txt


def test_fused_ce_functional_wrapper_grads():
    """nn.functional.fused_linear_cross_entropy: tape-level parity with
    cross_entropy(matmul(x, w.T)) — same loss, same dx/dw."""
    xn, wn, labn = _problem(n=16, h=8, v=40)
    lab2d = np.asarray(labn)[:, None].astype(np.int64)

    x1 = paddle.to_tensor(np.asarray(xn), stop_gradient=False)
    w1 = paddle.to_tensor(np.asarray(wn), stop_gradient=False)
    loss1 = F.fused_linear_cross_entropy(x1, w1,
                                         paddle.to_tensor(lab2d))
    loss1.backward()

    x2 = paddle.to_tensor(np.asarray(xn), stop_gradient=False)
    w2 = paddle.to_tensor(np.asarray(wn), stop_gradient=False)
    logits = paddle.matmul(x2, w2, transpose_y=True)
    loss2 = F.cross_entropy(logits, paddle.to_tensor(lab2d))
    loss2.backward()

    assert float(loss1) == pytest.approx(float(loss2), abs=1e-6)
    np.testing.assert_allclose(x1.grad.numpy(), x2.grad.numpy(),
                               atol=1e-5)
    np.testing.assert_allclose(w1.grad.numpy(), w2.grad.numpy(),
                               atol=1e-5)


def test_pick_vocab_block():
    assert pick_vocab_block(50304) == 2048
    assert pick_vocab_block(100) == 64     # <= vocab, power of two
    assert pick_vocab_block(1) == 1
    assert pick_vocab_block(50304, want=512) == 512


# ---------------------------------------------------------------------------
# flash-attention block sizes
# ---------------------------------------------------------------------------
def test_autotune_table_exact_hit():
    assert get_block_sizes(2048, 64, True, device_kind="v5e") == (512, 512)
    # device_kind strings come from jax verbatim; aliases normalize
    assert get_block_sizes(8192, 128, True, device_kind="TPU v5 lite") \
        == (1024, 1024)


def test_autotune_nearest_seq_fallback():
    # 6144 is not tabled for (v5e, d128, causal): the nearest tabled seq
    # (8192 is 2048 away, 1024 is 5120) supplies the tiles, clamped to
    # divide the actual seq
    assert get_block_sizes(6144, 128, True, device_kind="v5e") \
        == (1024, 1024)


def test_autotune_unknown_kind_uses_defaults():
    assert get_block_sizes(8192, 128, True, device_kind="gpu-h100") \
        == (512, 512)


def test_autotune_clamps_to_short_seq():
    bq, bk = get_block_sizes(128, 64, True, device_kind="v5e")
    assert bq <= 128 and bk <= 128 and 128 % bq == 0 and 128 % bk == 0


# The tables decide, whatever the machine holds.  Before PR 47 a JSON
# store (PADDLE_TPU_TUNING_CACHE, by its docstring ~/.cache/paddle_tpu/
# tuning.json) and two switches stood in front of every table below.
_POISON = (64, 64)       # divides every tabled sequence, is no entry


def _flash_table():
    import importlib
    return importlib.import_module(
        "paddle_tpu.ops.flash_attention")._AUTOTUNE_TABLE


@pytest.fixture
def poisoned_machine(tmp_path, monkeypatch):
    """HOME and every variable that once named a store point at files
    that hold other answers for every key asked below."""
    import json
    home = tmp_path / "home"
    store = home / ".cache" / "paddle_tpu" / "tuning.json"
    store.parent.mkdir(parents=True)
    entries = {f"flash_blocks|{k}|{s}|{d}|{int(c)}": list(_POISON)
               for (k, s, d, c) in _flash_table()}
    entries.update({
        "qmm_tiles|cpu|64|256|512|int8": [32, 128, 128],
        "prefill_buckets|cpu|1024": [48, 1024],
        "moe_a2a_chunks|cpu|64": 8,
        "remat_policy|cpu|128|2|256": "dots",
    })
    store.write_text(json.dumps(entries))
    legacy = tmp_path / "flash_autotune.json"
    legacy.write_text(json.dumps(
        {f"{k}|{s}|{d}|{int(c)}": list(_POISON)
         for (k, s, d, c) in _flash_table()}))
    monkeypatch.setenv("HOME", str(home))
    monkeypatch.setenv("PADDLE_TPU_TUNING_CACHE", str(store))
    monkeypatch.setenv("PADDLE_TPU_TUNING", "sweep")
    return legacy


@pytest.mark.parametrize("mode", [None, "0", "sweep"])
@pytest.mark.parametrize("key", sorted(_flash_table()),
                         ids=lambda k: f"s{k[1]}d{k[2]}")
def test_flash_table_decides_on_a_poisoned_machine(
        poisoned_machine, monkeypatch, key, mode):
    _, seq, d, causal = key
    if mode is not None:
        monkeypatch.setenv("PADDLE_TPU_FLASH_AUTOTUNE", mode)
    # under the chip's own name: the alias is what finds the table
    assert get_block_sizes(seq, d, causal, device_kind="TPU v5 lite") \
        == _flash_table()[key]
    monkeypatch.setenv("PADDLE_TPU_FLASH_AUTOTUNE_CACHE",
                       str(poisoned_machine))
    assert get_block_sizes(seq, d, causal, device_kind="TPU v5 lite") \
        == _flash_table()[key]


def test_qmm_tiles_decided_on_a_poisoned_machine(poisoned_machine):
    from paddle_tpu.ops import get_qmm_tiles
    assert get_qmm_tiles(64, 256, 512) == (64, 256, 512)


def test_prefill_buckets_decided_on_a_poisoned_machine(poisoned_machine):
    from paddle_tpu.inference.engine import default_prefill_buckets
    assert default_prefill_buckets(1024) == [16, 32, 64, 128, 256, 512,
                                             1024]


def test_moe_a2a_chunks_decided_on_a_poisoned_machine(poisoned_machine):
    from paddle_tpu.distributed.overlap import moe_a2a_chunks
    assert moe_a2a_chunks(64) == 2


def test_remat_policy_decided_on_a_poisoned_machine(poisoned_machine):
    from paddle_tpu.distributed import SpmdTrainer, create_mesh
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.models import GPTForCausalLM

    m = GPTForCausalLM(_tiny_cfg())         # hidden 128, 2 layers, 256
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=m.parameters())
    st = DistributedStrategy()
    st.recompute = True
    st.recompute_configs = {"policy": None}     # none named
    mesh = create_mesh({"dp": 1}, devices=jax.devices()[:1])
    SpmdTrainer(m, opt, lambda o, l: o.sum(), mesh=mesh, strategy=st)
    assert m.gpt._recompute_policy == "full"


# ---------------------------------------------------------------------------
# scan-over-layers
# ---------------------------------------------------------------------------
def _tiny_cfg(**kw):
    from dataclasses import replace
    from paddle_tpu.models.gpt import gpt_configs
    return replace(gpt_configs()["gpt3-tiny"], use_flash_attention=False,
                   **kw)


def _gpt_loss_and_grads(cfg, ids, labels, scan, recompute=False):
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
    paddle.seed(0)
    m = GPTForCausalLM(cfg)
    m.train()
    if recompute:
        m.enable_recompute(policy="dots_no_batch")
    m.enable_scan_layers(scan)
    loss = GPTPretrainingCriterion()(m(paddle.to_tensor(ids)),
                                     paddle.to_tensor(labels))
    loss.backward()
    grads = {n: np.asarray(p.grad.data) for n, p in m.named_parameters()
             if p.grad is not None}
    return float(loss), grads


def test_scan_layers_matches_unrolled():
    cfg = _tiny_cfg()
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (2, 32)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)
    l0, g0 = _gpt_loss_and_grads(cfg, ids, labels, scan=False)
    l1, g1 = _gpt_loss_and_grads(cfg, ids, labels, scan=True)
    assert l1 == pytest.approx(l0, abs=1e-5)
    assert set(g0) == set(g1)   # every per-layer param still gets a grad
    for name in g0:
        np.testing.assert_allclose(g1[name], g0[name], atol=2e-4,
                                   err_msg=name)


def test_scan_layers_with_fused_ce_and_remat():
    """The training cells' path: scan + per-iteration jax.checkpoint +
    blocked CE — still bit-comparable to the plain unrolled full-logits run."""
    cfg = _tiny_cfg()
    rng = np.random.RandomState(1)
    ids = rng.randint(0, cfg.vocab_size, (2, 32)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)
    l0, g0 = _gpt_loss_and_grads(cfg, ids, labels, scan=False)
    l1, g1 = _gpt_loss_and_grads(_tiny_cfg(fused_ce=True), ids, labels,
                                 scan=True, recompute=True)
    assert l1 == pytest.approx(l0, abs=1e-5)
    assert set(g0) == set(g1)
    for name in g0:
        np.testing.assert_allclose(g1[name], g0[name], atol=2e-4,
                                   err_msg=name)


def test_scan_falls_back_when_not_scannable():
    """Dropout>0 in train mode would share one mask across layers under
    scan; the model must silently take the unrolled path, not diverge."""
    from paddle_tpu.models import GPTForCausalLM
    cfg = _tiny_cfg(dropout=0.1)
    paddle.seed(0)
    m = GPTForCausalLM(cfg)
    m.train()
    m.enable_scan_layers(True)
    assert not m.gpt._scan_ok(None)
    m.eval()                      # dropout dead: scan becomes legal
    assert m.gpt._scan_ok(None)


def test_spmd_trainer_scan_layers_knob():
    from paddle_tpu.distributed import SpmdTrainer, create_mesh
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion

    cfg = _tiny_cfg(fused_ce=True)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (2, 32)).astype(np.int32)
    labels = np.roll(ids, -1, axis=1).astype(np.int32)

    def run(scan):
        paddle.seed(0)
        m = GPTForCausalLM(cfg)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=m.parameters())
        crit = GPTPretrainingCriterion()
        st = DistributedStrategy()
        st.recompute = True
        st.recompute_configs = {"policy": "dots_no_batch",
                                "scan_layers": scan}
        mesh = create_mesh({"dp": 1}, devices=jax.devices()[:1])
        tr = SpmdTrainer(m, opt, lambda o, l: crit(o, l), mesh=mesh,
                         strategy=st)
        return [float(tr.train_step(ids, labels)) for _ in range(3)]

    np.testing.assert_allclose(run(True), run(False), rtol=1e-4,
                               atol=1e-5)


def test_spmd_trainer_scan_layers_rejects_scanless_model(monkeypatch):
    from paddle_tpu.distributed import SpmdTrainer, create_mesh
    from paddle_tpu.distributed.fleet import DistributedStrategy
    import paddle_tpu.nn as nn

    m = nn.Linear(4, 4)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=m.parameters())
    st = DistributedStrategy()
    st.recompute_configs = {"scan_layers": True}
    mesh = create_mesh({"dp": 1}, devices=jax.devices()[:1])
    with pytest.raises(NotImplementedError, match="enable_scan_layers"):
        SpmdTrainer(m, opt, lambda o, l: o.sum(), mesh=mesh, strategy=st)
