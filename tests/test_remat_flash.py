"""What a remat policy keeps of flash attention (kernel interpreted on CPU).

The flash forward rule names the kernel's output and log-sum-exp
(`flash_out`, `flash_lse`); `checkpoint_policy("dots" | "dots_no_batch")`
keeps them beside the products' outputs, so the backward of a
rematerialised layer does not run the kernel's forward a second time.
`full` and `nothing` recompute it, as before; the undifferentiated call
(serving's prefills) carries no name at all.
"""
import contextlib
import importlib
import io
import logging
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.autograd import no_grad
from paddle_tpu.distributed import SpmdTrainer, create_mesh
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.distributed.mesh import compile_mesh_guard
from paddle_tpu.distributed.recompute import checkpoint_policy
from paddle_tpu.func import functional_call
from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                               GPTPretrainingCriterion)
from paddle_tpu.ops import kernel_paths

from test_flash_attention import _pallas_calls

fa = importlib.import_module("paddle_tpu.ops.flash_attention")

POLICIES = ["dots_no_batch", "dots", "full", "nothing"]
KEEPS = {"dots_no_batch": True, "dots": True, "full": False,
         "nothing": False}


@pytest.fixture(autouse=True)
def _interpret():
    fa.set_interpret_mode(True)
    yield
    fa.set_interpret_mode(False)


def _model(seed=3):
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=128, hidden_size=128, num_layers=2,
                    num_heads=2, max_seq_len=128, fused_ce=True)
    return GPTForCausalLM(cfg)


def _batch(rows=2):
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 128, (rows, 128)).astype(np.int32)
    return ids, np.roll(ids, -1, 1).astype(np.int64)


def _scanned_loss(model, policy):
    """loss(params) of the scanned GPT under `policy`, as a pure
    function of its parameter arrays."""
    model.train()
    model.enable_recompute(policy=policy)
    model.enable_scan_layers(True)
    crit = GPTPretrainingCriterion()
    ids, labels = _batch()
    params = {n: p.data for n, p in model.named_parameters()}

    def loss(ps):
        # the trainer's way: jax differentiates the traced operations,
        # the eager tape stands down
        with no_grad():
            out, _ = functional_call(model, ps, {}, paddle.to_tensor(ids))
            return crit(out, paddle.to_tensor(labels)).data

    return loss, params


# -- (a) one forward kernel a layer where the products are kept -------------
@pytest.mark.parametrize("policy", POLICIES)
def test_forward_kernels_in_the_differentiated_scan(policy):
    """The scan's body is traced once for the forward and once for the
    backward: the flash forward (3 operands) appears once under the two
    `dots` policies and twice, the second the recomputed one, under
    `full` and `nothing`; the backward kernel (6 operands) once."""
    loss, params = _scanned_loss(_model(), policy)
    kernel_paths.reset()
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params)
    assert kernel_paths.counts()["flash_attention"]["composite"] == 0
    operands = sorted(len(e.invars) for e in _pallas_calls(jaxpr.jaxpr))
    assert operands == ([3, 6] if KEEPS[policy] else [3, 3, 6])


def _logged_residuals(f, *args):
    """jax's own log of what a differentiated `jax.checkpoint` saved:
    it names a kept value by its `checkpoint_name`."""
    logged = []
    handler = logging.Handler()
    handler.emit = lambda record: logged.append(record.getMessage())
    logger = logging.getLogger("jax._src.ad_checkpoint")
    logger.addHandler(handler)
    jax.config.update("jax_log_checkpoint_residuals", True)
    try:
        jax.grad(f)(*args)
    finally:
        jax.config.update("jax_log_checkpoint_residuals", False)
        logger.removeHandler(handler)
    return "\n".join(logged)


# -- (b) what the checkpointed body saves -----------------------------------
@pytest.mark.parametrize("policy", ["dots_no_batch", "dots", "full"])
def test_saved_residuals_of_the_checkpointed_block(policy):
    """`print_saved_residuals` of one checkpointed block, and jax's own
    log of what the remat saved.  A kept value that the forward also
    reads (the output feeds the out projection) is printed as the
    no-op `reduce_precision` jax puts on it, so the printed text is
    asked for its shape and site and the log for its name."""
    model = _model()
    blk0 = model.gpt.blocks[0]
    params = {n: p.data for n, p in blk0.named_parameters()}
    x = jnp.ones((2, 128, 128), jnp.float32)

    def body(ps, h):
        with no_grad():
            out, _ = functional_call(blk0, ps, {}, h)
        return out.sum()

    body = jax.checkpoint(body, policy=checkpoint_policy(policy))
    text = io.StringIO()
    with contextlib.redirect_stdout(text):
        jax.ad_checkpoint.print_saved_residuals(body, params, x)
    text = text.getvalue()
    kept_out = [l for l in text.splitlines()
                if l.startswith("f32[2,128,2,64] ")
                and "flash_attention.py" in l]
    assert ("f32[4,1,1,128] named 'flash_lse'" in text) == KEEPS[policy], \
        text
    assert len(kept_out) == KEEPS[policy], text

    logged = _logged_residuals(body, params, x)
    assert "saving inputs with shapes" in logged
    for name in fa.RESIDUAL_NAMES:
        assert (f"named '{name}'" in logged) == KEEPS[policy], logged


# -- (c) keeping and recomputing are the same arithmetic --------------------
def _trainer_grads(policy, mesh, zero3):
    model = _model(seed=11)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=model.parameters())
    crit = GPTPretrainingCriterion()
    st = DistributedStrategy()
    if zero3:
        st.sharding = True
        st.sharding_configs = {"stage": 3, "overlap": True}
    st.recompute = True
    st.recompute_configs = {"scan_layers": True, "policy": policy}
    tr = SpmdTrainer(model, opt, lambda o, l: crit(o, l), mesh=mesh,
                     strategy=st)
    assert tr.zero3_overlap == zero3
    assert model.gpt._recompute_policy == policy
    ids, labels = tr.shard_batch(_batch(rows=mesh.size * 2))
    kernel_paths.reset()
    with compile_mesh_guard(mesh):
        loss, _, grads, _ = jax.jit(
            lambda p, b, i, l: tr._grads_fn(p, b, (i,), (l,)))(
                tr.params, tr.buffers, ids, labels)
    assert kernel_paths.counts()["flash_attention"] == \
        {"kernel": 1, "composite": 0}
    return np.asarray(loss), {n: np.asarray(g) for n, g in grads.items()}


@pytest.mark.parametrize("path", ["spmd", "zero3"])
def test_keeping_equals_recomputing_bit_for_bit(path):
    zero3 = path == "zero3"
    mesh = create_mesh({"dp": 4}, devices=jax.devices()[:4]) if zero3 \
        else create_mesh({"dp": 1}, devices=jax.devices()[:1])
    loss_full, g_full = _trainer_grads("full", mesh, zero3)
    loss_kept, g_kept = _trainer_grads("dots_no_batch", mesh, zero3)
    assert loss_kept.tobytes() == loss_full.tobytes()
    assert set(g_kept) == set(g_full) and len(g_full) > 10
    for name in g_full:
        assert np.abs(g_full[name]).max() > 0, name
        assert g_kept[name].tobytes() == g_full[name].tobytes(), name


# -- (d) the serving path cannot have moved ---------------------------------
@pytest.mark.parametrize("key_mask", [False, True])
def test_undifferentiated_lowering_carries_no_name(key_mask, monkeypatch):
    """A prefill's call: the lowered text of `flash_attention` is the
    same string as with the two `checkpoint_name` calls stubbed to
    identities, and its jaxpr holds no `name` equation; the
    differentiated call holds the two."""
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, 256, 16, 128), jnp.bfloat16)
               for _ in range(3))
    mask = jnp.asarray(np.arange(256)[None] < 200, jnp.int32) \
        if key_mask else None

    def prefill():      # a fresh function a call: no trace is reused
        return lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, kv_mask=mask)

    text = jax.jit(prefill()).lower(q, k, v).as_text()
    jaxpr = str(jax.make_jaxpr(prefill())(q, k, v))
    assert "pallas_call" in jaxpr and "name[" not in jaxpr
    grad = jax.grad(lambda *a: prefill()(*a).astype(jnp.float32).sum())
    names = re.findall(r"name\[name=(\w+)\]",
                       str(jax.make_jaxpr(grad)(q, k, v)))
    assert names == list(fa.RESIDUAL_NAMES)
    # only the undifferentiated call is traced under the stub: a
    # forward rule traced without its names would stay in jax's caches
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    assert jax.jit(prefill()).lower(q, k, v).as_text() == text


# -- (e) the policy names keep their meaning --------------------------------
def test_policy_names():
    assert checkpoint_policy(None) is None
    assert checkpoint_policy("full") is None
    for raw in ("nothing", "everything", "checkpoint_dots",
                "dots_saveable"):
        attr = {"nothing": "nothing_saveable",
                "everything": "everything_saveable"}.get(raw, raw)
        assert checkpoint_policy(raw) is getattr(
            jax.checkpoint_policies, attr)
    with pytest.raises(ValueError, match="unknown recompute policy"):
        checkpoint_policy("flash_too")


@pytest.mark.parametrize("policy", ["dots", "dots_no_batch"])
def test_dots_policies_keep_products_and_the_two_names(policy):
    """What the policy answers, asked as jax asks it: a product without
    batch dimensions and the two names are kept, an element-wise
    operation and another name are not."""
    pol = checkpoint_policy(policy)
    saved = lambda f, *args: _logged_residuals(
        jax.checkpoint(f, policy=pol), *args)
    x = jnp.ones((4, 4))
    named = lambda tag: lambda a: jnp.sin(
        jax.ad_checkpoint.checkpoint_name(jnp.sin(a), tag)).sum()
    assert "named 'flash_out'" in saved(named("flash_out"), x)
    assert "named 'flash_lse'" in saved(named("flash_lse"), x)
    assert "intermediates" not in saved(named("another"), x)
    assert "dot_general" in saved(lambda a: jnp.sin(a @ a).sum(), x)
    assert "intermediates" not in saved(
        lambda a: jnp.sin(jnp.sin(a)).sum(), x)
