"""The KDA scan's Pallas kernels (``ops/kda_chunk_kernel.py``) under
``interpret`` on the CPU, at a head's published widths (K = V = 128,
chunks of 64): outputs and all five gradients against XLA's chunked form
(``kda_scan._chunked``, the path off the chip) and against the
position-by-position recurrence, at lengths of one chunk, a padded tail
and sixteen chunks, at decays of -8 a step (a chunk's ``exp(-Gamma)``
overflows float32), in float32 and with bf16 operands; and a carried
state that is cut must show."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import ops
from test_kimi_linear import recurrence, scan_inputs

kda = importlib.import_module("paddle_tpu.ops.kda_scan")
kernel = importlib.import_module("paddle_tpu.ops.kda_chunk_kernel")

WIDE = dict(kdim=128, vdim=128)
EVERY = (0, 1, 2, 3, 4)
NAMES = "q k v g beta".split()
# bf16 operands against the float32 form, measured here over these seeds
# and lengths (XLA's own form with bf16 operands reads the same sizes):
# outputs within 0.8% of the largest, a gradient within 1.2% of its norm
BF16_OUT, BF16_GRAD = 0.016, 0.025


@pytest.fixture(autouse=True)
def interpreted():
    ops.set_interpret_mode(True)
    ops.kernel_paths.reset()
    try:
        yield
    finally:
        ops.set_interpret_mode(False)


def xla_form(*args, chunk=64):
    return kda._chunked(*args, chunk)


def weighed(fn):
    weigh = jnp.cos(jnp.arange(128.0))
    return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weigh)


def grads(fn, args):
    return jax.grad(weighed(fn), argnums=EVERY)(*args)


def assert_gradients_close(got, want):
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.isfinite(np.asarray(a)).all(), name
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a / scale, b / scale, atol=5e-6,
                                   err_msg=name)


@pytest.mark.parametrize("oracle", ["xla_form", "recurrence"])
@pytest.mark.parametrize("length,heads", [(64, 2), (200, 3), (1024, 1)])
def test_kernel_forward_matches(length, heads, oracle):
    args = scan_inputs(3, 1, length, heads=heads, **WIDE)
    assert float(jnp.cumsum(args[3], 1)[:, :64].min()) < -200
    got = ops.kda_scan(*args, chunk=64)
    assert ops.kernel_paths.counts()["kda_scan"] == \
        {"kernel": 1, "composite": 0}
    want = {"xla_form": xla_form, "recurrence": recurrence}[oracle](*args)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("oracle", ["xla_form", "recurrence"])
@pytest.mark.parametrize("length,heads", [(64, 2), (200, 3), (1024, 1)])
def test_kernel_gradients_match(length, heads, oracle):
    """Every input's gradient under decays whose whole-chunk exp(-Gamma)
    overflows: nothing is NaN or inf, and the decay's own gradient
    agrees.  (At 200 the tail is padded: positions of g = beta = 0
    neither decay nor write, and their gradients are cut away.)"""
    args = scan_inputs(4, 1, length, heads=heads, **WIDE)
    assert float(jnp.exp(-jnp.cumsum(args[3], 1)[:, :64]).max()) == np.inf
    assert_gradients_close(
        grads(lambda *a: ops.kda_scan(*a, chunk=64), args),
        grads({"xla_form": xla_form, "recurrence": recurrence}[oracle],
              args))


@pytest.mark.parametrize("chunk", [16, 32, 128])
def test_kernel_serves_other_chunks(chunk):
    args = scan_inputs(7, 2, 300, heads=2, **WIDE)
    got = ops.kda_scan(*args, chunk=chunk)
    assert ops.kernel_paths.counts()["kda_scan"]["kernel"] == 1
    np.testing.assert_allclose(got, xla_form(*args, chunk=chunk), atol=2e-6)
    assert_gradients_close(
        grads(lambda *a: ops.kda_scan(*a, chunk=chunk), args),
        grads(lambda *a: xla_form(*a, chunk=chunk), args))


@pytest.mark.parametrize("length", [64, 200, 1024])
def test_kernel_with_bf16_operands(length):
    """q, k and v in bf16 (g and beta stay float32, as the mixer hands
    them over) against the float32 form on the same rounded inputs."""
    full = scan_inputs(8, 1, length, heads=2, **WIDE)
    half = tuple(t.astype(jnp.bfloat16) for t in full[:3]) + full[3:]
    rounded = tuple(t.astype(jnp.float32) for t in half)
    got = ops.kda_scan(*half, chunk=64)
    assert got.dtype == jnp.bfloat16
    want = xla_form(*rounded)
    gap = jnp.max(jnp.abs(got.astype(jnp.float32) - want))
    assert float(gap / jnp.max(jnp.abs(want))) < BF16_OUT
    for name, a, b in zip(
            NAMES, grads(lambda *a: ops.kda_scan(*a, chunk=64), half),
            grads(xla_form, rounded)):
        assert a.dtype == (jnp.float32 if name in ("g", "beta")
                           else jnp.bfloat16), name
        a = a.astype(jnp.float32)
        assert np.isfinite(np.asarray(a)).all(), name
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < \
            BF16_GRAD, name


def test_kernel_with_the_carried_state_cut_differs():
    """Every chunk taken for a sequence of its own: the first chunk
    agrees with the recurrence, what follows does not."""
    args = scan_inputs(5, 1, 256, heads=2, **WIDE)
    cut = lambda t: t.reshape((-1, 64) + t.shape[2:])
    broken = ops.kda_scan(*map(cut, args), chunk=64).reshape(1, 256, 2, 128)
    want = recurrence(*args)
    np.testing.assert_allclose(broken[:, :64], want[:, :64], atol=2e-6)
    assert float(jnp.max(jnp.abs(broken[:, 64:] - want[:, 64:]))) > 0.01


def test_heads_are_taken_four_a_step():
    """Six heads go three a grid step, eight go four; the outputs are
    those of the heads taken one by one."""
    assert [kernel._heads_a_step(n) for n in (1, 4, 6, 8, 32)] == \
        [1, 4, 3, 4, 4]
    args = scan_inputs(9, 1, 128, heads=6, **WIDE)
    got = ops.kda_scan(*args, chunk=64)
    for h in range(6):
        one = ops.kda_scan(*(t[:, :, h:h + 1] for t in args), chunk=64)
        np.testing.assert_allclose(got[:, :, h:h + 1], one, atol=1e-7)
