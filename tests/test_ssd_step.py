"""The state-space recurrence's serving forms (``ops/ssd_scan.py``)
against the recurrence itself in float64 numpy: the single-token step,
the chunked entry's returned
state, chunks then steps against one sequential pass (with an initial
state and without), positions past ``real`` leaving state and window,
the one-token convolution against ``causal_conv1d``, and ``ssd_scan`` as
the training model calls it unchanged."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models import MambaLayerView

S = importlib.import_module("paddle_tpu.ops.ssd_scan")


def inputs(b=2, s=37, h=8, p=16, g=2, n=128, seed=0):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: rng.normal(size=shape).astype(np.float32)
    dt = np.log1p(np.exp(draw(b, s, h) - 1.0)).astype(np.float32)
    a_neg = -np.exp(0.5 * draw(h)).astype(np.float32)
    return draw(b, s, h, p), dt, a_neg, draw(b, s, g, n), draw(b, s, g, n)


def sequential(x, dt, a_neg, b_mat, c_mat, state=None):
    """The recurrence in float64, position by position: ``(y [b, s, H,
    P], the state after the last position [b, H, P, N])``."""
    x, dt, a_neg, b_mat, c_mat = (np.asarray(t, np.float64)
                                  for t in (x, dt, a_neg, b_mat, c_mat))
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2:]
    st = np.zeros((bsz, h, p, n)) if state is None \
        else np.asarray(state, np.float64).copy()
    ys = []
    for t in range(s):
        bh = np.repeat(b_mat[:, t], h // g, axis=1)
        ch = np.repeat(c_mat[:, t], h // g, axis=1)
        st = np.exp(dt[:, t] * a_neg)[..., None, None] * st + \
            (dt[:, t, :, None] * x[:, t])[..., None] * bh[:, :, None, :]
        ys.append((st * ch[:, :, None, :]).sum(-1))
    return np.stack(ys, 1), st


def one_step(active=None, seed=0, **shape):
    x, dt, a_neg, b_mat, c_mat = inputs(s=1, seed=seed, **shape)
    state = np.random.default_rng(seed + 1).normal(
        size=x.shape[:1] + x.shape[2:] + b_mat.shape[-1:]).astype(np.float32)
    got = S.ssd_step(x[:, 0], dt[:, 0], a_neg, b_mat[:, 0], c_mat[:, 0],
                     jnp.asarray(state), active)
    if active is not None:
        dt = dt * np.asarray(active, np.float32)[:, None, None]
    want = sequential(x, dt, a_neg, b_mat, c_mat, state)
    return got, (want[0][:, 0], want[1]), state


@pytest.mark.parametrize("shape", [dict(g=1), dict(g=2), dict(g=8),
                                   dict(b=2, h=64, p=64, g=1, n=128)])
def test_step_matches_the_recurrence(shape):
    """Small shapes and the published one (64 heads of 64, one group)."""
    (y, st), (want_y, want_st), _ = one_step(**shape)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(st, want_st, rtol=1e-6, atol=1e-6)
    assert y.dtype == st.dtype == jnp.float32


def test_an_inactive_slot_neither_decays_nor_writes():
    (y, st), _, before = one_step(np.array([0, 1]))
    np.testing.assert_array_equal(np.asarray(st)[0], before[0])
    assert not np.array_equal(np.asarray(st)[1], before[1])


@pytest.mark.parametrize("chunk, s", [(16, 37), (16, 64), (128, 40)])
@pytest.mark.parametrize("start", ["zero", "given"])
def test_chunked_entry_returns_the_last_state(chunk, s, start):
    x, dt, a_neg, b_mat, c_mat = inputs(s=s, seed=4)
    init = None if start == "zero" else np.random.default_rng(9).normal(
        size=(2, 8, 16, 128)).astype(np.float32)
    y, st = S.ssd_scan_with_state(
        x, dt, a_neg, b_mat, c_mat, chunk,
        None if init is None else jnp.asarray(init).reshape(2, 2, 4, 16, 128))
    want_y, want_st = sequential(x, dt, a_neg, b_mat, c_mat, init)
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st).reshape(2, 8, 16, 128),
                               want_st, rtol=1e-5, atol=1e-5)
    assert st.dtype == jnp.float32 and st.shape == (2, 2, 4, 16, 128)


@pytest.mark.parametrize("start", ["zero", "given"])
def test_chunks_then_steps_equal_one_sequential_pass(start):
    """L tokens through the chunked form, T through the step, the state
    handed over: the recurrence over L + T."""
    lead, tail = 29, 7
    x, dt, a_neg, b_mat, c_mat = inputs(s=lead + tail, seed=5)
    init = None if start == "zero" else np.random.default_rng(8).normal(
        size=(2, 8, 16, 128)).astype(np.float32)
    view = MambaLayerView(None if init is None else jnp.asarray(init), None,
                          chunk=8)
    head = lambda t: jnp.asarray(t[:, :lead])
    y0, view = view.absorb(head(x), head(dt), a_neg, head(b_mat),
                           head(c_mat)).read()
    ys = [np.asarray(y0)]
    for t in range(lead, lead + tail):
        at = lambda a: jnp.asarray(a[:, t:t + 1])
        y, view = view.absorb(at(x), at(dt), a_neg, at(b_mat),
                              at(c_mat)).read()
        ys.append(np.asarray(y))
    want_y, want_st = sequential(x, dt, a_neg, b_mat, c_mat, init)
    np.testing.assert_allclose(np.concatenate(ys, 1), want_y, rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(view.state, want_st, rtol=1e-5, atol=1e-5)


def test_positions_past_real_leave_state_and_window():
    """A padded bucket: the state and the window are those of the real
    tokens alone, whatever the padding holds."""
    x, dt, a_neg, b_mat, c_mat = inputs(s=32, seed=6)
    real = np.array([19, 2], np.int32)
    view = MambaLayerView(None, None, chunk=8)
    _, view = view.absorb(*(jnp.asarray(t) for t in (x, dt)), a_neg,
                          jnp.asarray(b_mat), jnp.asarray(c_mat),
                          jnp.asarray(real)).read()
    for row, n in enumerate(real):
        cut = lambda t: t[row:row + 1, :n]
        _, want = sequential(cut(x), cut(dt), a_neg, cut(b_mat), cut(c_mat))
        np.testing.assert_allclose(np.asarray(view.state)[row], want[0],
                                   rtol=1e-5, atol=1e-5)
    rng = np.random.default_rng(2)
    xc = rng.normal(size=(2, 32, 24)).astype(np.float32)
    w = rng.normal(size=(24, 4)).astype(np.float32)
    bias = rng.normal(size=(24,)).astype(np.float32)
    _, view = MambaLayerView(None, None).convolve(
        jnp.asarray(xc), jnp.asarray(w), jnp.asarray(bias),
        jnp.asarray(real))
    np.testing.assert_array_equal(np.asarray(view.window)[0], xc[0, 16:19])
    np.testing.assert_array_equal(np.asarray(view.window)[1, 0], 0)  # left pad
    np.testing.assert_array_equal(np.asarray(view.window)[1, 1:], xc[1, :2])


@pytest.mark.parametrize("bias", [True, False])
def test_one_token_convolution_equals_the_causal_one(bias):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 21, 24)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(24, 4)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(24,)).astype(np.float32)) if bias \
        else None
    want = np.asarray(S.causal_conv1d(x, w, b))
    # a prefill of 9 positions, then one position at a time over its window
    view = MambaLayerView(None, None)
    y, view = view.convolve(x[:, :9], w, b)
    got = [np.asarray(y)]
    for t in range(9, 21):
        y, view = view.convolve(x[:, t:t + 1], w, b)
        got.append(np.asarray(y))
    np.testing.assert_allclose(np.concatenate(got, 1), want, rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(view.window, x[:, 18:21])
    # a window of several positions continuing a window
    y, _ = MambaLayerView(None, x[:, 6:9]).convolve(x[:, 9:15], w, b)
    np.testing.assert_allclose(y, want[:, 9:15], rtol=1e-6, atol=1e-6)
    # an inactive slot keeps its window
    _, kept = MambaLayerView(None, x[:, 6:9]).convolve(
        x[:, 9:10], w, b, jnp.asarray([0, 1]))
    np.testing.assert_array_equal(kept.window[0], x[0, 6:9])
    np.testing.assert_array_equal(kept.window[1], x[1, 7:10])


def test_ssd_scan_as_the_training_model_calls_it_is_unchanged():
    """The entry the Nemotron mixer calls: y alone, the state dropped,
    and the same numbers as the stateful entry from zero."""
    x, dt, a_neg, b_mat, c_mat = inputs(s=40, seed=7)
    y = S.ssd_scan(x, dt, a_neg, b_mat, c_mat, chunk=16)
    y2, _ = S.ssd_scan_with_state(x, dt, a_neg, b_mat, c_mat, 16)
    np.testing.assert_array_equal(y, y2)
    want, _ = sequential(x, dt, a_neg, b_mat, c_mat)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)
    text = jax.jit(lambda *a: S.ssd_scan(*a, chunk=16)).lower(
        x, dt, a_neg, b_mat, c_mat).as_text()
    assert text.count("stablehlo.while") == 1
