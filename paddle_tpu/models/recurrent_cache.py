"""A serving cache for mixers that keep a recurrent state and no rows:
one state a layer and slot, valid at ONE position (the slot's length),
where ``StaticKVCache`` holds a row of keys and values a token.

What that changes for whoever holds the cache (``InferenceEngine``):

- its size does not depend on the sequence length, and a decode tick
  reads and writes all of it whatever the slots' lengths;
- a prefill at a padded bucket has to stop the state at the prompt's
  last real token (``absorb(..., real=prompt_len)``), and a reused slot
  starts from zero: a prefill REPLACES the slot's state, it never
  extends one;
- there is no row to roll back to, to share or to page: speculation, a
  prefix cache, chunked prefill over a window and the paged layout need
  rows, so the engine refuses them for such a model by name.

``RecurrentStateCache`` is the state of ONE kind of layer (power
retention, ``ops.power_retention``).  ``HybridStateCache`` holds a stack
that mixes kinds: one entry a layer, each either rows of keys and values
(``KVRows``, ``StaticKVCache``'s buffers) or a Mamba-2 state beside its
convolution's window (``MambaState``); it answers the engine for the sum
of them.  Both say ``holds_state``: that is what the engine asks before
it accepts an option that needs rows alone.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..ops.power_retention import (RetentionState, init_state,
                                   power_retention_chunked,
                                   power_retention_step)
from ..observability.exec_registry import tree_bytes
from ..ops.ssd_scan import (causal_conv1d, causal_conv1d_step, conv_window,
                            ssd_scan_with_state, ssd_step)
from .gpt import DenseKVLayer, kv_step_bytes, kv_tick_reads

__all__ = ["RecurrentStateCache", "RetentionLayerView", "HybridStateCache",
           "MambaLayerView", "KVRows", "MambaState"]


def _put_slot(buf, new, slot):
    """``buf`` with slot ``slot`` replaced by ``new [1, ...]``, written
    where the buffer lies."""
    return jax.lax.dynamic_update_slice(
        buf, new.astype(buf.dtype),
        (jnp.asarray(slot, jnp.int32),) +
        (jnp.asarray(0, jnp.int32),) * (buf.ndim - 1))


@dataclass
class RetentionLayerView:
    """One layer's retention state as a serving step sees it, behind two
    operations, the recurrent counterpart of ``KVLayerView``'s ``write``
    and ``attend``.

    ``absorb(k, v, log_g, real)`` takes W new tokens a slot (``k``/``v
    [B, W, Hkv, D]``, ``log_g [B, W, Hkv]``) of which the first
    ``real[b]`` are real (None: all); ``read(q)`` runs their queries
    ``[B, W, H, D]``, query i seeing the state after token i, and
    returns ``(y [B, W, H, D] float32, the view after the real
    tokens)``.  The work happens in ``read``: update and read-out are
    ONE pass over the state (it is the whole cost of a decode tick), so
    ``absorb`` only holds the tokens.  ``state`` None is a zero state (a
    slot about to be prefilled) and costs no read.

    W is a static shape and picks the form: one token a slot is the
    step (on the chip a kernel that updates a donated state where it
    lies), a window the chunked form."""

    state: Optional[RetentionState]
    chunk: int = 256
    eps: float = 1e-6
    tokens: Optional[tuple] = None

    def absorb(self, k, v, log_g, real=None) -> "RetentionLayerView":
        return replace(self, tokens=(k, v, log_g, real))

    def read(self, q):
        k, v, log_g, real = self.tokens
        if q.shape[1] > 1:
            y, state = power_retention_chunked(
                q, k, v, log_g, self.state, real, chunk=self.chunk,
                eps=self.eps)
            return y, replace(self, state=state, tokens=None)
        q, k, v, log_g = q[:, 0], k[:, 0], v[:, 0], log_g[:, 0]
        if real is not None:
            # a slot with no real token: gate 1, no write
            on = (jnp.asarray(real) > 0)[:, None]
            k = jnp.where(on[..., None], k, 0)
            log_g = jnp.where(on, log_g, 0)
        state = self.state
        if state is None:
            state = init_state(q.shape[0], k.shape[1], k.shape[2],
                               v.shape[2])
        y, state = power_retention_step(q, k, v, log_g, state, eps=self.eps)
        return y[:, None], replace(self, state=state, tokens=None)


class RecurrentStateCache:
    """``layers``: a tuple of one ``RetentionState`` a layer, every leaf
    ``[batch_slots, ...]`` float32 and its own array (each is donated and
    updated where it lies; nothing is sliced out of, or written back
    into, a stacked array: the reason ``StaticKVCache`` gives).
    ``lengths [batch_slots]`` int32 is each slot's position: the tokens
    its state has absorbed.  ``logical_slot_bytes`` is what the
    mathematics needs a slot (all layers), whatever padding the layout
    adds: what a tick's span reports as moved.

    Registered as a pytree; the engine holds it, donates it to every
    executable, warms it up and resets its lengths like the dense cache."""

    __slots__ = ("layers", "lengths", "logical_slot_bytes", "chunk", "eps")
    # what the engine asks of a cache before it takes an option
    holds_state = True
    has_rows = False

    def __init__(self, layers, lengths, logical_slot_bytes: int,
                 chunk: int = 256, eps: float = 1e-6):
        self.layers, self.lengths = tuple(layers), lengths
        self.logical_slot_bytes = int(logical_slot_bytes)
        self.chunk, self.eps = int(chunk), float(eps)

    @classmethod
    def zeros(cls, num_layers: int, batch_slots: int, kv_heads: int,
              head_dim: int, logical_slot_bytes: int, chunk: int = 256,
              eps: float = 1e-6) -> "RecurrentStateCache":
        return cls([init_state(batch_slots, kv_heads, head_dim)
                    for _ in range(num_layers)],
                   jnp.zeros((int(batch_slots),), jnp.int32),
                   logical_slot_bytes, chunk, eps)

    @property
    def num_layers(self):
        return len(self.layers)

    @property
    def batch_slots(self):
        return self.layers[0].s.shape[0]

    @property
    def dtype(self):
        return self.layers[0].s.dtype

    @property
    def quantized(self) -> bool:
        return False

    @property
    def slot_bytes(self) -> int:
        """Bytes of state a slot holds, all layers, as laid out."""
        return self.held_state_bytes // self.batch_slots

    @property
    def held_state_bytes(self) -> int:
        """Bytes of state held, every slot and layer, as laid out."""
        return tree_bytes(self.layers)

    def _like(self, layers, lengths) -> "RecurrentStateCache":
        return RecurrentStateCache(layers, lengths, self.logical_slot_bytes,
                                   self.chunk, self.eps)

    def with_lengths(self, lengths) -> "RecurrentStateCache":
        """The same states under new per-slot lengths."""
        return self._like(self.layers, lengths)

    def layer(self, i) -> RetentionLayerView:
        """Layer ``i``'s states of all slots, as a decode step's view."""
        return RetentionLayerView(self.layers[i], self.chunk, self.eps)

    def fresh(self) -> RetentionLayerView:
        """A zero state: what a slot about to be prefilled starts from."""
        return RetentionLayerView(None, self.chunk, self.eps)

    def with_layer(self, i, view: RetentionLayerView):
        return self._like(
            self.layers[:i] + (view.state,) + self.layers[i + 1:],
            self.lengths)

    def with_slot(self, i, slot, view: RetentionLayerView):
        """Layer ``i`` with slot ``slot``'s state REPLACED by a one-slot
        view's (a prefill's result); written where the buffer lies."""
        return self.with_layer(i, replace(view, state=jax.tree_util.tree_map(
            lambda buf, new: _put_slot(buf, new, slot), self.layers[i],
            view.state)))

    # ---- what the engine asks of any cache ----------------------------
    def tick_reads(self, active, slot_len, window: int) -> dict:
        """Arguments of the ``tick`` span: no cached position is read
        (``kv_positions`` 0), and ``state_bytes``, the state the tick
        has to read and write: the active slots' own, once each way."""
        return {"kv_positions": 0,
                "state_bytes": 2 * int(np.sum(active)) *
                self.logical_slot_bytes}

    def step_bytes_per_slot(self, positions: int, tp: int = 1) -> int:
        """Bytes of cache a decode step streams for one slot: its whole
        state, read and written, whatever ``positions`` it stands at."""
        return 2 * self.slot_bytes

    def __repr__(self):
        return (f"RecurrentStateCache(layers={self.num_layers}, "
                f"slots={self.batch_slots}, slot_bytes={self.slot_bytes})")


jax.tree_util.register_pytree_node(
    RecurrentStateCache,
    lambda c: ((c.layers, c.lengths),
               (c.logical_slot_bytes, c.chunk, c.eps)),
    lambda aux, ch: RecurrentStateCache(*ch, *aux))


class KVRows(NamedTuple):
    """An attention layer's entry: ``k``/``v [slots, Hkv, capacity, D]``,
    head-major, as ``StaticKVCache`` keeps a layer."""
    k: jax.Array
    v: jax.Array


class MambaState(NamedTuple):
    """A Mamba-2 layer's entry: ``s [slots, H, P, N]`` float32, the
    recurrence's state, and ``window [slots, K-1, C]``, the convolution's
    last ``K - 1`` inputs (oldest first)."""
    s: jax.Array
    window: jax.Array


@dataclass
class MambaLayerView:
    """One Mamba-2 layer's state as a serving step sees it, after
    ``RetentionLayerView``: ``convolve`` runs the depthwise convolution
    of W new positions a slot over the window and moves the window on;
    ``absorb`` takes the W tokens (``x [B, W, H, P]``, ``dt [B, W, H]``
    after its softplus, ``b``/``c [B, W, G, N]``), of which the first
    ``real[b]`` are real (None: all); ``read`` returns ``(y [B, W, H, P]
    float32 without the D skip, the view after the real tokens)`` in ONE
    pass over the state.  ``state`` / ``window`` None are zeros (a slot
    about to be prefilled).  W = 1 is the step, W > 1 the chunked form."""

    state: Optional[jax.Array]
    window: Optional[jax.Array]
    chunk: int = 256
    tokens: Optional[tuple] = None

    def convolve(self, x, weight, bias, real=None):
        """``x [B, W, C]`` -> ``(causal_conv1d of it continuing the
        window, the view with the window after the real positions)``."""
        k = weight.shape[1]
        b, w, _ = x.shape
        window = self.window
        if w == 1:
            if window is None:
                window = jnp.zeros((b, k - 1, x.shape[2]), x.dtype)
            y, moved = causal_conv1d_step(window, x[:, 0], weight, bias)
            if real is not None:
                on = (jnp.asarray(real) > 0)[:, None, None]
                moved = jnp.where(on, moved, window)
            return y[:, None], replace(self, window=moved)
        real = jnp.full((b,), w, jnp.int32) if real is None \
            else jnp.asarray(real, jnp.int32)
        if window is None:
            return causal_conv1d(x, weight, bias), \
                replace(self, window=conv_window(x, real, k))
        full = jnp.concatenate([window.astype(x.dtype), x], axis=1)
        return causal_conv1d(full, weight, bias)[:, k - 1:], \
            replace(self, window=conv_window(full, real + (k - 1), k))

    def absorb(self, x, dt, a_neg, b_mat, c_mat, real=None):
        return replace(self, tokens=(x, dt, a_neg, b_mat, c_mat, real))

    def read(self):
        x, dt, a_neg, b_mat, c_mat, real = self.tokens
        bsz, w, heads, p = x.shape
        groups, n = b_mat.shape[2:]
        if w > 1:
            if real is not None:
                # past a row's real tokens: neither decay nor write
                live = jnp.arange(w, dtype=jnp.int32)[None, :] < \
                    jnp.asarray(real, jnp.int32)[:, None]
                dt = jnp.where(live[..., None], dt, 0)
            state = None if self.state is None else self.state.reshape(
                bsz, groups, heads // groups, p, n)
            y, state = ssd_scan_with_state(x, dt, a_neg, b_mat, c_mat,
                                           self.chunk, state)
            return y.astype(jnp.float32), replace(
                self, state=state.reshape(bsz, heads, p, n), tokens=None)
        state = self.state
        if state is None:
            state = jnp.zeros((bsz, heads, p, n), jnp.float32)
        y, state = ssd_step(x[:, 0], dt[:, 0], a_neg, b_mat[:, 0],
                            c_mat[:, 0], state, real)
        return y[:, None], replace(self, state=state, tokens=None)


def _entry_of(view):
    """A layer's view back as the entry the cache keeps."""
    if isinstance(view, MambaLayerView):
        return MambaState(view.state, view.window)
    return KVRows(view.k, view.v)


class HybridStateCache:
    """A serving cache for a stack that mixes kinds: ``layers`` is a
    tuple with one entry a layer, ``KVRows`` for an attention layer and
    ``MambaState`` for a Mamba-2 layer, every leaf ``[batch_slots, ...]``
    and its own array (each is donated and updated where it lies).
    ``lengths [batch_slots]`` int32 is each slot's position: the rows it
    holds in every ``KVRows`` and the tokens every state has absorbed.
    ``logical_slot_bytes`` is what the mathematics needs of STATE a slot
    (all state layers, windows and rows apart), whatever the layout
    pads: what a tick's span reports as moved.

    A prefill writes a slot's rows and REPLACES its states and windows
    (``with_slot``): a reused slot inherits neither.  Registered as a
    pytree; the engine holds it like the dense cache."""

    __slots__ = ("layers", "lengths", "logical_slot_bytes", "chunk")
    holds_state = True

    def __init__(self, layers, lengths, logical_slot_bytes: int,
                 chunk: int = 256):
        self.layers, self.lengths = tuple(layers), lengths
        self.logical_slot_bytes = int(logical_slot_bytes)
        self.chunk = int(chunk)

    @property
    def num_layers(self):
        return len(self.layers)

    @property
    def batch_slots(self):
        return self.lengths.shape[0]

    @property
    def _rows(self) -> list:
        return [e for e in self.layers if isinstance(e, KVRows)]

    @property
    def has_rows(self) -> bool:
        return bool(self._rows)

    @property
    def capacity(self):
        return self._rows[0].k.shape[2]

    @property
    def quantized(self) -> bool:
        return False

    @property
    def held_state_bytes(self) -> int:
        """Bytes of state and windows held, every slot and state layer,
        as laid out; the rows are not state."""
        return tree_bytes([e for e in self.layers
                            if isinstance(e, MambaState)])

    @property
    def slot_bytes(self) -> int:
        return self.held_state_bytes // self.batch_slots

    def _like(self, layers, lengths) -> "HybridStateCache":
        return HybridStateCache(layers, lengths, self.logical_slot_bytes,
                                self.chunk)

    def with_lengths(self, lengths) -> "HybridStateCache":
        return self._like(self.layers, lengths)

    def layer(self, i):
        """Layer ``i``'s buffers of all slots, as a decode step's view:
        a ``DenseKVLayer`` or a ``MambaLayerView``."""
        entry = self.layers[i]
        if isinstance(entry, KVRows):
            return DenseKVLayer(entry.k, entry.v)
        return MambaLayerView(entry.s, entry.window, self.chunk)

    def fresh(self, i):
        """What a slot about to be prefilled starts layer ``i`` from: a
        zero state and window; None for rows (a prompt attends itself)."""
        if isinstance(self.layers[i], KVRows):
            return None
        return MambaLayerView(None, None, self.chunk)

    def _with_entry(self, i, entry) -> "HybridStateCache":
        return self._like(self.layers[:i] + (entry,) + self.layers[i + 1:],
                          self.lengths)

    def with_layer(self, i, view) -> "HybridStateCache":
        return self._with_entry(i, _entry_of(view))

    def with_slot(self, i, slot, view) -> "HybridStateCache":
        """Layer ``i`` with slot ``slot`` taken from a one-slot view (a
        prefill's result): its rows ``[1, Hkv, s, D]`` written from
        position 0, its state and window REPLACED; each written where
        the buffer lies."""
        return self._with_entry(i, jax.tree_util.tree_map(
            lambda buf, one: _put_slot(buf, one, slot), self.layers[i],
            _entry_of(view)))

    # ---- what the engine asks of any cache ----------------------------
    def tick_reads(self, active, slot_len, window: int) -> dict:
        """Arguments of the ``tick`` span: ``kv_positions``, the cached
        positions a row layer's attention has to read (0 with no such
        layer), and ``state_bytes``, the active slots' states once each
        way."""
        rows = kv_tick_reads(active, slot_len, window) if self.has_rows \
            else {"kv_positions": 0}
        return {**rows, "state_bytes": 2 * int(np.sum(active)) *
                self.logical_slot_bytes}

    def step_bytes_per_slot(self, positions: int, tp: int = 1) -> int:
        """Bytes of cache a decode step streams for one slot: its rows
        to `positions` in every row layer, its states read and written."""
        rows = self._rows
        kv = kv_step_bytes(len(rows), rows[0].k.shape[1], rows[0].k.shape[3],
                           rows[0].k.dtype, False, positions, tp) \
            if rows else 0
        return kv + 2 * self.slot_bytes

    def __repr__(self):
        return (f"HybridStateCache(layers={self.num_layers}, "
                f"row_layers={len(self._rows)}, slots={self.batch_slots}, "
                f"state_slot_bytes={self.slot_bytes})")


jax.tree_util.register_pytree_node(
    HybridStateCache,
    lambda c: ((c.layers, c.lengths), (c.logical_slot_bytes, c.chunk)),
    lambda aux, ch: HybridStateCache(*ch, *aux))
