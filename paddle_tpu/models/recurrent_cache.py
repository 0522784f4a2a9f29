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

This is the state of ONE kind of layer (power retention,
``ops.power_retention``); a stack that mixes kinds needs a manager over
several such specs, which is not here.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..ops.power_retention import (RetentionState, init_state,
                                   power_retention_chunked,
                                   power_retention_step)

__all__ = ["RecurrentStateCache", "RetentionLayerView"]


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
        leaves = jax.tree_util.tree_leaves(self.layers)
        return sum(x.size * x.dtype.itemsize for x in leaves) // \
            self.batch_slots

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
        def put(buf, new):
            return jax.lax.dynamic_update_slice(
                buf, new.astype(buf.dtype),
                (jnp.asarray(slot, jnp.int32),) +
                (jnp.asarray(0, jnp.int32),) * (buf.ndim - 1))
        return self.with_layer(i, replace(view, state=jax.tree_util.tree_map(
            put, self.layers[i], view.state)))

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
