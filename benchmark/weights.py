"""Weights from ``--seed``: every leaf of a configuration's parameter
spec, made on the device in ONE jitted call, in the dtype it is run in.

The spec (name -> shape) comes from the configuration's plain reference,
the rules (which leaf is drawn how) from the configuration's file; the
program's model only receives the arrays, by name.
"""
from __future__ import annotations

import re

import numpy as np


def seed_key(seed: int, stream: int = 0):
    """A threefry key from any non-negative whole number (the driver's
    seeds pass 2**31, which a 32-bit PRNGKey(seed) refuses)."""
    import jax
    import jax.numpy as jnp
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(
        2, dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def host_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed), int(stream)]))


def _rule_for(name: str, rules):
    for rule in rules:
        if re.search(rule["match"], name):
            return rule
    raise KeyError(f"no init rule matches parameter {name!r}")


def make_weights(seed: int, spec: dict, rules: list, dtype: str) -> dict:
    """{name: array} for every leaf of `spec`, from `seed`."""
    import jax
    import jax.numpy as jnp
    names = sorted(spec)
    plan = [(n, tuple(spec[n]), _rule_for(n, rules)) for n in names]
    dt = jnp.dtype(dtype)

    def gen(key):
        out = {}
        for i, (name, shape, rule) in enumerate(plan):
            kind = rule["kind"]
            if kind == "normal":
                w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32) * rule["std"]
            elif kind == "ones":
                w = jnp.ones(shape, jnp.float32)
            elif kind == "zeros":
                w = jnp.zeros(shape, jnp.float32)
            else:
                raise ValueError(f"unknown init kind {kind!r} for {name}")
            out[name] = w.astype(dt)
        return out

    return jax.jit(gen)(seed_key(seed, stream=1))
