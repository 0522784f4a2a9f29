"""The one general traffic generator.  A mix is a data file under
``benchmark/traffic/``; this turns it and ``--seed`` into inputs.

Every seed gets the SAME multiset of sizes and arrival gaps, in another
order: lengths and gaps are the stratified quantiles of the mix's
distributions, laid out in blocks of ``block`` requests, and the seed only
permutes them inside each block and draws the token ids.  So two seeds
offer the same work, and a difference between runs is the system's.

Kinds:

- ``train_steps``: ``batches`` distinct batches of ``batch`` rows of
  ``seq_len`` random ids (labels are the ids shifted left by one, wrapping
  inside the row), cycled.
- ``closed_loop``: ``clients`` callers, each sending its next request when
  its last one has completed; started ``lead_in_s`` before the window, one
  after another, so that the window opens on full slots.
- ``open_loop``: arrivals on a schedule at ``rate_rps`` whatever the
  system does; gaps are ``arrival``-distributed (``exponential``, or
  ``gamma`` with a coefficient of variation ``cv``); the schedule starts
  ``lead_in_s`` before the window.
"""
from __future__ import annotations

import json
import math
import os
from statistics import NormalDist

import numpy as np

from .weights import host_rng

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str, rehearse: bool = False) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if rehearse:
        mix.update(mix.get("rehearse", {}))
    mix["name"] = name
    return mix


def _lognormal_quantiles(dist: dict, n: int) -> np.ndarray:
    """n stratified draws of a clipped lognormal: its (i+0.5)/n
    quantiles, as whole numbers."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def _gap_quantiles(mix: dict, n: int) -> np.ndarray:
    """n stratified inter-arrival gaps with mean 1/rate."""
    q = (np.arange(n) + 0.5) / n
    kind = mix.get("arrival", "exponential")
    if kind == "exponential":
        g = -np.log1p(-q)
    elif kind == "gamma":
        # no scipy here: a large seeded sample's quantiles stand in
        shape = 1.0 / float(mix["cv"]) ** 2
        sample = np.sort(np.random.default_rng(12345).gamma(
            shape, 1.0 / shape, size=200_000))
        g = sample[(q * sample.size).astype(int)]
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    return g / g.mean() / float(mix["rate_rps"])


def train_batches(mix: dict, vocab: int, seed: int):
    """[(ids, labels)] int32 arrays [batch, seq_len]; every row differs."""
    rng = host_rng(seed, 2)
    out = []
    for _ in range(int(mix["batches"])):
        ids = rng.integers(0, vocab, (int(mix["batch"]), int(mix["seq_len"])),
                           dtype=np.int32)
        out.append((ids, np.roll(ids, -1, axis=1)))
    return out


def requests(mix: dict, vocab: int, seed: int, horizon_s: float) -> list:
    """The requests of a serving mix, in sending order: dicts with
    ``prompt`` (int32 ids), ``max_new`` and, for an open loop, ``due``
    (seconds from the start of the lead-in).  Enough of them for
    `horizon_s` seconds: an open loop's schedule covers it, a closed
    loop gets ``plan_requests``."""
    rng = host_rng(seed, 3)
    block = int(mix.get("block", 48))
    prompts = _lognormal_quantiles(mix["prompt_tokens"], block)
    outputs = _lognormal_quantiles(mix["output_tokens"], block)
    open_loop = mix["kind"] == "open_loop"
    if open_loop:
        gaps = _gap_quantiles(mix, block)
        n_blocks = math.ceil(horizon_s * float(mix["rate_rps"]) / block) + 1
    else:
        n_blocks = math.ceil(int(mix["plan_requests"]) / block)
    out, clock = [], 0.0
    for _ in range(n_blocks):
        p = rng.permutation(prompts)
        o = rng.permutation(outputs)
        g = rng.permutation(gaps) if open_loop else None
        for i in range(block):
            req = {"prompt": rng.integers(1, vocab, int(p[i]),
                                          dtype=np.int32),
                   "max_new": int(o[i])}
            if open_loop:
                clock += float(g[i])
                req["due"] = clock
            out.append(req)
    return out
