"""One general traffic generator, driven by a mix's data file.

A mix is a fixed trace: a length distribution is sampled at evenly
spaced quantiles (a stratified set), exponential gaps likewise, and the
mix's own ``schedule_seed`` draws the order once.  It is NOT a Poisson
process: the marginals are a Poisson process's, the order is balanced
(below), and the clusters of short gaps and long prompts that a real
one throws up now and then are taken out.  A run's ``--seed``
draws the token ids and the weights, nothing else, so every seed offers
the same requests at the same instants and does the same amount of
work.  (PR 24 first let ``--seed`` draw the order too: the same set in
another order moved ``ttft_p90_ms`` by 17% between seeds, more than any
bound could hold; see PERF.md.)

The order is drawn in blocks (``BLOCK`` consecutive requests): the
sorted set is cut into BLOCK strata, and every block of the schedule
gets one value from each stratum, in a seeded order.  Marginals stay
what the mix says (lognormal lengths, exponential gaps); what is taken
out is the chance that one seed front-loads the long prompts or the
short gaps and the next does not, which moved the window's tail more
than any change to the system would.

A distribution is ``{"dist": "lognormal", "median": m, "sigma": s,
"min": a, "max": b}``, ``{"dist": "uniform", "min": a, "max": b}`` or
``{"dist": "fixed", "value": v}``.
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def stratified(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` whole-number lengths at the mid-points of n equal slices of
    the distribution's probability."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        x = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist:
        x = np.clip(x, dist["min"], dist["max"])
    return np.rint(x).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` gaps with the exponential marginal of a Poisson process of
    ``rate`` per second, at stratified quantiles (their sum is n / rate
    to within a percent)."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / float(rate)


BLOCK = 16


def blocked_order(values: np.ndarray, rng) -> np.ndarray:
    """The fixed set ``values`` in a seeded order in which every run of
    BLOCK consecutive entries holds one value of each of BLOCK strata."""
    v = np.sort(np.asarray(values))
    n = len(v)
    blocks = -(-n // BLOCK)
    out = np.empty((blocks, BLOCK), v.dtype)
    bounds = np.linspace(0, n, BLOCK + 1).astype(int)
    for j in range(BLOCK):
        stratum = v[bounds[j]:bounds[j + 1]]
        stratum = rng.permutation(stratum)
        # a stratum one short of the block count borrows its own median
        fill = np.resize(stratum, blocks) if len(stratum) else np.resize(v, blocks)
        out[:, j] = fill
    for b in range(blocks):
        out[b] = rng.permutation(out[b])
    return out.reshape(-1)[:n]


def requests(mix: Dict[str, Any], n: int, vocab: int, seed: int
             ) -> List[Dict[str, Any]]:
    """``n`` requests of the mix.  The lengths are the fixed set in the
    order the MIX's own ``schedule_seed`` draws; the run's ``seed`` draws
    the token ids (and, elsewhere, the weights)."""
    order = np.random.default_rng([int(mix["schedule_seed"]), 0x7A11])
    p_len = blocked_order(stratified(mix["prompt_tokens"], n), order)
    o_len = blocked_order(stratified(mix["output_tokens"], n), order)
    rng = np.random.default_rng([int(seed), 0x1D5])
    out = []
    for p, o in zip(p_len, o_len):
        ids = rng.integers(1, vocab, size=int(p), dtype=np.int64)
        out.append({"ids": ids.tolist(), "n_new": int(o)})
    return out


def arrivals(rate: float, seconds: float, schedule_seed: int) -> np.ndarray:
    """Due times in [0, seconds) at ``rate`` a second: the fixed set of
    exponential gaps in the block-balanced order the mix's
    ``schedule_seed`` draws."""
    n = max(1, int(math.ceil(rate * seconds)))
    rng = np.random.default_rng([int(schedule_seed), 0xA771])
    due = np.cumsum(blocked_order(exponential_gaps(rate, n), rng))
    return due[due < seconds]


def token_rows(rows: int, seq: int, vocab: int, seed: int) -> np.ndarray:
    """Training rows: uniform token ids, every row different."""
    rng = np.random.default_rng([int(seed), 0x70C5])
    return rng.integers(1, vocab, size=(rows, seq), dtype=np.int32)
