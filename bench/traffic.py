"""The one traffic generator: a mix file's parameters and a seed give the
requests of a run.

Every seed gets the same schedule: the same sizes, gaps and order.  Lengths
and inter-arrival gaps are stratified quantiles of the mix's distributions,
put in one order drawn from a fixed key (``SCHEDULE``), never from the seed;
the seed draws the token ids (and, in ``run.py``, the weights).  A window
holds a dozen requests or a few completions, so the order alone would move
a tail or a rate by 10-40% from seed to seed; with one schedule, runs of
different seeds do the same work and spread no more than runs of one seed.

Open loop (``"loop": "open"``): ``rate * seconds`` requests, due at the
cumulative sum of the gaps (exponential quantiles at the cell's rate:
Poisson arrivals with the sampling noise taken out).  The window's requests
are one block, so each holds every quantile once.
Closed loop (``"loop": "closed"``): a backlog of ``pool`` requests in
blocks of ``block``; the harness keeps ``outstanding_per_slot * slots``
submitted and adds one at each completion.  The first ``slots`` requests
are already under way when the window opens, as in a batch job's steady
state: request ``j`` has produced a share ``(r_j + 0.5) / slots`` of its
answer (``r`` a permutation), which joins its prompt, and its ``max_new``
is what remains.  The harness prefills them in set-up (``Req.started``),
so completions and refills happen inside the window from its start.
Greedy decoding with no end-of-sequence token, so output lengths are exact.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List

import numpy as np

SCHEDULE = 20250101     # key of the one order every seed shares


@dataclasses.dataclass
class Req:
    due: float              # seconds after the window opens (open loop)
    prompt: List[int]
    max_new: int
    started: bool = False   # under way when the window opens


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a length distribution, whole numbers."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        v = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), dist["min"], dist["max"]).astype(int)


def _blocked(order, values: np.ndarray, n: int) -> np.ndarray:
    """``n`` values: whole blocks of ``values``, each block permuted."""
    reps = -(-n // len(values))
    return np.concatenate([order.permutation(values)
                           for _ in range(reps)])[:n]


def generate(mix: dict, params: dict, seed: int, seconds: float,
             vocab: int, slots: int) -> List[Req]:
    order = np.random.default_rng(np.random.SeedSequence(SCHEDULE))
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    if mix["loop"] == "open":
        rate = params["rate_per_s"]
        n = block = max(1, int(round(rate * seconds)))
        u = (np.arange(n) + 0.5) / n
        gaps = order.permutation(-np.log1p(-u) / rate)
        due = np.cumsum(gaps) - gaps[0]
    elif mix["loop"] == "closed":
        n, block = mix["pool"], mix["block"]
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    plen = _blocked(order, quantiles(mix["prompt"], block), n)
    olen = _blocked(order, quantiles(mix["output"], block), n)
    started = np.zeros(n, bool)
    if mix["loop"] == "closed":
        k = min(slots, n)
        made = ((order.permutation(k) + 0.5) / k * olen[:k]).astype(int)
        plen[:k] += made
        olen[:k] -= made
        started[:k] = True
    return [Req(float(t), rng.integers(0, vocab, int(p)).tolist(), int(o),
                bool(s)) for t, p, o, s in zip(due, plen, olen, started)]
