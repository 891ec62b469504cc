"""The one traffic generator: open-loop arrivals and request sizes from a
mix file and a seed.

The arrival processes are copied from the program's ``serve/queue.py``
(``poisson_arrivals``, ``burst_arrivals``), so that a change to the
program cannot change the load it is measured under.  Sizes and gaps are
*stratified*: for N requests the i-th size is the distribution's quantile
at (i + 1/2) / N.  They keep one fixed order, and the seed only swaps
values of neighbouring rank in pairs (``seeded_order``).  The warm-up and
the window each get a stratified set of their own, so every seed offers
the window the same requests' sizes and gaps at nearly the same moments,
and seeds differ about as little as two runs of one seed.

A mix file (``bench/traffic/<mix>.json``) holds, for a serving mix:

  {"kind": "open_loop",
   "arrival": {"process": "poisson", "rate_per_s": 2.0}
              | {"process": "burst", "rate_per_s": 2.0, "duty": 0.25,
                 "period_s": 4.0},
   "prompt_len": {"dist": "lognormal", "median": 1024, "sigma": 0.6,
                  "min": 128, "max": 3584},
   "output_len": {... same keys ...},
   "warmup_s": 20.0}

Request ids, prompt tokens and arrival times are all drawn from ``seed``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


@dataclass(frozen=True)
class Req:
    req_id: int
    arrival_s: float         # seconds from the start of the window
    prompt: np.ndarray       # int32 token ids
    max_new_tokens: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, seed & 0xFFFFFFFF, seed >> 32])


RANK_GROUP = 2           # values of neighbouring rank a seed may swap


def strata(n: int) -> np.ndarray:
    """Quantile levels (i + 1/2) / n, i = 0..n-1."""
    return (np.arange(n) + 0.5) / n


def seeded_order(vals: np.ndarray, seed: int, stream: int) -> np.ndarray:
    """``vals`` in an order drawn from the seed: one fixed permutation of
    the sorted values (drawn from ``stream`` alone, the same for every
    seed), in which the seed only permutes values of neighbouring rank
    among themselves, ``RANK_GROUP`` at a time.  Every seed then offers
    the same work at nearly the same moments, in another order; a long
    request never trades places with a short one."""
    n = len(vals)
    ranks = np.sort(vals)
    rng = _rng(seed, stream)
    for lo in range(0, n, RANK_GROUP):
        ranks[lo:lo + RANK_GROUP] = rng.permutation(ranks[lo:lo + RANK_GROUP])
    place = np.random.default_rng([stream, 0x5EED]).permutation(n)
    out = np.empty_like(ranks)
    out[place] = ranks
    return out


def lengths(spec: Dict[str, Any], n: int, seed: int, stream: int
            ) -> np.ndarray:
    """n lengths of the stratified distribution ``spec``, in seeded order
    (``seeded_order``)."""
    if spec["dist"] == "fixed":
        vals = np.full(n, int(spec["value"]))
    elif spec["dist"] == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(q) for q in strata(n)])
        vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
        vals = np.clip(np.rint(vals), spec["min"], spec["max"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return seeded_order(vals.astype(np.int64), seed, stream)


def exp_gaps(n: int, rate_per_s: float, seed: int, stream: int
             ) -> np.ndarray:
    """n stratified Exp(rate) inter-arrival gaps in seeded order."""
    if rate_per_s <= 0:
        raise ValueError("rate must be > 0")
    gaps = -np.log1p(-strata(n)) / rate_per_s
    return seeded_order(gaps, seed, stream)


def window_count(mix: Dict[str, Any], seconds: float) -> int:
    """Requests that arrive in a window of ``seconds``: the mean count,
    rounded down, so the stratified gaps (which sum to less than
    n / rate) all end inside it."""
    return max(1, int(mix["arrival"]["rate_per_s"] * seconds))


def arrivals(mix: Dict[str, Any], n: int, seed: int, stream: int
             ) -> np.ndarray:
    """n arrival times from 0 on, each one gap after the last (the
    first gap included, so back-to-back sets keep the process's rate)."""
    a = mix["arrival"]
    if a["process"] == "poisson":
        return np.cumsum(exp_gaps(n, a["rate_per_s"], seed, stream))
    if a["process"] == "burst":
        duty, period = a["duty"], a.get("period_s", 1.0)
        if not 0.0 < duty <= 1.0:
            raise ValueError(f"duty must be in (0,1], got {duty}")
        t_on = np.cumsum(exp_gaps(n, a["rate_per_s"] / duty, seed, stream))
        on_len = duty * period
        k = np.floor(t_on / on_len)
        return k * period + (t_on - k * on_len)
    raise ValueError(f"unknown arrival process {a['process']!r}")


def _set(mix, n, t0, seed, stream, first_id, vocab, tok) -> List[Req]:
    t = t0 + arrivals(mix, n, seed, stream)
    plen = lengths(mix["prompt_len"], n, seed, stream + 1)
    olen = lengths(mix["output_len"], n, seed, stream + 2)
    return [Req(req_id=first_id + i, arrival_s=float(t[i]),
                prompt=tok.integers(0, vocab, size=int(plen[i]),
                                    dtype=np.int32),
                max_new_tokens=int(olen[i]))
            for i in range(n)]


def requests(mix: Dict[str, Any], seconds: float, seed: int, vocab: int
             ) -> List[Req]:
    """The whole open-loop schedule of a run, in two stratified sets: the
    warm-up's requests arrive from ``-warmup_s`` on, so the engine is in
    steady state when the window opens at 0, and the window's own
    ``window_count`` requests arrive inside it.  Every seed offers the
    window the same sizes and gaps, in another order."""
    if mix["kind"] != "open_loop":
        raise ValueError(f"not a serving mix: {mix['kind']!r}")
    warm_s = float(mix.get("warmup_s", 0.0))
    n_warm = int(mix["arrival"]["rate_per_s"] * warm_s)
    tok = _rng(seed, 4)
    warm = _set(mix, n_warm, -warm_s, seed, 10, 0, vocab, tok)
    win = _set(mix, window_count(mix, seconds), 0.0, seed, 20, n_warm,
               vocab, tok)
    return warm + win
