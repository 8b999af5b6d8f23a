"""The one general traffic generator: a schedule of requests from a
traffic mix (a data file of parameters), a rate and a seed.

No JAX here: the load generator's child process imports this module's
sibling and must never touch the chip.

A mix (``chipbench/traffic/<name>.json``) gives

    arrivals       {"process": "poisson"}: exponential gaps, the count
                   varies with the seed;
                   {"process": "stratified", "stratum_s"}: the same
                   Poisson process conditioned on its count in every
                   stratum of ``stratum_s`` seconds (a stratum as long
                   as the window fixes the window's work and nothing
                   else: every burst and lull inside it stays);
                   {"process": "burst", "period_s", "jitter_s"}
    prompt_len     a length distribution
    max_new_tokens a length distribution
    lengths_block  how many consecutive requests share one stratified
                   draw of lengths; 0 draws every length on its own
    max_total      cap on prompt + output positions

A length distribution is {"dist": "lognormal", "median", "sigma", "min",
"max"}, {"dist": "uniform", "min", "max"} or {"dist": "fixed", "value"}.

With ``stratified`` arrivals and a ``lengths_block`` the amount of work
is fixed by the mix and the rate, and only its order and timing are
drawn from the seed: every seed offers the same number of requests in
every stratum and the same multiset of lengths in every block of
requests, so two runs differ by which requests meet, not by how much was
asked.  ``poisson`` with ``lengths_block`` 0 fixes nothing: the offered
load itself varies from seed to seed.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as onp


def _quantile(dist: dict, u: float) -> int:
    kind = dist["dist"]
    if kind == "fixed":
        return int(dist["value"])
    if kind == "uniform":
        return int(round(dist["min"] + u * (dist["max"] - dist["min"])))
    if kind == "lognormal":
        z = NormalDist().inv_cdf(min(max(u, 1e-9), 1 - 1e-9))
        x = math.exp(math.log(dist["median"]) + dist["sigma"] * z)
        return int(min(max(round(x), dist["min"]), dist["max"]))
    raise KeyError(f"unknown length distribution {kind!r}")


def _lengths(dist: dict, n: int, block: int, rng) -> List[int]:
    """``n`` lengths; each block of ``block`` consecutive requests holds
    the distribution's ``block`` mid-quantiles in a seeded order, or
    with ``block`` 0 every length is a draw of its own."""
    if not block:
        return [_quantile(dist, u) for u in rng.random(n)]
    out: List[int] = []
    while len(out) < n:
        qs = [(i + 0.5) / block for i in rng.permutation(block)]
        out += [_quantile(dist, u) for u in qs]
    return out[:n]


def _arrivals(spec: dict, rate: float, horizon: float, rng) -> List[float]:
    process = spec["process"]
    if process == "poisson":
        # plain Poisson: exponential gaps; the count varies with the seed
        out, t = [], rng.exponential(1.0 / rate)
        while t < horizon:
            out.append(t)
            t += rng.exponential(1.0 / rate)
        return out
    if process == "stratified":
        # Poisson inside each stratum, conditioned on its count: every
        # stratum of ``stratum_s`` seconds gets its share of the rate,
        # placed uniformly
        width = float(spec["stratum_s"])
        out, owed, lo = [], 0.0, 0.0
        while lo < horizon:
            hi = min(lo + width, horizon)
            owed += rate * (hi - lo)
            k = int(owed + 1e-9)
            owed -= k
            out += sorted(lo + rng.random(k) * (hi - lo))
            lo = hi
        return [float(t) for t in out]
    if process == "burst":
        # ``burst`` requests together every ``period_s`` seconds (the
        # mean rate is burst / period_s; ``rate`` scales the burst)
        period = float(spec["period_s"])
        size = max(1, int(round(rate * period)))
        jitter = float(spec.get("jitter_s", 0.0))
        out, t = [], 0.0
        while t < horizon:
            out += sorted(t + rng.random(size) * jitter)
            t += period
        return [float(x) for x in out if x < horizon]
    raise KeyError(f"unknown arrival process {process!r}")


def make_schedule(traffic: dict, rate_rps: float, seed: int,
                  horizon_s: float, vocab: int) -> List[Dict]:
    """Requests ``{"i", "due_s", "prompt", "max_new_tokens"}`` due in
    ``[0, horizon_s)``, in order of ``due_s``."""
    rng = onp.random.default_rng([int(seed), 0x51ED])
    due = _arrivals(traffic["arrivals"], float(rate_rps), float(horizon_s),
                    rng)
    n = len(due)
    block = int(traffic.get("lengths_block", 32))
    plens = _lengths(traffic["prompt_len"], n, block, rng)
    olens = _lengths(traffic["max_new_tokens"], n, block, rng)
    cap = int(traffic.get("max_total", 1 << 30))
    out = []
    for i, (t, pl, ol) in enumerate(zip(due, plens, olens)):
        pl = max(1, min(pl, cap - 1))
        ol = max(1, min(ol, cap - pl))
        out.append({"i": i, "due_s": t,
                    "prompt": rng.integers(0, vocab, pl).tolist(),
                    "max_new_tokens": ol})
    return out
