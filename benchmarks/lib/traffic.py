"""The one general traffic generator: a traffic file's parameters and a seed
in, requests out.

Every seed gets the SAME multiset of prompt lengths, output lengths and
arrival gaps, in another order: lengths and gaps are the stratified quantiles
of their distributions (quantile (i + 0.5) / n for i < n), permuted by the
seed.  Two seeds then offer the same work, so that runs differ by the system
and not by the draw.  Token ids are random from the seed.

A length distribution is ``{"dist": "lognormal", "median", "sigma", "min",
"max"}`` or ``{"dist": "uniform", "min", "max"}`` (both ends inclusive).
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np

from benchmarks.lib import seeds


def length_quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths: the distribution's stratified quantiles, clipped."""
    q = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        values = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        values = dist["min"] + q * (dist["max"] + 1 - dist["min"]) - 0.5
    else:
        raise ValueError("unknown length distribution %r" % dist["dist"])
    return np.clip(np.rint(values), dist["min"], dist["max"]).astype(int)


def gap_quantiles(rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps of a Poisson process at ``rate`` a second:
    the exponential's stratified quantiles (their mean is close to
    1 / rate, so the last arrival is due near n / rate)."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def _payload(rng, prompt_len, new_tokens, vocab_limit):
    return {"prompt": [int(t) for t in
                       rng.integers(0, vocab_limit, (int(prompt_len),))],
            "max_new_tokens": int(new_tokens), "temperature": 0.0}


def _lengths(traffic, n, rng):
    prompts = rng.permutation(length_quantiles(traffic["prompt_len"], n))
    outputs = rng.permutation(length_quantiles(traffic["output_len"], n))
    return prompts, np.minimum(outputs, traffic["max_total"] - prompts)


def open_plan(traffic: dict, seed: int, seconds: float, vocab_limit: int,
              rate: float = None):
    """``[(offset_seconds, payload)]``: round(rate x seconds) requests,
    Poisson arrivals from offset 0."""
    rate = float(rate if rate is not None else traffic["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    rng = seeds.rng(seed, "traffic")
    prompts, outputs = _lengths(traffic, n, rng)
    offsets = np.concatenate(
        [[0.0], np.cumsum(rng.permutation(gap_quantiles(rate, n)))[:-1]])
    return [(float(t), _payload(rng, p, o, vocab_limit))
            for t, p, o in zip(offsets, prompts, outputs)]


class ClosedPlan:
    """``payload(client, k)``: the k-th request of a client.  Round k of
    every client together holds one stratified sample of the lengths, dealt
    to the clients in the seed's order, so every round offers the same work;
    rounds are drawn when first asked for, so no client runs out."""

    def __init__(self, traffic: dict, seed: int, vocab_limit: int):
        self.traffic, self.seed, self.vocab_limit = traffic, seed, vocab_limit
        self.clients = int(traffic["clients"])
        self._rounds = {}

    def payload(self, client: int, k: int) -> dict:
        if k not in self._rounds:
            rng = seeds.rng(self.seed, "traffic-round-%d" % k)
            prompts, outputs = _lengths(self.traffic, self.clients, rng)
            self._rounds[k] = [_payload(rng, p, o, self.vocab_limit)
                               for p, o in zip(prompts, outputs)]
        return self._rounds[k][client]


def warm_plan(traffic: dict, seed: int, vocab_limit: int):
    """A few staggered requests that touch every program the window will
    use: the shortest and the longest prompt of the mix (one prefill chunk
    and many), decode steps with requests joining and leaving."""
    rng = seeds.rng(seed, "warm")
    lo = traffic["prompt_len"]["min"]
    hi = min(traffic["prompt_len"]["max"], traffic["max_total"] - 16)
    lens = [hi, lo, (lo + hi) // 2, lo, hi, lo]
    news = [12, 16, 8, 12, 6, 10]
    return [(0.25 * i, _payload(rng, p, o, vocab_limit))
            for i, (p, o) in enumerate(zip(lens, news))]
