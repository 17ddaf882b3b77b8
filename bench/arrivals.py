"""The one traffic generator: open-loop arrivals from a mix file and a seed.

A mix (``bench/traffic/<mix>.json``) gives the arrival process, the
distribution of prompt lengths (a fixed set of lengths, so that set-up can
compile every shape) and of output budgets. Requests come in blocks of
``block``: each block holds the same ``block`` stratified quantiles of each
distribution (prompt lengths by largest remainder over their weights,
budgets and inter-arrival gaps at the quantiles ``(i + 0.5) / block``),
shuffled by a counter-based Philox stream keyed on the mix's
``order_seed`` and the block index (the discipline of
``repro.data.synthetic.philox_rng``). The run's seed draws the prompts'
token ids (and, elsewhere, the weights). So every seed offers the same
schedule of sizes and arrivals, with other tokens: the order of long and
short requests moves a p95 of time to first token by more than any bound
could hold, and the content is what the seed has to vary.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    uid: int
    at_s: float                    # due time from the start of the traffic
    prompt: tuple[int, ...]
    max_new: int


def philox(seed: int, *counters: int) -> np.random.Generator:
    counter = np.zeros(4, np.uint64)
    counter[:len(counters)] = counters
    return np.random.Generator(np.random.Philox(key=int(seed), counter=counter))


def _lognormal_quantile(u: float, median: float, sigma: float) -> float:
    return median * math.exp(sigma * statistics.NormalDist().inv_cdf(u))


def _lognormal_cdf(x: float, median: float, sigma: float) -> float:
    if x <= 0:
        return 0.0
    if math.isinf(x):
        return 1.0
    return statistics.NormalDist().cdf(math.log(x / median) / sigma)


def length_weights(spec: dict) -> list[float]:
    """Weight of each prompt length: given, or the lognormal's mass in the
    length's bin (edges at the geometric midpoints between lengths)."""
    values = spec["values"]
    if "weights" in spec:
        w = [float(x) for x in spec["weights"]]
    else:
        ln = spec["lognormal"]
        edges = ([0.0] + [math.sqrt(a * b) for a, b in zip(values, values[1:])]
                 + [math.inf])
        w = [_lognormal_cdf(hi, ln["median"], ln["sigma"])
             - _lognormal_cdf(lo, ln["median"], ln["sigma"])
             for lo, hi in zip(edges, edges[1:])]
    total = sum(w)
    return [x / total for x in w]


def block_prompt_lens(spec: dict, block: int) -> list[int]:
    """The prompt lengths of one block: ``block`` of them in proportion to
    their weights, by largest remainder."""
    values, w = spec["values"], length_weights(spec)
    exact = [x * block for x in w]
    counts = [int(math.floor(x)) for x in exact]
    order = sorted(range(len(values)), key=lambda i: exact[i] - counts[i],
                   reverse=True)
    for i in order[:block - sum(counts)]:
        counts[i] += 1
    return [v for v, c in zip(values, counts) for _ in range(c)]


def block_budgets(spec: dict, block: int) -> list[int]:
    """The output budgets of one block: quantiles of a lognormal cut to
    ``[lo, hi]``."""
    ln, lo, hi = spec["lognormal"], spec["lo"], spec["hi"]
    f_lo = _lognormal_cdf(lo, ln["median"], ln["sigma"])
    f_hi = _lognormal_cdf(hi, ln["median"], ln["sigma"])
    out = []
    for i in range(block):
        u = f_lo + (i + 0.5) / block * (f_hi - f_lo)
        out.append(int(min(max(round(_lognormal_quantile(
            u, ln["median"], ln["sigma"])), lo), hi)))
    return out


def block_gaps(mix: dict, rate_rps: float, block: int) -> list[float]:
    """Inter-arrival gaps of one block, in seconds, with mean ``1/rate``:
    exponential quantiles for ``poisson``, Gamma quantiles of coefficient of
    variation ``cv`` for ``gamma`` (read off a fixed large sample)."""
    us = [(i + 0.5) / block for i in range(block)]
    if mix["process"] == "poisson":
        q = [-math.log1p(-u) for u in us]
    elif mix["process"] == "gamma":
        k = 1.0 / mix["cv"] ** 2
        sample = np.sort(np.random.Generator(np.random.Philox(key=0))
                         .gamma(k, 1.0 / k, size=1 << 18))
        q = [float(sample[int(u * len(sample))]) for u in us]
    else:
        raise ValueError(f"unknown arrival process {mix['process']!r}")
    mean = sum(q) / len(q)
    return [x / mean / rate_rps for x in q]


def generate(mix: dict, seed: int, rate_rps: float, duration_s: float,
             vocab: int) -> list[Arrival]:
    """Arrivals due in ``[0, duration_s]`` at ``rate_rps``; same arguments,
    same list; another seed, the same schedule with other token ids."""
    block = int(mix["block"])
    lens = block_prompt_lens(mix["prompt_len"], block)
    budgets = block_budgets(mix["max_new"], block)
    gaps = block_gaps(mix, rate_rps, block)
    out: list[Arrival] = []
    t, b = 0.0, 0
    while t <= duration_s:
        order = philox(mix["order_seed"], b)
        order_l, order_n, order_g = (order.permutation(block) for _ in range(3))
        ids = philox(seed, b)
        for j in range(block):
            t += gaps[order_g[j]]
            if t > duration_s:
                break
            n = lens[order_l[j]]
            prompt = ids.integers(1, vocab, size=n)
            out.append(Arrival(uid=len(out), at_s=t,
                               prompt=tuple(int(x) for x in prompt),
                               max_new=budgets[order_n[j]]))
        b += 1
    return out
