"""Open-loop chat traffic: Poisson arrivals, lognormal prompt and output
lengths (the shape of the Azure 2023 conversation trace as the Splitwise
paper, arXiv:2311.18677, summarises it).

Arithmetic follows ``repro.workloads.traces`` (exponential gaps, clipped
length draws), re-parameterised to wall-clock seconds and rates of a few
requests a second.  Every seed gets the same multiset of prompt lengths,
output lengths and inter-arrival gaps: each is a set of stratified
quantiles of its distribution.  Their order is one draw from the mix's
``schedule_seed``, stratified: each run of ``order_block`` consecutive
arrivals takes one value from each ``order_block``-quantile stratum of
every multiset, so every stretch of the window holds the same mix of short
and long prompts, outputs and gaps.  ``--seed`` draws the prompt tokens,
and reorders each multiset only between values of neighbouring rank (the
1st and 2nd shortest swap places or not, the 3rd and 4th, and so on).  So
every seed offers the same work in another order, spread over the window
alike, and a run's spread is the system's, not the draw's.  (A free
permutation decides which long outputs fall past the window's end and which
prefills meet many live slots, and so moves the window's numbers from seed
to seed far more than the system does from run to run.)

Mix parameters (``traffic/<name>.json``):
    rate_per_s                      arrivals per second
    prompt_median, prompt_sigma     lognormal prompt length (tokens)
    prompt_min, prompt_max          clip
    prompt_round                    prompt lengths rounded up to a multiple
    output_median, output_sigma     lognormal output length (tokens)
    output_min, output_max          clip
    order_block                     arrivals per stratified block
    schedule_seed                   the draw of the stratified order
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclass(frozen=True)
class TimedRequest:
    uid: int
    due_s: float          # seconds after the window opens
    prompt: np.ndarray    # (prompt_len,) int32
    max_new_tokens: int


def _lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                         hi: int, round_to: int = 1) -> np.ndarray:
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(median) + sigma * z)
    x = np.clip(np.ceil(x), lo, hi)
    x = np.ceil(x / round_to) * round_to
    return np.minimum(x, hi).astype(np.int64)


def lengths(mix: Dict, n: int):
    """The (prompt, output) length multisets for ``n`` requests, sorted."""
    prompts = _lognormal_quantiles(
        n, mix["prompt_median"], mix["prompt_sigma"], mix["prompt_min"],
        mix["prompt_max"], mix.get("prompt_round", 1))
    outputs = _lognormal_quantiles(
        n, mix["output_median"], mix["output_sigma"], mix["output_min"],
        mix["output_max"])
    return prompts, outputs


def stratified_order(values: np.ndarray, block: int,
                     rng: np.random.Generator) -> np.ndarray:
    """``values`` in an order drawn from ``rng`` in which each run of
    ``block`` consecutive entries holds one value of each of the ``block``
    quantile strata of the sorted values (the last run may hold fewer)."""
    strata = [rng.permutation(s)
              for s in np.array_split(np.sort(values), block)]
    out = []
    for j in range(len(strata[0])):
        out.extend(rng.permutation([s[j] for s in strata if j < len(s)]))
    return np.asarray(out, dtype=values.dtype)


def swap_neighbours(values: np.ndarray, rng: np.random.Generator
                    ) -> np.ndarray:
    """``values`` with the entries of ranks 2k and 2k+1 (in sorted order)
    swapped, each pair with probability one half."""
    out = values.copy()
    rank = np.argsort(values, kind="stable")
    pairs = rank[:len(rank) // 2 * 2].reshape(-1, 2)
    swap = pairs[rng.random(len(pairs)) < 0.5]
    out[swap[:, 0]], out[swap[:, 1]] = values[swap[:, 1]], values[swap[:, 0]]
    return out


def count(mix: Dict, seconds: float) -> int:
    return max(int(round(mix["rate_per_s"] * seconds)), 1)


def generate(mix: Dict, seed: int, seconds: float, vocab: int
             ) -> List[TimedRequest]:
    """Requests due in ``[0, seconds)``, sorted by due time."""
    n = count(mix, seconds)
    prompts, outputs = lengths(mix, n)
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    base = np.random.default_rng([mix["schedule_seed"], 0x7a11])
    rng = np.random.default_rng([abs(int(seed)), 0x7a11])
    block = mix["order_block"]
    prompts, outputs, gaps = (
        swap_neighbours(stratified_order(v, block, base), rng)
        for v in (prompts, outputs, gaps))
    # the first request is due at once; the gaps then fill the window
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    due = due * (seconds / (due[-1] + gaps[-1]))
    out = []
    for i in range(n):
        toks = rng.integers(0, vocab, size=int(prompts[i]), dtype=np.int32)
        out.append(TimedRequest(uid=i, due_s=float(due[i]), prompt=toks,
                                max_new_tokens=int(outputs[i])))
    return out
