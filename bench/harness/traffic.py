"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<name>.json`` and turns them, with a seed, into requests.

Every seed gets the same work.  Lengths and arrival gaps are drawn as
evenly spaced quantiles of their distributions, in blocks of ``block``
requests; the seed only shuffles each block and picks the tokens.  So any
run's window holds the same mix of sizes and the same offered load, in
another order, and runs with different seeds spread no more than runs of
one seed.

Parameters of a mix:

* ``rate_per_s``: the offered rate of an open loop; arrival gaps have the
  exponential distribution of a Poisson process.
* ``prompt`` and ``output``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal length distribution, clipped.
* ``block``: requests per stratified block.
* ``order_seed`` (optional): where given, the order of each block comes
  from this number and not from the run's seed, so every seed offers the
  same sizes at the same times and the run's seed picks only the tokens.
* ``warm``: ``{"burst", "min_s", "quiet_s", "max_s"}``: the warm-up opens
  with ``burst`` requests at once, runs at least ``min_s`` seconds and
  ends once ``quiet_s`` seconds pass with no new fused-step executable
  (at most ``max_s``); see ``driver.Driver.warm_up``.  With
  ``longest_first``: n, the warm-up's first n requests carry the n
  longest prompts and the n longest outputs of the block, so a context
  as long as the window's longest grows through every width of block
  table before it opens.
* ``drain_s``: at most this long after the window closes, the run waits
  for the window's requests to get their first token.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator

import numpy as np

# seed streams: warm-up traffic, the window's traffic, what follows it
WARM, WINDOW, AFTER = 1, 2, 3


@dataclass
class Spec:
    """One request as the generator makes it."""
    prompt: np.ndarray          # int32 token ids
    max_new: int
    gap_s: float                # time from this arrival to the next


def lognormal_quantiles(p: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of a clipped lognormal, as ints."""
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    x = p["median"] * np.exp(p["sigma"] * np.asarray(z))
    return np.clip(np.rint(x), p["min"], p["max"]).astype(np.int64)


def exp_quantiles(rate: float, n: int) -> np.ndarray:
    return np.asarray([-math.log(1.0 - (i + 0.5) / n) / rate
                       for i in range(n)])


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def stream(mix: dict, vocab: int, seed: int, which: int) -> Iterator[Spec]:
    """An endless stream of requests for one phase of a run."""
    rng = _rng(seed, which)
    order_rng = (_rng(mix["order_seed"], which) if "order_seed" in mix
                 else rng)
    b = mix["block"]
    prompts = lognormal_quantiles(mix["prompt"], b)
    outs = lognormal_quantiles(mix["output"], b)
    gaps = exp_quantiles(mix["rate_per_s"], b)
    lead = mix["warm"].get("longest_first", 0) if which == WARM else 0
    while True:
        order = [order_rng.permutation(b) for _ in range(3)]
        if lead:
            # the quantiles ascend: the last ``lead`` are the longest
            top = list(range(b - 1, b - 1 - lead, -1))
            for k in (0, 1):
                order[k] = np.asarray(top + [i for i in order[k]
                                             if i not in top])
            lead = 0
        for i in range(b):
            yield Spec(prompt=rng.integers(0, vocab, prompts[order[0][i]],
                                           dtype=np.int32),
                       max_new=int(outs[order[1][i]]),
                       gap_s=float(gaps[order[2][i]]))
