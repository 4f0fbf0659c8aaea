"""One general traffic generator, driven by a mix's data file.

A mix file gives the length distributions of prompts and outputs, the loop
(``open`` with ``rate_rps``, or ``closed`` with ``clients``), the ramp, the
``pool`` and the ``order_seed``.  Every run serves the same work: the
pool's lengths are the quantiles ``(i + 0.5) / pool`` of their
distribution and its open-loop gaps the quantiles of an exponential of mean
``1 / rate_rps``, put in an order drawn once from ``order_seed``; request
``i`` takes entry ``i % pool``.  The run's seed draws the prompt token ids
(and, elsewhere, the weights).  So runs with different seeds replay the
same arrivals and sizes, as a recorded trace would, and differ in content,
not in how much work there is or when it comes.

Distributions (``dist``): ``lognormal`` with ``median`` and ``sigma``,
truncated to ``[min, max]`` (the requests of that distribution that fit the
range: a deployment with a smaller slot sends the others elsewhere);
``uniform`` over ``[min, max]`` inclusive.
"""
from __future__ import annotations

from statistics import NormalDist
from typing import Dict, Tuple

import numpy as np


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: Dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of ``dist`` (sorted)."""
    q = _quantiles(n)
    lo, hi = int(dist["min"]), int(dist["max"])
    if dist["dist"] == "lognormal":
        norm, mu = NormalDist(), np.log(float(dist["median"]))
        sigma = float(dist["sigma"])
        a, b = (norm.cdf((np.log(x) - mu) / sigma) for x in (lo, hi))
        z = np.array([norm.inv_cdf(a + x * (b - a)) for x in q])
        v = np.exp(mu + sigma * z)
    elif dist["dist"] == "uniform":
        v = lo + q * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def _seed_words(seed: int):
    """Any whole number, however large, as a numpy seed sequence entropy."""
    return [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF,
            int(int(seed) < 0)]


class Traffic:
    """The request sequence of one run: sizes, arrivals and prompt tokens."""

    def __init__(self, mix: Dict, seed: int, vocab: int):
        self.mix = mix
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.pool = pool = int(mix["pool"])
        rng = np.random.default_rng(_seed_words(mix["order_seed"]) + [1])
        self.prompt_lens = rng.permutation(lengths(mix["prompt"], pool))
        self.output_lens = rng.permutation(lengths(mix["output"], pool))
        self.loop = mix["loop"]
        if self.loop == "open":
            rate = float(mix["rate_rps"])
            gaps = -np.log1p(-_quantiles(pool)) / rate
            self.arrivals = np.cumsum(rng.permutation(gaps))
        elif self.loop == "closed":
            self.clients = int(mix["clients"])
            self.arrivals = None
        else:
            raise ValueError(f"unknown loop {self.loop!r}")

    def size(self, i: int) -> Tuple[int, int]:
        """(prompt tokens, output tokens) of request ``i``."""
        j = i % self.pool
        return int(self.prompt_lens[j]), int(self.output_lens[j])

    def arrival(self, i: int) -> float:
        """Due time of request ``i`` in seconds from the start (open loop)."""
        n = i // self.pool
        return float(n * self.arrivals[-1] + self.arrivals[i % self.pool])

    def prompt(self, i: int) -> np.ndarray:
        n, _ = self.size(i)
        rng = np.random.default_rng(_seed_words(self.seed) + [2, i])
        return rng.integers(0, self.vocab, n, dtype=np.int64).astype(np.int32)


def check_fits(mix: Dict, max_len: int) -> None:
    """Every request of the mix must fit one serving slot."""
    worst = int(mix["prompt"]["max"]) + int(mix["output"]["max"])
    if worst > max_len:
        raise ValueError(f"longest request ({worst} tokens) exceeds the "
                         f"configuration's max_len {max_len}")

