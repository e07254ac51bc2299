"""Poisson-like arrivals at the mix's ``rate_per_s``.

The ``round(rate * seconds)`` gaps are the quantiles of the exponential
distribution at (i + 1/2) / n, scaled to sum to ``seconds``, in an order
drawn from the mix's ``arrival_seed``.  Every run of the mix offers the
same arrivals: under a queue the 95th percentile latency depends on how
the short gaps cluster, and an order drawn per run seed moved it by about
28% between seeds against under 6% between two runs of one seed.
"""
from __future__ import annotations

import numpy as np


def due_times(mix: dict, seconds: float) -> np.ndarray:
    n = max(int(round(float(mix["rate_per_s"]) * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(mix["arrival_seed"]), 2]))
    gaps = gaps[rng.permutation(n)]
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])
