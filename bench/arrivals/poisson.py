"""Poisson arrivals at ``rate_rps``, one fixed schedule per
``schedule_seed``: the count fixed by rate and length, the gaps the
exponential distribution's quantiles in the order ``schedule_seed``
draws."""

import numpy as np

from bench import gen


def arrivals(traffic: dict, seconds: float) -> np.ndarray:
    n = int(round(float(traffic["rate_rps"]) * seconds))
    if n == 0:
        return np.zeros(0)
    # n + 1 gaps: the exponential distribution's quantiles, in the
    # schedule's order
    gaps = -np.log1p(-(np.arange(n + 1) + 0.5) / (n + 1))
    gaps = gen.rng(int(traffic["schedule_seed"]), 2).permutation(gaps)
    return np.cumsum(gaps)[:n] * (seconds / gaps.sum())
