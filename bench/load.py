"""The one traffic generator: reads a mix's parameters, nothing else.

A traffic file (``bench/traffic/<cell>.json``) holds:

* ``loop``: ``"open"`` (arrivals on a schedule, whatever the server does)
  or ``"closed"`` (``outstanding`` requests, each replaced when answered);
* ``rate_rps`` (open loop): mean rate of the Poisson arrivals, and
  ``schedule_seed``: the one order of their gaps that every run uses;
* ``pool``: distinct queries, made from database windows by ``queries``
  (``subst`` residue substitution rate or Gaussian ``noise``), each asked
  once in every pass over the pool;
* ``eps``: the range of every query.

Every seed gets the same work, in another order.  The arrival times are
one Poisson schedule of the mix, the same in every run: the count fixed by
rate and length, the gaps the exponential distribution's quantiles in the
order ``schedule_seed`` draws.  The seed makes the data and the pool, and
picks which query rides each arrival; each pass over the pool asks for
every query once.  (Drawn per seed, the order of the gaps alone moved a
cell's median latency by more than a tenth on the chip: see ``PERF.md``.)
"""

from __future__ import annotations

from typing import Iterator

import numpy as np

from bench import gen


def arrivals(traffic: dict, seconds: float) -> np.ndarray:
    """Due times, in seconds from the window's start, of an open loop."""
    n = int(round(float(traffic["rate_rps"]) * seconds))
    if n == 0:
        return np.zeros(0)
    # n + 1 gaps: the exponential distribution's quantiles, in the
    # schedule's order
    gaps = -np.log1p(-(np.arange(n + 1) + 0.5) / (n + 1))
    gaps = gen.rng(int(traffic["schedule_seed"]), 2).permutation(gaps)
    return np.cumsum(gaps)[:n] * (seconds / gaps.sum())


def query_stream(traffic: dict, seed: int) -> Iterator[int]:
    """Endless pool indices, one per request, in passes of ``pool``."""
    pool = int(traffic["pool"])
    r = gen.rng(seed, 3)
    while True:
        yield from r.permutation(pool).tolist()
