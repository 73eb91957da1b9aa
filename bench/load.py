"""The one traffic generator: reads a mix's parameters, nothing else.

A traffic file (``bench/traffic/<cell>.json``) holds:

* ``loop``: ``"open"`` (arrivals on a schedule, whatever the server does)
  or ``"closed"`` (``outstanding`` requests, each replaced when answered);
* ``arrivals`` (open loop): the name of the arrival pattern,
  ``bench/arrivals/<name>.py`` (``poisson`` where absent), with its mean
  ``rate_rps`` and ``schedule_seed``, the one schedule that every run
  uses;
* ``popularity``: the name of the pattern that picks the query of each
  request, ``bench/popularity/<name>.py`` (``uniform`` where absent: each
  pass over the pool asks every query once);
* a pattern's own parameters sit under a key of its name (``"mmpp":
  {...}``, ``"zipf": {...}``);
* ``pool``: distinct queries, made by the configuration's request kind
  from ``queries`` (for windows: a ``subst`` residue substitution rate or
  Gaussian ``noise``);
* ``eps``: the range of every query.

Every seed gets the same arrivals: they come from ``schedule_seed`` alone.
The seed makes the data and the pool, and the popularity picks which
query rides each arrival from it.  (Drawn per seed, the order of the gaps
alone moved a cell's median latency by more than a tenth on the chip:
see ``PERF.md``.)
"""

from __future__ import annotations

from typing import Iterator

import numpy as np

from bench import byname


def arrivals(traffic: dict, seconds: float) -> np.ndarray:
    """Due times, in seconds from the window's start, of an open loop."""
    name = traffic.get("arrivals", "poisson")
    return byname.module("arrivals", name).arrivals(traffic, seconds)


def query_stream(traffic: dict, seed: int) -> Iterator[int]:
    """Endless pool indices, one per request."""
    name = traffic.get("popularity", "uniform")
    return byname.module("popularity", name).stream(traffic, seed)
