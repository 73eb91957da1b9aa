"""Zipf popularity over the pool: the query of rank ``k`` (1-based) is
asked with probability proportional to ``k ** -s``, ``s`` from
``traffic["zipf"]["s"]``.  The seed permutes which pool query holds each
rank and draws the requests."""

import numpy as np

from bench import gen

#: requests drawn at a time
_BLOCK = 4096


def stream(traffic: dict, seed: int):
    pool = int(traffic["pool"])
    s = float(traffic["zipf"]["s"])
    weight = np.arange(1, pool + 1, dtype=np.float64) ** -s
    by_rank = gen.rng(seed, 4).permutation(pool)
    r = gen.rng(seed, 3)
    while True:
        ranks = r.choice(pool, _BLOCK, p=weight / weight.sum())
        yield from by_rank[ranks].tolist()
