"""Data and query generators, copied from the program's synthetic corpora.

The benchmark keeps its own copy so that the data a cell measures on cannot
move with the program.  Every array comes from ``rng(seed, stream)``, so one
seed gives the same database and queries in every run.

* ``proteins``: windows of ``l`` residues over a 20-letter alphabet, drawn
  from ``n_motifs`` motif families with ``mutation`` of the residues
  replaced (the program's ``repro.data.synthetic.proteins``).
* ``trajectories``: 2-D GPS-like windows, a smooth heading random walk at
  0.5-0.7 units per step from an origin uniform in [-10, 10]^2 (the
  program's ``repro.data.synthetic.trajectories``).
* ``perturb``: near-miss queries made from database windows, residues
  substituted with probability ``subst`` or Gaussian noise of ``noise``
  added (the program's ``repro.launch.serve.make_queries``).
"""

from __future__ import annotations

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed works,
    however large."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0), stream])


def proteins(n_windows: int, seed: int, *, l: int = 20, alphabet: int = 20,
             n_motifs: int = 64, mutation: float = 0.15) -> np.ndarray:
    r = rng(seed, 0)
    motifs = r.integers(0, alphabet, size=(n_motifs, l))
    data = motifs[r.integers(0, n_motifs, n_windows)]
    mut = r.random((n_windows, l)) < mutation
    return np.where(mut, r.integers(0, alphabet, size=(n_windows, l)),
                    data).astype(np.int32)


def trajectories(n_windows: int, seed: int, *, l: int = 20) -> np.ndarray:
    r = rng(seed, 0)
    heading = np.cumsum(r.normal(scale=0.3, size=(n_windows, l)), axis=1)
    speed = 0.5 + 0.2 * r.random((n_windows, 1))
    xy = np.stack([np.cumsum(np.cos(heading) * speed, 1),
                   np.cumsum(np.sin(heading) * speed, 1)], axis=-1)
    origin = r.uniform(-10, 10, size=(n_windows, 1, 2))
    return (xy + origin).astype(np.float32)


GENERATORS = {"proteins": proteins, "trajectories": trajectories}


def perturb(data: np.ndarray, n: int, seed: int, *, subst: float = 0.0,
            noise: float = 0.0, alphabet: int = 20) -> np.ndarray:
    """``n`` distinct database windows, each perturbed into a query."""
    r = rng(seed, 1)
    queries = data[r.choice(len(data), n, replace=False)].copy()
    if subst:
        flips = r.random(queries.shape) < subst
        queries[flips] = r.integers(0, alphabet, int(flips.sum()))
    if noise:
        queries += r.normal(scale=noise, size=queries.shape).astype(
            queries.dtype)
    return queries
