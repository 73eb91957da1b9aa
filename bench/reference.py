"""The plain reference: a brute-force scan of every window, in numpy.

It imports nothing of the program.  Each distance's DP is in
``bench/distances/<name>.py`` (``reference``, and ``control`` where the
distance has a lower precision that a kernel could be tempted to use);
``levenshtein``, ``erp`` and ``erp_bf16`` name the two that the existing
configurations use.

``distances`` threads over queries; numpy lets go of the interpreter lock
inside each array operation.  ``verdict_gaps`` holds a served hit set
against the reference distances.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Iterable, List, Sequence

import numpy as np

from bench import byname


def dp(name: str, control: bool = False):
    """The reference DP of distance ``name``, or its control."""
    mod = byname.module("distances", name)
    if control and not hasattr(mod, "control"):
        raise KeyError(f"distance {name!r} has no control")
    return mod.control if control else mod.reference


levenshtein = dp("levenshtein")
erp = dp("erp")
erp_bf16 = dp("erp", control=True)


def distances(name: str, queries: Sequence[np.ndarray], data: np.ndarray,
              *, control: bool = False, threads: int = 8) -> List[np.ndarray]:
    """Distance of each query to every window, one array per query."""
    fn = dp(name, control)
    with ThreadPoolExecutor(max(1, threads)) as pool:
        return list(pool.map(lambda q: fn(q, data), queries))


def verdict_gaps(hits: Iterable[int], dist: np.ndarray, eps: float
                 ) -> np.ndarray:
    """``|d - eps|`` of every window whose served verdict disagrees with
    the reference: a hit with ``d > eps``, or ``d <= eps`` left out.  A
    hit that names no window reads the largest float."""
    ids = np.fromiter(hits, np.int64)
    bad = (ids < 0) | (ids >= len(dist))
    served = np.zeros(len(dist), bool)
    served[ids[~bad]] = True
    wrong = served != (dist <= eps)
    return np.concatenate([np.abs(dist[wrong] - eps),
                           np.full(int(bad.sum()), np.finfo(float).max)])
