"""The plain reference: a brute-force scan of every window, in numpy.

It imports nothing of the program.  Each distance is the textbook dynamic
programme, one query against all windows at a time, rows of the DP table
vectorised over the windows:

* Levenshtein: unit substitution, insertion and deletion costs, in int16.
  The left-to-right dependency inside a row is a running minimum:
  ``D[i, j] = min_k<=j (T[k] + j - k)``.
* ERP (Chen and Ng, VLDB 2004) with gap element ``g = 0`` and the L2 norm
  between points, in float64.  Inside a row
  ``D[i, j] = Gy[j] + min_k<=j (T[k] - Gy[k])`` with ``Gy`` the running sum
  of the gap costs of ``y``.

``distances`` threads over queries; numpy lets go of the interpreter lock
inside each array operation.

``control`` is the same ERP computed cell by cell in bfloat16 (data, gap
costs and every sum rounded to bfloat16): the lower precision that a
kernel could be tempted to use.  ``verdict_gaps`` holds a served hit set
against the reference distances.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Iterable, List, Sequence

import numpy as np


def levenshtein(q: np.ndarray, ys: np.ndarray) -> np.ndarray:
    """Edit distance of ``q`` (l,) to every row of ``ys`` (n, l)."""
    n, ly = ys.shape
    ar = np.arange(ly + 1, dtype=np.int16)
    prev = np.broadcast_to(ar, (n, ly + 1))
    t = np.empty((n, ly + 1), np.int16)
    for i, qi in enumerate(q, start=1):
        cost = (ys != qi).astype(np.int16)
        t[:, 0] = i
        np.minimum(prev[:, 1:] + 1, prev[:, :-1] + cost, out=t[:, 1:])
        prev = np.minimum.accumulate(t - ar, axis=1) + ar
    return prev[:, ly].astype(np.float64)


def erp(q: np.ndarray, ys: np.ndarray) -> np.ndarray:
    """ERP (gap element 0) of ``q`` (l, d) to every row of ``ys`` (n, l, d)."""
    q = np.asarray(q, np.float64)
    ys = np.asarray(ys, np.float64)
    n, ly, _ = ys.shape
    gy = np.sqrt((ys * ys).sum(-1))
    big_gy = np.concatenate([np.zeros((n, 1)), np.cumsum(gy, 1)], 1)
    gx = np.sqrt((q * q).sum(-1))
    big_gx = np.cumsum(gx)
    prev = big_gy
    t = np.empty((n, ly + 1))
    for i in range(len(q)):
        diff = ys - q[i]
        cost = np.sqrt((diff * diff).sum(-1))
        t[:, 0] = big_gx[i]
        np.minimum(prev[:, :-1] + cost, prev[:, 1:] + gx[i], out=t[:, 1:])
        prev = big_gy + np.minimum.accumulate(t - big_gy, axis=1)
    return prev[:, ly]


def erp_bf16(q: np.ndarray, ys: np.ndarray) -> np.ndarray:
    """ERP computed cell by cell with every value rounded to bfloat16."""
    from ml_dtypes import bfloat16 as bf
    q = np.asarray(q, np.float32).astype(bf)
    ys = np.asarray(ys, np.float32).astype(bf)
    n, ly, _ = ys.shape
    gy = np.sqrt((ys * ys).sum(-1, dtype=bf))
    gx = np.sqrt((q * q).sum(-1, dtype=bf))
    prev = np.zeros((n, ly + 1), bf)
    for j in range(ly):
        prev[:, j + 1] = prev[:, j] + gy[:, j]
    for i in range(len(q)):
        cur = np.empty_like(prev)
        cur[:, 0] = prev[:, 0] + gx[i]
        diff = ys - q[i]
        cost = np.sqrt((diff * diff).sum(-1, dtype=bf))
        for j in range(ly):
            cur[:, j + 1] = np.minimum(
                np.minimum(prev[:, j] + cost[:, j], prev[:, j + 1] + gx[i]),
                cur[:, j] + gy[:, j])
        prev = cur
    return prev[:, ly].astype(np.float64)


DISTANCES = {"levenshtein": levenshtein, "erp": erp}
CONTROLS = {"erp": erp_bf16}


def distances(name: str, queries: Sequence[np.ndarray], data: np.ndarray,
              *, control: bool = False, threads: int = 8) -> List[np.ndarray]:
    """Distance of each query to every window, one array per query."""
    fn = (CONTROLS if control else DISTANCES)[name]
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
