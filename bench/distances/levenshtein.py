"""Levenshtein: unit substitution, insertion and deletion costs.

``reference`` is the textbook DP in int16, one query against all windows
at a time, rows of the table vectorised over the windows.  The
left-to-right dependency inside a row is a running minimum:
``D[i, j] = min_k<=j (T[k] + j - k)``.  Distances are whole numbers, exact
in every precision a kernel could use, so there is no ``control``.

Per DP cell: one comparison for the substitution cost, then three adds
and two minimums, 6 operations.  Bytes: both inputs read once as 4-byte
numbers, a 4-byte distance and a 4-byte verdict written.
"""

import numpy as np


def reference(q: np.ndarray, ys: np.ndarray) -> np.ndarray:
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


def row_ops(lx: int, ly: int, d: int) -> int:
    return 6 * lx * ly


def row_bytes(lx: int, ly: int, d: int) -> int:
    return 4 * d * (lx + ly) + 8
