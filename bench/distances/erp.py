"""ERP (Chen and Ng, VLDB 2004) with gap element ``g = 0`` and the L2 norm
between points.

``reference`` is the DP in float64, one query against all windows at a
time.  Inside a row ``D[i, j] = Gy[j] + min_k<=j (T[k] - Gy[k])`` with
``Gy`` the running sum of the gap costs of ``y``.  ``control`` is the same
DP computed cell by cell in bfloat16 (data, gap costs and every sum
rounded to bfloat16): the lower precision that a kernel could be tempted
to use for float32.

Per DP cell: the L2 cost of the pair (``d`` subtractions, ``d``
multiplies, ``d - 1`` adds, one square root: ``3d``), three adds and two
minimums; plus the gap cost of every element once (``d`` multiplies,
``d - 1`` adds, one square root: ``2d``).  Bytes: both inputs read once
as 4-byte numbers, a 4-byte distance and a 4-byte verdict written.
"""

import numpy as np


def reference(q: np.ndarray, ys: np.ndarray) -> np.ndarray:
    """ERP of ``q`` (l, d) to every row of ``ys`` (n, l, d)."""
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


def control(q: np.ndarray, ys: np.ndarray) -> np.ndarray:
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


def row_ops(lx: int, ly: int, d: int) -> int:
    return (3 * d + 5) * lx * ly + 2 * d * (lx + ly)


def row_bytes(lx: int, ly: int, d: int) -> int:
    return 4 * d * (lx + ly) + 8
