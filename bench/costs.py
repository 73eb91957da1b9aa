"""Operations and bytes that one wavefront DP row needs, and the peaks.

A row aligns a query of ``lx`` elements with a window of ``ly``, each
element ``d`` numbers wide, over ``lx * ly`` DP cells.  Per cell:

* Levenshtein: one comparison for the substitution cost, then three adds
  and two minimums: 6 operations.
* ERP: the L2 cost of the pair (``d`` subtractions, ``d`` multiplies,
  ``d - 1`` adds, one square root: ``3d``), three adds and two minimums;
  plus the gap cost of every element once (``d`` multiplies, ``d - 1``
  adds, one square root: ``2d``).

Bytes are what the answer cannot do without: both inputs read once as
4-byte numbers, a 4-byte distance and a 4-byte verdict written.  Padding
rows and the kernel's own layout copies are not counted, so the share of
the roofline is the useful share.
"""

from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).with_name("peaks.json")


def row_ops(distance: str, lx: int, ly: int, d: int) -> int:
    if distance == "levenshtein":
        return 6 * lx * ly
    if distance == "erp":
        return (3 * d + 5) * lx * ly + 2 * d * (lx + ly)
    raise KeyError(f"no operation count for {distance!r}")


def row_bytes(distance: str, lx: int, ly: int, d: int) -> int:
    return 4 * d * (lx + ly) + 8


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a chip that is not in the table is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device {device_kind!r}")
    return table[device_kind]


def roofline(distance: str, rows: int, lx: int, ly: int, d: int,
             kernel_s: float, device_kind: str):
    """``(share in %, binding bound)``: the least time the chip could take
    for ``rows`` real rows, over the kernel's measured device time."""
    p = peaks(device_kind)
    t_ops = rows * row_ops(distance, lx, ly, d) / p["flops_per_s"]
    t_bytes = rows * row_bytes(distance, lx, ly, d) / p["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / kernel_s, bound
