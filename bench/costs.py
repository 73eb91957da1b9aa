"""Operations and bytes of the wavefront's DP rows, and the peaks.

A row aligns a query of ``lx`` elements with a window of ``ly``, each
element ``d`` numbers wide, over ``lx * ly`` DP cells.  What one row
needs is counted in ``bench/distances/<name>.py`` (``row_ops``,
``row_bytes``): operations per cell as the distance's DP does them, and
bytes the answer cannot do without, both inputs read once as 4-byte
numbers and a 4-byte distance and verdict written.  Padding rows and the
kernel's own layout copies are not counted, so the share of the roofline
is the useful share.
"""

from __future__ import annotations

import json
import pathlib

from bench import byname

PEAKS = pathlib.Path(__file__).with_name("peaks.json")


def row_ops(distance: str, lx: int, ly: int, d: int) -> int:
    return byname.module("distances", distance).row_ops(lx, ly, d)


def row_bytes(distance: str, lx: int, ly: int, d: int) -> int:
    return byname.module("distances", distance).row_bytes(lx, ly, d)


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a chip that is not in the table is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device {device_kind!r}")
    return table[device_kind]


def share(ops: int, nbytes: int, kernel_s: float, device_kind: str):
    """``(share in %, binding bound)``: the least time the chip could take
    for ``ops`` operations over ``nbytes`` bytes, over the kernel's
    measured device time."""
    p = peaks(device_kind)
    t_ops = ops / p["flops_per_s"]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / kernel_s, bound


def roofline(distance: str, rows: int, lx: int, ly: int, d: int,
             kernel_s: float, device_kind: str):
    """:func:`share` of ``rows`` real rows of one shape."""
    return share(rows * row_ops(distance, lx, ly, d),
                 rows * row_bytes(distance, lx, ly, d), kernel_s,
                 device_kind)
