"""Packed ragged-bucket dispatch: one device call per frontier round.

The matching layer buckets query segments by length (§5: there are only
``2*lambda_0 + 1`` lengths), and before this module every engine round paid
one device dispatch *per length bucket*.  The packed dispatcher folds a
round's work across **all** buckets into one padded call:

* rows are segment-sorted by their ``(len_x, len_y)`` bucket (stable), so
  equal shapes sit contiguously and the bucket layout is deterministic;
* the bucket offsets of the sorted layout are recorded as static metadata
  (:class:`PackedMeta`) — diagnostics for the benchmarks and the hook for a
  future per-bucket grid split;
* operands are padded to the round's maximum lengths and handed to the
  kernel registry in ONE call; per-row actual lengths ride along, so the
  ragged wavefront kernel reads each row's answer off its own diagonal;
* results are scattered back to the caller's row order.

Padding rows added by the registry's power-of-two batch discipline never
reach the caller (sliced off device-side) and are never counted — eval
accounting stays with :class:`~repro.core.counter.CountedDistance`, which
counts requested rows only (the same positional-masking discipline PR 3
established for the device query path).

:data:`STATS` counts the packed calls issued and the rows they carried;
the ``dispatch.*`` spans (:mod:`repro.spans`) time the pack, the padding,
the launch, the copy back and the unpack of each call.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import numpy as np

from repro import spans
from repro.kernels import registry


@dataclasses.dataclass(frozen=True)
class PackedMeta:
    """Static layout of one packed dispatch (sorted by bucket)."""
    #: ``(len_x, len_y, count)`` per contiguous bucket, in sorted order
    buckets: Tuple[Tuple[int, int, int], ...]
    #: row offset of each bucket in the sorted layout
    offsets: Tuple[int, ...]

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)


@dataclasses.dataclass
class DispatchStats:
    """Cumulative packed-dispatch accounting (benchmarks read this)."""
    dispatches: int = 0     # packed device calls actually issued
    rows: int = 0           # requested rows (excl. any padding)
    #: LB-cascade accounting per tier (``endpoint`` / ``envelope``): rows a
    #: tier's bound was evaluated on, and rows it certified ``> eps``.
    #: Requested rows only — the registry's pow2 batch padding is sliced
    #: off before any bound value reaches these counters.
    lb_rows: Dict[str, int] = dataclasses.field(default_factory=dict)
    lb_pruned: Dict[str, int] = dataclasses.field(default_factory=dict)
    last_meta: Optional[PackedMeta] = None

    def reset(self) -> None:
        self.dispatches = 0
        self.rows = 0
        self.lb_rows = {}
        self.lb_pruned = {}
        self.last_meta = None

    def note_lb(self, tier: str, rows: int, pruned: int) -> None:
        self.lb_rows[tier] = self.lb_rows.get(tier, 0) + int(rows)
        self.lb_pruned[tier] = self.lb_pruned.get(tier, 0) + int(pruned)


STATS = DispatchStats()


def pad_ragged_rows(rows):
    """Stack ragged rows into a zero-padded ``(N, W[, d])`` array.

    Returns ``(padded, lengths)`` — the one ragged-batch layout every
    packed caller (engine, fleet serving) shares."""
    lens = np.array([len(r) for r in rows], np.int64)
    out = np.zeros((len(rows), int(lens.max())) + rows[0].shape[1:],
                   rows[0].dtype)
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
    return out, lens


def pack_meta(lx: np.ndarray, ly: np.ndarray
              ) -> Tuple[np.ndarray, PackedMeta]:
    """Stable bucket sort of rows by ``(len_x, len_y)``.

    Returns the sort order plus the static bucket metadata of the sorted
    layout."""
    if not len(lx):
        return np.zeros(0, np.int64), PackedMeta((), ())
    # one small-integer key, so the stable sort is a radix sort
    key = np.asarray(lx, np.int64) * (int(ly.max()) + 1) + ly
    order = np.argsort(key.astype(np.min_scalar_type(key.max())),
                       kind="stable")
    slx, sly = lx[order], ly[order]
    starts = np.r_[0, np.flatnonzero((slx[1:] != slx[:-1])
                                     | (sly[1:] != sly[:-1])) + 1]
    counts = np.diff(np.r_[starts, len(order)])
    return order, PackedMeta(
        tuple(zip(slx[starts].tolist(), sly[starts].tolist(),
                  counts.tolist())), tuple(starts.tolist()))


def packed_batch(name: str, xs, ys, lx=None, ly=None, *, eps=None,
                 block_b: Optional[int] = None,
                 interpret: Optional[bool] = None,
                 exec: Optional[str] = None, tile: Optional[int] = None,
                 trim: bool = True) -> registry.KernelOut:
    """ONE padded device call over every length bucket of a round.

    ``xs``/``ys`` are row-paired batches whose rows may come from different
    ``(len_x, len_y)`` buckets (``lx``/``ly`` carry the actual lengths);
    ``eps`` (scalar or per-row; +inf rows opt out) enables fused ε-pruning.
    ``exec``/``tile`` pick the wavefront execution mode and Pallas band
    depth (None: the registry's process-wide policy / VMEM heuristic);
    ``trim=False`` keeps the caller's padded widths as the shape class.
    Results come back in the caller's row order as numpy arrays.
    """
    spec = registry.get(name)
    xs = np.asarray(xs)
    ys = np.asarray(ys)
    B = len(xs)
    if B == 0:
        z = np.zeros((0,), np.float32)
        return registry.KernelOut(z, z.astype(bool), z.astype(bool))
    with spans.span(spans.DISPATCH_PACK, rows=B) as sp:
        lx = np.full(B, xs.shape[1], np.int64) if lx is None \
            else np.asarray(lx, np.int64)
        ly = np.full(B, ys.shape[1], np.int64) if ly is None \
            else np.asarray(ly, np.int64)
        eps_v = None if eps is None else \
            np.broadcast_to(np.asarray(eps, np.float32), (B,))
        order, meta = pack_meta(lx, ly)
        sp.set_metadata(buckets=meta.n_buckets)
        xs, ys, lx, ly = xs[order], ys[order], lx[order], ly[order]
        if eps_v is not None:
            eps_v = eps_v[order]
    out = spec.batch(xs, ys, lx, ly, eps=eps_v, block_b=block_b,
                     interpret=interpret, exec=exec, tile=tile, trim=trim)

    with spans.span(spans.DISPATCH_UNPACK):
        inv = np.empty_like(order)
        inv[order] = np.arange(B)
        result = registry.KernelOut(out.dist[inv], out.hit[inv],
                                    out.pruned[inv])
    STATS.dispatches += 1
    STATS.rows += B
    STATS.last_meta = meta
    return result


def packed_band(name: str, xs, ys, lx, ly, *, lam: int, lambda0: int,
                eps, block_b: Optional[int] = None,
                interpret: Optional[bool] = None,
                exec: Optional[str] = None,
                tile: Optional[int] = None) -> np.ndarray:
    """ONE band-mode call over start-pair rows (the verify stage of
    subsequence matching): row ``r`` aligns ``xs[r, :lx[r]]`` with
    ``ys[r, :ly[r]]`` from their first elements, and comes back as
    ``(B, Lx-lam+1, 2*lambda0+1)``: ``D[i, i + q - lambda0]`` at
    ``[r, i - lam, q]``, ``+inf`` where that cell does not exist or
    exceeds the row's ``eps``.  The operands' padded widths are the shape
    class (no trim), so a caller that pads to fixed widths compiles one
    class per power-of-two batch.  :data:`STATS` is left alone: it counts
    :func:`packed_batch`'s calls, and ``last_meta`` stays the latest
    merged filter round's."""
    spec = registry.get(name)
    xs = np.asarray(xs)
    B = len(xs)
    if B == 0:
        return np.zeros((0, xs.shape[1] - lam + 1, 2 * lambda0 + 1),
                        np.float32)
    lx = np.asarray(lx, np.int64)
    ly = np.asarray(ly, np.int64)
    with spans.span(spans.DISPATCH_PACK, rows=B) as sp:
        _, meta = pack_meta(lx, ly)
        sp.set_metadata(buckets=meta.n_buckets)
    return spec.batch(xs, ys, lx, ly, eps=eps, block_b=block_b,
                      interpret=interpret, exec=exec, tile=tile,
                      trim=False, band=(int(lam), int(lambda0)))


def packed_envelope(name: str, xs, ys, lx=None, ly=None, *, eps,
                    block_b: int = 8,
                    interpret: Optional[bool] = None) -> registry.KernelOut:
    """ONE elementwise envelope-bound call over a round's candidate rows.

    The ``lb:<name>`` KernelSpec is O(B*L) elementwise work (no wavefront),
    so rows need no bucket sort — per-row lengths mask the ragged tails
    directly.  Returns the bound in ``.dist`` (never BIG-masked), with
    ``.pruned`` marking rows whose bound certifies ``dist > eps``.  Tier
    accounting lands in :data:`STATS` (``lb_rows['envelope']`` /
    ``lb_pruned['envelope']``); the registry's pow2 batch padding is sliced
    off inside ``spec.batch`` so padding rows never reach the counters.
    """
    spec = registry.get_envelope(name)
    xs = np.asarray(xs)
    ys = np.asarray(ys)
    B = len(xs)
    if B == 0:
        z = np.zeros((0,), np.float32)
        return registry.KernelOut(z, z.astype(bool), z.astype(bool))
    lx = np.full(B, xs.shape[1], np.int64) if lx is None \
        else np.asarray(lx, np.int64)
    ly = np.full(B, ys.shape[1], np.int64) if ly is None \
        else np.asarray(ly, np.int64)
    eps_v = np.broadcast_to(np.asarray(eps, np.float32), (B,))
    out = spec.batch(xs, ys, lx, ly, eps=eps_v,
                     block_b=block_b, interpret=interpret)
    STATS.note_lb("envelope", B, int(out.pruned.sum()))
    return out
