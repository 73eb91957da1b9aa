"""Pallas TPU kernel: batched anti-diagonal wavefront alignment DP,
VMEM-blocked into diagonal bands.

This is the paper's compute hot spot (§5/§7 step 4: every query segment is
compared against every surviving database window under an O(l^2) alignment
distance).  The TPU-native schedule:

* the batch of independent DP problems rides the sublane axis — one grid
  cell owns a ``(block_b, L+1)`` wavefront held in VMEM/VREGs;
* the ``2l`` diagonal steps are cut into **bands** of ``tile`` consecutive
  anti-diagonals; the grid is ``(batch block, band)`` and each cell runs a
  ``fori_loop`` over its band's diagonals — pure VPU work: two rolling
  diagonal buffers, an elementwise cost slice, min/add;
* the elementwise cost is computed **on the fly** from the x tile and a
  *flipped* y tile: cost of diagonal k is ``elem(x[i-1], y[k-i-1])`` which
  is one contiguous window load of reversed-y at a sublane offset — no
  gathers, no (L x L) cost tile in HBM, arithmetic intensity stays
  on-chip;
* per band, only that band's ``(Lx + tile)``-wide window of reversed-y is
  staged (``band_layout`` pre-gathers the overlapping windows, since a
  BlockSpec index map can only address multiples of the block shape), so
  the VMEM working set is fixed by the tile, not the segment length;
* the two carry diagonals, the per-row answer, and the fused-ε liveness
  certificate are handed between bands through VMEM scratch accumulators
  — TPU grids iterate sequentially (bands innermost), so band ``j`` reads
  exactly what band ``j-1`` wrote, and the final band materializes the
  outputs;
* borders (column j=0 / row i=0) are injected per step from precomputed
  border vectors (constant for DTW/DFD/Lev, gap cumsums for ERP).

Ragged batches: every row carries its own ``(len_x, len_y)`` (the packed
dispatcher concatenates all length buckets of a round into one call), and
the answer ``D[len_x, len_y]`` is recorded on the fly when diagonal
``len_x + len_y`` passes — whichever band that diagonal lands in.  Cells
outside a row's actual problem compute padding garbage that never feeds
its answer cell (DP dependencies only point to smaller indices).

Band mode (``band=(lam, lambda0)``): a row is a start pair of the paper's
step-5 verification, and its answer is every end cell ``(i, j)`` with
``i, j >= lam`` and ``|i - j| <= lambda0`` inside its lengths, recorded
the same way as its diagonal passes — one DP per start pair, however many
candidate pairs share it.

Fused ε-pruning: each row also carries an ``eps`` threshold.  All four
distances are monotone along alignment paths (every combine adds a
nonnegative cost or takes a max), and any monotone path touches at least
one cell of any two consecutive diagonals, so ``min`` over the two rolling
diagonals exceeding ``eps`` is a certificate that the final distance does.
The kernel tracks that certificate per row step-by-step (bit-identical to
the untiled schedule), but a prune **verdict** is only ever emitted at a
band boundary — the certificate rides the scratch accumulators and the
``pruned`` output materializes with the final band, which preserves
soundness under any band split.  Rows with ``eps = +inf`` (the default
layout for value-consuming callers) disable both effects, so fused and
plain evaluation share one compiled kernel.

:func:`wavefront_scan` is the compiled ``lax.scan`` twin (the registry's
``exec="scan"`` mode): the same operand layout and the same per-diagonal
update (:func:`_make_step` is the single source of the DP math for every
execution mode), scanned over diagonals as one XLA while loop — the
measured win on CPU CI, while the Pallas path targets TPU.

Modes: ``dtw`` / ``erp`` / ``dfd`` / ``lev`` (paper's four alignment
distances).  Per-call padded shapes and the band tile are static; the
registry (``kernels/registry.py``) owns the jit cache over them.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 3.4e37  # python float: Pallas kernels must not capture traced constants


def _tiles(n: int, m: int) -> int:
    return -(-n // m) * m


def vmem_bytes(Lx: int, Ly: int, d: int, tile: int, block_b: int = 8,
               nb: int = 0) -> int:
    """Upper bound on the banded kernel's VMEM working set, as the chip lays
    it out: a block's last axis is padded to 128 lanes and the one before
    it to 8 sublanes, so a ``(block_b, W, d)`` block costs ``W*128`` words
    per row however small ``d`` is.  Counts the double-buffered x and
    band-y blocks (y with one ERP gap channel), four in-loop temporaries
    of the diagonal window's shape, the double-buffered 2-D operands and
    outputs, and the carry scratch; in band mode (``nb`` band diagonals)
    also the band's carry scratch, its double-buffered output block and
    one temporary per band diagonal.  Only the y band grows with ``tile``.
    """
    W = Lx + 1
    lanes_x = _tiles(d, 128)
    lanes_y = _tiles(d + 1, 128)
    rows = _tiles(block_b, 8)
    words = (2 * block_b * _tiles(W, 8) * lanes_x
             + 2 * block_b * _tiles(Lx + tile, 8) * lanes_y
             + 4 * block_b * _tiles(W, 8) * lanes_y
             + 2 * rows * (2 * _tiles(W, 128) + _tiles(Ly + 1, 128) + 5 * 128)
             + rows * (2 * _tiles(W, 128) + 2 * 128)
             + 4 * nb * rows * _tiles(W, 128))
    return 4 * words


def _shift_right(v, fill):
    return jnp.concatenate([jnp.full_like(v[:, :1], fill), v[:, :-1]], axis=1)


def _make_step(mode: str, Lx: int, Ly: int, d: int,
               band: Optional[Tuple[int, int]] = None):
    """One anti-diagonal DP update — the single source of the per-step math.

    Every execution mode (tiled Pallas, interpret-mode Pallas, compiled
    scan) calls this exact closure, so their results are bit-identical and
    the parity gates compare equality, not tolerance.  ``carry`` is
    ``(d1, d2, res, alive)``: the two rolling diagonals, the recorded
    answers, and the fused-ε liveness mask (f32 0/1 so it can ride VMEM
    scratch).  ``res`` is each row's ``D[len_x, len_y]``, or in band mode
    (``band=(lam, lambda0)``) a tuple of ``2*lambda0 + 1`` ``(B, Lx+1)``
    arrays: entry ``q`` holds ``D[i, i + q - lambda0]`` at position ``i``
    for every end cell with ``lam <= i <= len_x`` and
    ``lam <= j <= len_y``, recorded as its diagonal passes (see
    :func:`_record_band`).  ``yw`` is diagonal ``k``'s ``(B, Lx+1, dy)``
    reversed-y window, already sliced by the caller (full-layout or
    band-tile offsets — the only thing that differs between execution
    modes); for ERP its last channel is the reversed gap cost of y
    (``dy = d + 1``).

    Every lookup by the traced diagonal index ``k`` is an iota select (and
    a select-sum over one nonzero term, which is exact): Mosaic lowers
    neither scatters nor value-level dynamic slices.
    """

    def step(k, carry, x, yw, gx, bc, br, lx, target, eps):
        d1, d2, res, alive = carry  # diagonals k-1, k-2
        ii = jax.lax.broadcasted_iota(jnp.int32, (1, Lx + 1), 1)
        jj = jax.lax.broadcasted_iota(jnp.int32, (1, Ly + 1), 1)
        ysl = yw[..., :d]
        if mode == "lev":
            c = (jnp.sum(jnp.abs(x - ysl), axis=-1) > 0).astype(jnp.float32)
        else:
            c = jnp.sqrt(jnp.maximum(jnp.sum((x - ysl) ** 2, axis=-1), 0.0))
            c = jnp.minimum(c, BIG)
        dd = _shift_right(d2, BIG)
        du = _shift_right(d1, BIG)
        dl = d1
        if mode == "dtw":
            new = c + jnp.minimum(dd, jnp.minimum(du, dl))
        elif mode == "dfd":
            new = jnp.maximum(c, jnp.minimum(dd, jnp.minimum(du, dl)))
        elif mode == "lev":
            new = jnp.minimum(dd + c, jnp.minimum(du + 1.0, dl + 1.0))
        else:  # erp
            gy = jnp.sum(yw[..., d:], axis=-1)
            new = jnp.minimum(dd + c, jnp.minimum(du + gx, dl + gy))
        # clamp: sums of quasi-infinities must stay quasi-infinite, never
        # run off to float32 inf/NaN (long high-gap-mass series)
        new = jnp.minimum(new, BIG)
        # border column j = 0 lives at position i = k (while k <= Lx)
        new = jnp.where((ii == k) & (k <= Lx), bc, new)
        # border row i = 0 lives at position 0 (while k <= Ly)
        rowv = jnp.sum(jnp.where(jj == jnp.minimum(k, Ly), br, 0.0),
                       axis=1, keepdims=True)
        new = jnp.where(ii == 0, jnp.where(k <= Ly, rowv, BIG), new)
        # outside the valid band
        new = jnp.where((ii > k) | (ii < k - Ly), BIG, new)
        if band is None:
            # record each row's answer when its target diagonal passes
            val = jnp.sum(jnp.where(ii == lx, new, 0.0), axis=1,
                          keepdims=True)
            res = jnp.where(target == k, val, res)
        else:
            res = _record_band(k, new, res, ii, lx, target, band)
        # fused ε certificate: every monotone path touches one of any two
        # consecutive diagonals, so both exceeding eps bounds the final
        rowmin = jnp.min(jnp.minimum(new, d1), axis=1, keepdims=True)
        ok = ((rowmin <= eps) | (k > target)).astype(jnp.float32)
        return (new, d1, res, alive * ok)

    return step


def _record_band(k, new, res, ii, lx, target, band):
    """Band mode: keep diagonal ``k``'s end cells.  Cell ``(i, j)`` lies on
    diagonal ``i + j``, so band entry ``q`` (``j = i + q - lambda0``)
    takes position ``i`` when ``k - 2i == q - lambda0``; cells outside
    ``[lam, len_x] x [lam, len_y]`` are never written and keep ``BIG``."""
    lam, l0 = band
    j = k - ii
    live = (ii >= lam) & (ii <= lx) & (j >= lam) & (j <= target - lx)
    return tuple(jnp.where(live & (j - ii == q - l0), new, r)
                 for q, r in enumerate(res))


def _init_carry(bc, target, band=None):
    """Diagonal 0 holds only ``D[0,0] = bc[:, 0]``; diagonal -1 is empty."""
    ii = jax.lax.broadcasted_iota(jnp.int32, bc.shape, 1)
    diag0 = jnp.where(ii == 0, bc, BIG)
    if band is None:
        res = jnp.where(target == 0, bc[:, 0:1], BIG)
    else:
        res = tuple(jnp.full(bc.shape, BIG, jnp.float32)
                    for _ in range(2 * band[1] + 1))
    return (diag0,
            jnp.full(bc.shape, BIG, jnp.float32),
            res,
            jnp.ones(target.shape, jnp.float32))


def _band_out(raw, eps, lam: int):
    """``(nb, B, Lx+1)`` recorded band -> ``(B, Lx-lam+1, nb)``: entry
    ``[r, i - lam, q]`` is ``D[i, i + q - lambda0]`` of row ``r``, ``+inf``
    where the cell does not exist or exceeds the row's ``eps`` (fused ε:
    a cell above it is never needed, so it is never returned)."""
    v = jnp.moveaxis(raw[:, :, lam:], 0, 2)
    keep = (v < BIG) & (v <= eps[:, :, None])
    return jnp.where(keep, v, jnp.inf)


def band_layout(y_rev_pad, Lx: int, Ly: int, tile: int):
    """Pre-gather the per-band overlapping reversed-y windows.

    Band ``j`` (diagonals ``j*tile+1 .. (j+1)*tile``) reads reversed-y
    window starts ``s(k) = Lx+1+Ly-k`` over ``tile`` consecutive diagonals,
    i.e. the ``(Lx + tile)``-wide stretch starting at
    ``o_j = Lx+1+Ly-(j+1)*tile``.  A BlockSpec index map can only address
    multiples of the block shape, so overlapping stride-``tile`` windows of
    width ``Lx + tile`` are not expressible directly — instead the bands
    are gathered into a ``(nbands, B, Lx+tile, dy)`` operand whose ``j``-th
    slab is band ``j``'s tile.  The band axis leads so that a block's last
    two dims are the full ``(Lx+tile, dy)`` (the TPU's (8, 128) block rule
    holds at any band width), and the kernel's in-band offset for diagonal
    ``k`` is ``(j+1)*tile - k`` (``tile-1-t`` for the band-local step
    index ``t``) — on the sublane axis, where a dynamic offset is legal.

    Late bands clip below index 0; clipped positions are only ever read by
    DP cells outside the valid band, whose values the kernel overwrites
    with borders or the BIG sentinel before they can feed any answer.
    """
    Ypad = y_rev_pad.shape[1]
    K = Lx + Ly
    nbands = -(-K // tile)
    w = jnp.arange(Lx + tile)
    o = Lx + 1 + Ly - (jnp.arange(nbands) + 1) * tile
    idx = jnp.clip(o[:, None] + w[None, :], 0, Ypad - 1)
    return jnp.moveaxis(jnp.take(y_rev_pad, idx, axis=1), 1, 0)


def _make_kernel(mode: str, Lx: int, Ly: int, d: int, tile: int,
                 nbands: int, band: Optional[Tuple[int, int]] = None):
    W = Lx + 1
    K = Lx + Ly
    step = _make_step(mode, Lx, Ly, d, band)
    n_out = 3 if band is None else 1
    nb = 0 if band is None else 2 * band[1] + 1

    def load_res(ref):
        return ref[...] if band is None else tuple(ref[q] for q in range(nb))

    def store_res(ref, res):
        if band is None:
            ref[...] = res
        else:
            for q in range(nb):
                ref[q] = res[q]

    def kernel(x_ref, yb_ref, gx_ref, bc_ref, br_ref, lens_ref, eps_ref,
               *refs):
        outs = refs[:n_out]
        d1_ref, d2_ref, res_ref, alive_ref = refs[n_out:]
        x = x_ref[...]          # (Bt, W, d)   x[i] = x_orig[i-1]
        gx = gx_ref[...]        # (Bt, W)      ERP gap cost of x_i (else 0)
        bc = bc_ref[...]        # (Bt, Lx+1)   border column D[i,0]
        br = br_ref[...]        # (Bt, Ly+1)   border row    D[0,j]
        lens = lens_ref[...]    # (Bt, 2)      int32 actual (len_x, len_y)
        eps = eps_ref[...]      # (Bt, 1)      fused threshold (+inf = off)
        lx = lens[:, 0:1]
        target = lx + lens[:, 1:2]   # diagonal holding D[len_x, len_y]
        j = pl.program_id(1)

        # band 0 seeds the carry scratch; later bands inherit band j-1's
        # (TPU grids iterate sequentially with bands innermost, and the
        # scratch accumulators persist across a batch block's grid cells)
        @pl.when(j == 0)
        def _init():
            d1, d2, res, alive = _init_carry(bc, target, band)
            d1_ref[...] = d1
            d2_ref[...] = d2
            store_res(res_ref, res)
            alive_ref[...] = alive

        def body(t, carry):
            k = j * tile + 1 + t
            # diagonal k's window inside this band's tile (see band_layout):
            # yb_ref is (Bt, Lx+tile, dy), the offset rides the sublanes
            yw = yb_ref[:, pl.ds(tile - 1 - t, W), :]
            out = step(k, carry, x, yw, gx, bc, br, lx, target, eps)
            # the last band may be ragged: steps past diagonal K are no-ops
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(k <= K, n, o), out, carry)

        carry = (d1_ref[...], d2_ref[...], load_res(res_ref),
                 alive_ref[...])
        d1, d2, res, alive = jax.lax.fori_loop(0, tile, body, carry)
        d1_ref[...] = d1
        d2_ref[...] = d2
        store_res(res_ref, res)
        alive_ref[...] = alive

        # prune verdicts are only emitted at a band boundary — here, the
        # final one; the certificate itself rides the scratch accumulator
        @pl.when(j == nbands - 1)
        def _emit():
            if band is not None:
                store_res(outs[0], res)
                return
            out_ref, hit_ref, prune_ref = outs
            hit = res <= eps
            out_ref[...] = jnp.where(hit, res, BIG)
            hit_ref[...] = hit.astype(jnp.float32)
            prune_ref[...] = 1.0 - alive

    return kernel


def wavefront_pallas(x_pad, y_rev_pad, gap_x, border_col, border_row, lens,
                     eps, *, mode, Lx, Ly, d, block_b, interpret,
                     tile: Optional[int] = None,
                     band: Optional[Tuple[int, int]] = None):
    """Run the banded kernel on pre-laid-out inputs (traceable — the
    registry owns jit caching; see ``registry.KernelSpec.device_call``).

    ``tile`` is the band depth in anti-diagonals (static per shape; the
    registry's ``default_tile`` VMEM-budget heuristic picks it when None).
    ``tile >= Lx + Ly`` degenerates to a single band — the exact untiled
    schedule.  The scoped-VMEM limit is set from :func:`vmem_bytes` (plus
    1 MiB for the compiler's own scratch), so a band that the registry's
    budget admitted, or an explicit ``tile``, is not refused by a fixed
    default limit.  Returns ``(dist, hit, pruned)`` as (B,) float32
    arrays: masked distances (``BIG`` where the verdict is a miss), the
    hit mask, and the early-prune certificate mask.

    Band mode (``band=(lam, lambda0)``, the ``pallas_call`` named
    ``wavefront_<mode>_band``) returns instead every end cell of each
    row's DP near its diagonal, ``(B, Lx-lam+1, 2*lambda0+1)`` (see
    :func:`_band_out`): one DP per start pair answers all of its
    subsequence pairs.
    """
    B = x_pad.shape[0]
    W = Lx + 1
    K = Lx + Ly
    T = K if tile is None else max(1, min(int(tile), K))
    nbands = -(-K // T)
    Wb = Lx + T
    dy = y_rev_pad.shape[2]
    nb = 0 if band is None else 2 * band[1] + 1
    y_bands = band_layout(y_rev_pad, Lx, Ly, T)    # (nbands, B, Wb, dy)
    grid = (B // block_b, nbands)
    kernel = _make_kernel(mode, Lx, Ly, d, T, nbands, band)
    row = lambda b, j: (b, 0)  # noqa: E731 — per-batch-block 2-D operands
    if band is None:
        out_specs = [pl.BlockSpec((block_b, 1), row)] * 3
        out_shape = [jax.ShapeDtypeStruct((B, 1), jnp.float32)] * 3
        res_scratch = pltpu.VMEM((block_b, 1), jnp.float32)
        name = f"wavefront_{mode}"
    else:
        out_specs = [pl.BlockSpec((nb, block_b, W), lambda b, j: (0, b, 0))]
        out_shape = [jax.ShapeDtypeStruct((nb, B, W), jnp.float32)]
        res_scratch = pltpu.VMEM((nb, block_b, W), jnp.float32)
        name = f"wavefront_{mode}_band"
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, W, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((pl.Squeezed(), block_b, Wb, dy),
                         lambda b, j: (j, b, 0, 0)),
            pl.BlockSpec((block_b, W), row),
            pl.BlockSpec((block_b, Lx + 1), row),
            pl.BlockSpec((block_b, Ly + 1), row),
            pl.BlockSpec((block_b, 2), row),
            pl.BlockSpec((block_b, 1), row),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_b, W), jnp.float32),   # carry diagonal k-1
            pltpu.VMEM((block_b, W), jnp.float32),   # carry diagonal k-2
            res_scratch,                             # recorded answers
            pltpu.VMEM((block_b, 1), jnp.float32),   # fused-ε liveness
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_bytes(Lx, Ly, d, T, block_b, nb)
            + (1 << 20)),
        interpret=interpret,
        name=name,
    )(x_pad, y_bands, gap_x, border_col, border_row, lens, eps)
    if band is not None:
        return _band_out(outs[0], eps, band[0])
    dist, hit, pruned = outs
    return dist[:, 0], hit[:, 0] > 0, pruned[:, 0] > 0


def wavefront_scan(x_pad, y_rev_pad, gap_x, border_col, border_row, lens,
                   eps, *, mode, Lx, Ly, d,
                   band: Optional[Tuple[int, int]] = None):
    """Compiled ``lax.scan`` wavefront — the registry's ``exec="scan"``
    execution mode.

    Identical operand layout and per-diagonal update as the Pallas kernel
    (:func:`_make_step`), but scanned over the ``Lx+Ly`` diagonals as one
    XLA while loop with a known trip count — no Pallas, no banding, no
    batch blocking.  On CPU CI this is the measured device-path win (the
    interpret-mode Pallas emulation is parity theater); on TPU the banded
    Pallas kernel owns the hot path.  Returns the same ``(dist, hit,
    pruned)`` triple, bit-identical to the Pallas schedules, or in band
    mode the same band block.
    """
    B = x_pad.shape[0]
    W = Lx + 1
    dy = y_rev_pad.shape[2]
    lx = lens[:, 0:1]
    target = lx + lens[:, 1:2]
    step = _make_step(mode, Lx, Ly, d, band)

    def body(carry, k):
        s = Lx + 1 + Ly - k  # start of the diagonal window in reversed y
        yw = jax.lax.dynamic_slice(y_rev_pad, (0, s, 0), (B, W, dy))
        return step(k, carry, x_pad, yw, gap_x, border_col, border_row,
                    lx, target, eps), None

    (_, _, res, alive), _ = jax.lax.scan(
        body, _init_carry(border_col, target, band),
        jnp.arange(1, Lx + Ly + 1))
    if band is not None:
        return _band_out(jnp.stack(res), eps, band[0])
    hit = res <= eps
    dist = jnp.where(hit, res, BIG)
    return dist[:, 0], hit[:, 0] > 0, alive[:, 0] < 0.5
