"""One kernel registry for every device evaluation path.

Before this module existed the repo had four parallel device paths that had
drifted apart (``kernels/ops.py`` re-resolving the backend and re-laying-out
operands per call, ``core/counter.py``'s pallas branch restricted to a
single length bucket, ``core/distributed.py``'s private ``_batch_dist``,
and the jnp oracle).  Novak et al. (arXiv:1206.2510) argue for exactly one
pluggable evaluation substrate under many matching strategies; this
registry is that substrate's single entry point:

* one :class:`KernelSpec` per distance, keyed exactly like the PR-4
  distance registry (``dtw`` / ``erp`` / ``frechet`` / ``levenshtein`` —
  the wavefront modes — plus elementwise ``euclidean`` / ``hamming``, and
  one ``lb:<name>`` envelope spec per alignment distance with an envelope
  bound: the LB-cascade tier-1 kernel, pure O(B*L) elementwise work that
  shares this cache and the zero-retrace gate);
* one ``interpret`` policy: resolved against the default JAX backend once
  per process (:func:`default_interpret`), not per call — overridable via
  the ``REPRO_INTERPRET`` env var or the :func:`set_default_interpret`
  test hook.  Callers pass ``interpret=None`` to follow it, so a TPU
  always runs the compiled kernels;
* one execution-mode policy for the wavefront specs
  (:func:`default_exec`): ``"pallas"`` (the banded VMEM-blocked kernel —
  interpret-mode off-TPU, real hardware on TPU) or ``"scan"`` (the
  compiled ``lax.scan`` wavefront, the measured win on CPU CI) —
  overridable via ``REPRO_KERNEL_EXEC``, :func:`set_default_exec`,
  ``RetrievalConfig.kernel_exec``, or per call;
* one band-tile policy for the Pallas schedule: :func:`default_tile`
  picks the deepest band that fits the per-band VMEM budget (static per
  shape — part of the jit cache key), overridable via
  ``RetrievalConfig.kernel_tile`` or per call;
* one jit cache: every ``(kernel, Lx, Ly, d, batch, block, interpret,
  exec, tile)`` shape class compiles exactly once (:data:`STATS` counts
  traces — the retrace regression tests gate this);
* fused ε-pruning (Twin Subsequence Search, arXiv:2104.06874): pass
  ``eps`` and the kernel returns the hit mask and early-prune certificate
  alongside ``BIG``-masked distances, so range queries never materialize
  distances for pruned candidates.

Two calling conventions per spec:

* :meth:`KernelSpec.device_call` — *traceable*: safe inside an enclosing
  ``jax.jit`` (``core/distributed._device_query_jit`` composes it);
* :meth:`KernelSpec.batch` — host entry: numpy in/out, batch padded to a
  power of two, routed through the shared jit cache.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.kernels.wavefront import (BIG, vmem_bytes, wavefront_pallas,
                                     wavefront_scan)

#: wavefront mode <-> distance-registry name
MODE_OF_NAME = {"dtw": "dtw", "erp": "erp", "frechet": "dfd",
                "levenshtein": "lev"}
NAME_OF_MODE = {v: k for k, v in MODE_OF_NAME.items()}

#: trace accounting — ``traces`` increments once per kernel compile (the
#: retrace regression tests pin it).
STATS = {"traces": 0}


class CacheKey(NamedTuple):
    """One compiled shape class of the jit cache."""
    name: str
    x_shape: tuple
    x_dtype: str
    y_shape: tuple
    y_dtype: str
    batch: int
    block_b: int
    interpret: bool
    exec: Optional[str]
    tile: Optional[int]


_JIT_CACHE: Dict[CacheKey, object] = {}
_DEFAULT_INTERPRET: Optional[bool] = None

#: wavefront execution modes: the banded Pallas kernel vs the compiled
#: ``lax.scan`` wavefront (same layout, same per-diagonal math)
EXEC_MODES = ("pallas", "scan")
_DEFAULT_EXEC: Optional[str] = None

#: VMEM budget (bytes) for one grid cell of the tiled wavefront, as
#: :func:`repro.kernels.wavefront.vmem_bytes` counts it: under the 16 MiB
#: default scoped limit of a TPU v5e core
VMEM_TILE_BUDGET = 12 << 20


class KernelOut(NamedTuple):
    """One device evaluation: masked distances + fused-ε masks.

    ``dist`` holds the exact distance for rows whose verdict is a hit (or
    every row when ``eps`` was +inf/None), ``BIG`` otherwise.  ``pruned``
    marks rows certified ``> eps`` before their final diagonal (a subset
    of ``~hit``)."""
    dist: object
    hit: object
    pruned: object


def default_interpret() -> bool:
    """Interpret-mode policy, resolved ONCE per process.

    Resolution order: a value pinned by :func:`set_default_interpret`, the
    ``REPRO_INTERPRET`` env var (``1/true/yes/on`` vs anything else), then
    the JAX backend (interpret everywhere except TPU).  The env override
    lets tests pin the policy without import-order games."""
    global _DEFAULT_INTERPRET
    if _DEFAULT_INTERPRET is None:
        env = os.environ.get("REPRO_INTERPRET")
        if env is not None:
            _DEFAULT_INTERPRET = \
                env.strip().lower() in ("1", "true", "yes", "on")
        else:
            _DEFAULT_INTERPRET = jax.default_backend() != "tpu"
    return _DEFAULT_INTERPRET


def set_default_interpret(value: Optional[bool]) -> Optional[bool]:
    """Pin the process-wide interpret policy (test/bench hook).

    ``None`` clears the pin so the next :func:`default_interpret` call
    re-resolves from ``REPRO_INTERPRET`` / the JAX backend.  Returns the
    previously pinned value (None if it was unresolved) so callers can
    restore it."""
    global _DEFAULT_INTERPRET
    prev = _DEFAULT_INTERPRET
    _DEFAULT_INTERPRET = None if value is None else bool(value)
    return prev


def resolve_interpret(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


def default_exec() -> str:
    """Wavefront execution-mode policy, resolved ONCE per process.

    ``REPRO_KERNEL_EXEC`` (``pallas`` | ``scan``) overrides the default
    (``pallas``); :func:`set_default_exec` pins it programmatically."""
    global _DEFAULT_EXEC
    if _DEFAULT_EXEC is None:
        env = os.environ.get("REPRO_KERNEL_EXEC", "").strip().lower()
        if env and env not in EXEC_MODES:
            raise ValueError(
                f"REPRO_KERNEL_EXEC must be one of {EXEC_MODES}; "
                f"got {env!r}")
        _DEFAULT_EXEC = env or "pallas"
    return _DEFAULT_EXEC


def set_default_exec(value: Optional[str]) -> Optional[str]:
    """Pin the process-wide wavefront execution mode (test/bench hook).

    ``None`` clears the pin (next resolution re-reads the env var).
    Returns the previously pinned value for restore."""
    global _DEFAULT_EXEC
    if value is not None and value not in EXEC_MODES:
        raise ValueError(
            f"exec mode must be one of {EXEC_MODES}; got {value!r}")
    prev = _DEFAULT_EXEC
    _DEFAULT_EXEC = value
    return prev


def resolve_exec(exec_mode: Optional[str]) -> str:
    if exec_mode is None:
        return default_exec()
    if exec_mode not in EXEC_MODES:
        raise ValueError(
            f"exec mode must be one of {EXEC_MODES}; got {exec_mode!r}")
    return exec_mode


def default_tile(Lx: int, Ly: int, d: int, block_b: int = 8,
                 budget: int = VMEM_TILE_BUDGET) -> int:
    """Deepest anti-diagonal band whose working set fits the VMEM budget.

    The working set is :func:`~repro.kernels.wavefront.vmem_bytes` (lane-
    and sublane-padded, double-buffered); only the band's reversed-y tile
    scales with the depth, so the deepest admissible tile is linear in the
    budget.  Clamped to ``[8, Lx+Ly]`` — on short segments (every CI bench
    shape) the whole DP fits one band, which is exactly the untiled
    schedule; where even 8 diagonals overrun the budget the kernel raises
    its own VMEM limit to match.
    """
    K = Lx + Ly
    base = vmem_bytes(Lx, Ly, d, 0, block_b)
    per_t = (vmem_bytes(Lx, Ly, d, 8 * K, block_b) - base) // (8 * K)
    T = (budget - base) // per_t
    return max(8, min(int(T), K))


def clear_cache() -> None:
    """Drop compiled kernels + stats (test hygiene)."""
    _JIT_CACHE.clear()
    STATS["traces"] = 0


def cache_keys() -> list:
    """The shape classes compiled so far (what ran, and how)."""
    return list(_JIT_CACHE)


def _pad_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _pad_rows(a: np.ndarray, P: int) -> np.ndarray:
    if len(a) == P:
        return a
    pad = [(0, P - len(a))] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, pad)


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Device evaluation of one registered distance."""

    name: str                 # distance-registry key
    kind: str                 # "wavefront" | "elementwise" | "envelope"
    mode: Optional[str] = None  # wavefront DP mode (dtw/erp/dfd/lev)

    # -- traceable path ------------------------------------------------------

    def device_call(self, xs, ys, lx=None, ly=None, eps=None, *,
                    block_b: int = 8, interpret: Optional[bool] = None,
                    exec: Optional[str] = None,
                    tile: Optional[int] = None) -> KernelOut:
        """Traceable batched evaluation -> :class:`KernelOut` of jnp arrays.

        ``xs``/``ys`` are row-paired ``(B, Lx[, d])`` / ``(B, Ly[, d])``
        batches (integer tokens for the string distances); ``lx``/``ly``
        per-row actual lengths (default: the padded widths); ``eps`` a
        scalar or per-row threshold enabling the fused ε outputs.
        ``exec`` picks the wavefront execution mode (``pallas`` | ``scan``;
        None follows :func:`default_exec`) and ``tile`` the Pallas band
        depth (None: the :func:`default_tile` VMEM heuristic) — both only
        apply to the wavefront specs.
        """
        interpret = resolve_interpret(interpret)
        xs = jnp.asarray(xs)
        ys = jnp.asarray(ys)
        B = xs.shape[0]
        lx = jnp.full((B,), xs.shape[1], jnp.int32) if lx is None \
            else jnp.asarray(lx, jnp.int32)
        ly = jnp.full((B,), ys.shape[1], jnp.int32) if ly is None \
            else jnp.asarray(ly, jnp.int32)
        eps_v = jnp.full((B,), jnp.inf, jnp.float32) if eps is None \
            else jnp.broadcast_to(jnp.asarray(eps, jnp.float32), (B,))
        if self.kind == "elementwise":
            return self._elementwise(xs, ys, lx, eps_v)
        if self.kind == "envelope":
            return self._envelope(xs, ys, lx, ly, eps_v)
        return self._wavefront(xs, ys, lx, ly, eps_v, block_b=block_b,
                               interpret=interpret,
                               exec_mode=resolve_exec(exec), tile=tile)

    def _elementwise(self, xs, ys, lx, eps_v) -> KernelOut:
        L = xs.shape[1]
        mask = jnp.arange(L)[None, :] < lx[:, None]
        if self.name == "hamming":
            d = jnp.sum((xs != ys) & mask, axis=1).astype(jnp.float32)
        else:  # euclidean
            diff = xs.astype(jnp.float32) - ys.astype(jnp.float32)
            d2 = diff * diff
            if d2.ndim == 3:
                d2 = jnp.sum(d2, axis=-1)
            d = jnp.sqrt(jnp.maximum(jnp.sum(d2 * mask, axis=1), 0.0))
        hit = d <= eps_v
        return KernelOut(jnp.where(hit, d, BIG), hit,
                         jnp.zeros_like(hit))

    def _envelope(self, xs, ys, lx, ly, eps_v) -> KernelOut:
        """LB-cascade tier-1 envelope bound (O(B*L) elementwise, VPU-shaped).

        The device mirror of ``distances/bounds.py``'s two-sided envelope
        bounds (soundness proofs live there): per-row axis-aligned boxes
        over the valid positions, per-position box distances, and the
        mode-specific combine — sum (dtw), max (dfd), or the ERP element
        consumption + prefix gap-mass refinement.  ``dist`` carries the
        bound itself (never BIG-masked — pruned rows return their bound so
        callers keep the ``<= eps`` verdict); ``pruned`` certifies
        ``lb > eps``, i.e. the exact wavefront DP can be skipped."""
        xs = xs.astype(jnp.float32)
        ys = ys.astype(jnp.float32)
        if xs.ndim == 2:
            xs, ys = xs[..., None], ys[..., None]
        B, Lx, _ = xs.shape
        Ly = ys.shape[1]
        mx = jnp.arange(Lx)[None, :] < lx[:, None]
        my = jnp.arange(Ly)[None, :] < ly[:, None]
        big = jnp.float32(3.4e38)
        lo_y = jnp.where(my[..., None], ys, big).min(axis=1)
        hi_y = jnp.where(my[..., None], ys, -big).max(axis=1)
        lo_x = jnp.where(mx[..., None], xs, big).min(axis=1)
        hi_x = jnp.where(mx[..., None], xs, -big).max(axis=1)

        def box_gap(a, lo, hi):
            g = jnp.maximum(lo[:, None, :] - a, 0.0) \
                + jnp.maximum(a - hi[:, None, :], 0.0)
            return jnp.sqrt(jnp.maximum(jnp.sum(g * g, axis=-1), 0.0))

        bdx = box_gap(xs, lo_y, hi_y)          # (B, Lx)
        bdy = box_gap(ys, lo_x, hi_x)          # (B, Ly)
        if self.mode == "dfd":
            lb = jnp.maximum(jnp.max(jnp.where(mx, bdx, 0.0), axis=1),
                             jnp.max(jnp.where(my, bdy, 0.0), axis=1))
        elif self.mode == "dtw":
            lb = jnp.maximum(jnp.sum(bdx * mx, axis=1),
                             jnp.sum(bdy * my, axis=1))
        else:  # erp
            gx = jnp.where(mx, jnp.sqrt(jnp.maximum(
                jnp.sum(xs * xs, -1), 0.0)), 0.0)
            gy = jnp.where(my, jnp.sqrt(jnp.maximum(
                jnp.sum(ys * ys, -1), 0.0)), 0.0)
            cons = jnp.maximum(
                jnp.sum(jnp.minimum(gx, bdx) * mx, axis=1),
                jnp.sum(jnp.minimum(gy, bdy) * my, axis=1))
            z = jnp.zeros((B, 1), jnp.float32)
            Gx = jnp.concatenate([z, jnp.cumsum(gx, axis=1)], axis=1)
            Gy = jnp.concatenate([z, jnp.cumsum(gy, axis=1)], axis=1)
            r = jnp.arange(B)
            Tx = Gx[r, lx]
            Ty = Gy[r, ly]
            a = Gx[r, lx // 2]
            b = Tx - a
            f = jnp.abs(a[:, None] - Gy) \
                + jnp.abs(b[:, None] - (Ty[:, None] - Gy))
            valid_m = jnp.arange(Ly + 1)[None, :] <= ly[:, None]
            lb = jnp.maximum(cons, jnp.min(
                jnp.where(valid_m, f, jnp.inf), axis=1))
        hit = lb <= eps_v
        return KernelOut(lb, hit, ~hit)

    def _wavefront(self, xs, ys, lx, ly, eps_v, *, block_b, interpret,
                   exec_mode: str = "pallas",
                   tile: Optional[int] = None) -> KernelOut:
        mode = self.mode
        xs = xs.astype(jnp.float32)  # lev tokens ride as exact small floats
        ys = ys.astype(jnp.float32)
        if xs.ndim == 2:
            xs, ys = xs[..., None], ys[..., None]
        B, Lx, d = xs.shape
        Ly = ys.shape[1]

        # layout: x laid out so position i holds x[i-1]; reversed y padded so
        # diagonal k reads window start Lx+1+Ly-k (ragged rows keep their
        # zero padding at the *front* after the flip — the DP cells that
        # read it never feed the answer at (len_x, len_y))
        x_pad = jnp.pad(xs, ((0, 0), (1, 0), (0, 0)))
        Ypad = 2 * Lx + Ly + 1
        y_rev = jnp.flip(ys, axis=1)
        y_rev_pad = jnp.pad(y_rev, ((0, 0), (Lx + 1, Ypad - (Lx + 1) - Ly),
                                    (0, 0)))

        if mode == "erp":
            gx = jnp.minimum(jnp.sqrt(jnp.maximum(
                jnp.sum(xs * xs, -1), 0.0)), BIG)          # (B, Lx)
            gy = jnp.minimum(jnp.sqrt(jnp.maximum(
                jnp.sum(ys * ys, -1), 0.0)), BIG)          # (B, Ly)
            # zero the padding tail so border cumsums end at (len_x, len_y)
            gx = jnp.where(jnp.arange(Lx)[None, :] < lx[:, None], gx, 0.0)
            gy = jnp.where(jnp.arange(Ly)[None, :] < ly[:, None], gy, 0.0)
            gap_x = jnp.pad(gx, ((0, 0), (1, 0)))
            # y's gap cost rides reversed y as one extra channel, so the
            # kernel slices both with one sublane-offset window load
            gap_y_rev = jnp.pad(jnp.flip(gy, axis=1),
                                ((0, 0), (Lx + 1, Ypad - (Lx + 1) - Ly)))
            y_rev_pad = jnp.concatenate([y_rev_pad, gap_y_rev[..., None]],
                                        axis=2)
            zero = jnp.zeros((B, 1), jnp.float32)
            # clamp: a cumsum above the BIG sentinel would corrupt the DP's
            # quasi-infinity ordering (and overflow to inf three adds later)
            border_col = jnp.minimum(
                jnp.concatenate([zero, jnp.cumsum(gx, 1)], axis=1), BIG)
            border_row = jnp.minimum(
                jnp.concatenate([zero, jnp.cumsum(gy, 1)], axis=1), BIG)
        else:
            gap_x = jnp.zeros((B, Lx + 1), jnp.float32)
            if mode == "lev":
                border_col = jnp.broadcast_to(
                    jnp.arange(Lx + 1, dtype=jnp.float32)[None], (B, Lx + 1))
                border_row = jnp.broadcast_to(
                    jnp.arange(Ly + 1, dtype=jnp.float32)[None], (B, Ly + 1))
            else:
                border_col = jnp.where(jnp.arange(Lx + 1)[None] == 0, 0.0,
                                       jnp.full((B, Lx + 1), BIG, jnp.float32))
                border_row = jnp.where(jnp.arange(Ly + 1)[None] == 0, 0.0,
                                       jnp.full((B, Ly + 1), BIG, jnp.float32))

        lens = jnp.stack([lx, ly], axis=1).astype(jnp.int32)  # (B, 2)
        eps_col = eps_v[:, None]
        args = [x_pad, y_rev_pad, gap_x, border_col, border_row, lens,
                eps_col]
        if exec_mode == "scan":
            # compiled lax.scan twin: same layout, same per-diagonal math,
            # no batch blocking or banding (XLA owns the schedule)
            dist, hit, pruned = wavefront_scan(
                *args, mode=mode, Lx=Lx, Ly=Ly, d=d)
            return KernelOut(dist, hit, pruned)
        P = B + ((-B) % block_b)
        if P != B:
            args = [jnp.pad(a, [(0, P - B)] + [(0, 0)] * (a.ndim - 1))
                    for a in args]
        if tile is None:
            tile = default_tile(Lx, Ly, d, block_b)
        dist, hit, pruned = wavefront_pallas(
            *args, mode=mode, Lx=Lx, Ly=Ly, d=d, block_b=block_b,
            interpret=interpret, tile=tile)
        return KernelOut(dist[:B], hit[:B], pruned[:B])

    # -- host path (cached jit) ----------------------------------------------

    def batch(self, xs, ys, lx=None, ly=None, eps=None, *,
              block_b: int = 8, interpret: Optional[bool] = None,
              exec: Optional[str] = None,
              tile: Optional[int] = None) -> KernelOut:
        """Host entry: numpy in/out, shapes padded and jit-cached.

        ``lx``/``ly`` may mix length buckets freely; operands are trimmed
        to the max actual lengths and the batch padded to a power of two so
        the number of distinct compiled shapes stays bounded.  ``exec`` /
        ``tile`` select the wavefront execution mode and Pallas band depth
        (see :meth:`device_call`); both resolve to static values *before*
        the cache lookup, so each (shape, exec, tile) class compiles once.
        """
        xs = np.asarray(xs)
        ys = np.asarray(ys)
        B = len(xs)
        if B == 0:
            z = np.zeros((0,), np.float32)
            return KernelOut(z, z.astype(bool), z.astype(bool))
        with spans.span(spans.DISPATCH_PAD, rows=B) as sp:
            if lx is None:
                lx = np.full(B, xs.shape[1], np.int32)
            else:
                lx = np.asarray(lx, np.int32)
                xs = xs[:, :max(int(lx.max()), 1)]
            if ly is None:
                ly = np.full(B, ys.shape[1], np.int32)
            else:
                ly = np.asarray(ly, np.int32)
                ys = ys[:, :max(int(ly.max()), 1)]
            eps_v = np.full(B, np.inf, np.float32) if eps is None else \
                np.broadcast_to(np.asarray(eps, np.float32), (B,))
            P = _pad_pow2(max(B, block_b))
            args = [_pad_rows(a, P) for a in (xs, ys, lx, ly, eps_v)]
            sp.set_metadata(
                padded_rows=P, cells=int(np.dot(lx.astype(np.int64), ly)),
                padded_cells=P * xs.shape[1] * ys.shape[1])
            interpret = resolve_interpret(interpret)
            if self.kind == "wavefront":
                exec_mode = resolve_exec(exec)
                if exec_mode == "pallas" and tile is None:
                    dim = xs.shape[2] if xs.ndim == 3 else 1
                    tile = default_tile(xs.shape[1], ys.shape[1], dim,
                                        block_b)
                if exec_mode == "scan":
                    tile = None  # no banding: one cache entry per shape
            else:
                exec_mode, tile = None, None  # elementwise/envelope: jnp
        with spans.span(spans.DISPATCH_LAUNCH,
                        h2d_bytes=sum(a.nbytes for a in args)):
            fn = self._cached(xs, ys, P, block_b, interpret, exec_mode,
                              tile)
            d, h, p = fn(*args)
        with spans.span(spans.DISPATCH_FETCH,
                        d2h_bytes=d.nbytes + h.nbytes + p.nbytes):
            return KernelOut(np.asarray(d)[:B], np.asarray(h)[:B],
                             np.asarray(p)[:B])

    def _cached(self, xs, ys, P, block_b, interpret, exec_mode=None,
                tile=None):
        key = CacheKey(self.name, xs.shape[1:], str(xs.dtype), ys.shape[1:],
                       str(ys.dtype), P, block_b, interpret, exec_mode, tile)
        fn = _JIT_CACHE.get(key)
        if fn is None:
            spec = self

            def traced(xs, ys, lx, ly, eps):
                STATS["traces"] += 1  # python side effect: runs per (re)trace
                return spec.device_call(xs, ys, lx, ly, eps,
                                        block_b=block_b, interpret=interpret,
                                        exec=exec_mode, tile=tile)

            fn = jax.jit(traced)
            _JIT_CACHE[key] = fn
        return fn


_KERNELS: Dict[str, KernelSpec] = {}
for _name, _mode in MODE_OF_NAME.items():
    _KERNELS[_name] = KernelSpec(name=_name, kind="wavefront", mode=_mode)
for _name in ("euclidean", "hamming"):
    _KERNELS[_name] = KernelSpec(name=_name, kind="elementwise")
# LB-cascade tier-1 envelope kernels: one per alignment distance with a
# registered envelope bound (levenshtein's length bound is already exact
# at tier 0, and token boxes are meaningless — no lb:levenshtein).
for _name in ("dtw", "erp", "frechet"):
    _KERNELS[f"lb:{_name}"] = KernelSpec(
        name=f"lb:{_name}", kind="envelope", mode=MODE_OF_NAME[_name])


def has(name: str) -> bool:
    return name in _KERNELS


def has_envelope(name: str) -> bool:
    """Whether distance ``name`` has a device tier-1 envelope kernel."""
    return f"lb:{name}" in _KERNELS


def get_envelope(name: str) -> KernelSpec:
    return get(f"lb:{name}")


def get(name: str) -> KernelSpec:
    if name not in _KERNELS:
        raise KeyError(
            f"no device kernel for distance {name!r}; have {sorted(_KERNELS)}")
    return _KERNELS[name]


def spec_for_mode(mode: str) -> KernelSpec:
    """Look up a wavefront spec by DP mode (``dtw``/``erp``/``dfd``/``lev``)."""
    if mode not in NAME_OF_MODE:
        raise KeyError(f"unknown wavefront mode {mode!r}")
    return get(NAME_OF_MODE[mode])


def names():
    return sorted(_KERNELS)
