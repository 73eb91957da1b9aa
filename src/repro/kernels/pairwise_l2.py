"""Pallas TPU kernel: MXU-tiled pairwise Euclidean distance matrix.

Used by the embedding-retrieval path: filtering M query windows against N
database windows under L2 is ``||x||^2 + ||y||^2 - 2 x @ y.T`` — one MXU
matmul per (128, 128) output tile with both operand tiles resident in VMEM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import registry


def _kernel(x_ref, y_ref, out_ref):
    x = x_ref[...]  # (bm, d)
    y = y_ref[...]  # (bn, d)
    xn = jnp.sum(x * x, axis=1, keepdims=True)          # (bm, 1)
    yn = jnp.sum(y * y, axis=1, keepdims=True).T        # (1, bn)
    xy = jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)             # (bm, bn) on the MXU
    d2 = xn + yn - 2.0 * xy
    out_ref[...] = jnp.sqrt(jnp.maximum(d2, 0.0))


def pairwise_l2_pallas(x, y, *, bm: int = 128, bn: int = 128,
                       interpret: Optional[bool] = None):
    """(M, d) x (N, d) -> (M, N); M, N padded to tile multiples by ops.py.

    ``interpret=None`` resolves through the registry's single process-wide
    interpret policy (``registry.default_interpret()``) — resolution
    happens *outside* the jitted inner so a later policy change (the
    ``set_default_interpret`` hook) is never shadowed
    by a stale jit cache entry keyed on None.
    """
    return _pairwise_l2_jit(x, y, bm=bm, bn=bn,
                            interpret=registry.resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def _pairwise_l2_jit(x, y, *, bm, bn, interpret):
    M, d = x.shape
    N = y.shape[0]
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    grid = (M // bm, N // bn)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(x.astype(jnp.float32), y.astype(jnp.float32))
