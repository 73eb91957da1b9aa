"""Window-level range search: a request is one window-length query, its
answer the ids of every database window within ``eps`` of it.

A request kind is a file ``bench/kinds/<kind>.py``, named by the
configuration's top-level ``"kind"`` (this one where it is absent).  It
gives the harness everything that depends on what a request is:

* ``make(cell, seed)``: the database handed to ``Retriever.build`` and
  the query pool, from the configuration, the traffic and the seed;
* ``warm(cell, data, pool, eps)``: run every kernel shape the window can
  reach once; returns how many it ran;
* ``serve(fleet, eps)``: the engine the window drives;
* ``submit(engine, query, at)``: one query, stamped with its due time;
  returns the engine's request;
* ``answer(request)`` and ``size(answer)``: a finished request's answer,
  and its size for the window line's ``hits_per_request``;
* ``reference(cell, data, queries, threads)``: the plain reference for
  each query, computed after the window;
* ``control(cell, data, queries, refs, eps, threads)``: the answers of
  the configuration's ``control`` in the program's place;
* ``gaps(answer, ref, eps)``: ``|d - eps|`` of each wrong verdict of one
  request, so that ``unanswered``, ``wrong_verdicts``, ``verdict_gap``
  and ``failed`` mean the same for every kind.
* ``kernel_work(run)``: ``(operations, bytes)`` of the real kernel rows
  the window dispatched (``bench/costs.py`` says what is counted), which
  ``wavefront_roofline`` divides by the kernel's device time; ``None``
  where there were none.

Here the data comes from ``bench/gen.py`` (the configuration's
``data.generator``) and the queries are pool windows perturbed by the
traffic's ``queries``.  The distance is the configuration's
``retrieval.distance``, found in ``bench/distances/<name>.py``.  Every
kernel row is a query of ``data.l`` elements against a window of as
many, each element ``d`` numbers wide (the configuration's top-level
``"d"``, 1 where absent).  The controls: ``open_ball`` (the reference
with ``d < eps`` in place of ``d <= eps``, which breaks the stated
guarantee) and ``bf16`` (the distance's ``control``, its DP in
bfloat16).
"""

from __future__ import annotations

import numpy as np

from bench import costs, gen
from bench import reference as plain


def make(cell, seed: int):
    d = dict(cell.config["data"])
    data = gen.GENERATORS[d.pop("generator")](seed=seed, **d)
    pool = gen.perturb(data, int(cell.traffic["pool"]), seed,
                       **cell.traffic.get("queries", {}))
    return data, pool


def warm(cell, data, pool, eps: float) -> int:
    """Run one packed dispatch of each batch class a round can reach.

    The kernel registry compiles one program per power-of-two batch from
    8 rows up; the traffic file's ``warm_rows`` is the largest round its
    cell is warmed for.  (The served path screens rows with the envelope
    tier on the host, so no envelope kernel runs.)"""
    from repro.kernels import dispatch
    top = int(cell.traffic["warm_rows"])
    rows, n = 8, 0
    while True:
        idx = np.arange(min(rows, top))
        dispatch.packed_batch(cell.distance, pool[idx % len(pool)],
                              data[idx % len(data)], eps=eps)
        n += 1
        if rows >= top:
            return n
        rows *= 2


def serve(fleet, eps: float):
    return fleet.serve(eps)


def submit(engine, query, at: float):
    return engine.submit(query, now=at)


def answer(request):
    return request.hits


def size(answer_) -> int:
    return len(answer_)


def reference(cell, data, queries, threads: int):
    """Each query's distance to every window."""
    return plain.distances(cell.distance, queries, data, threads=threads)


def control(cell, data, queries, refs, eps: float, threads: int):
    kind = cell.config["control"]
    if kind == "open_ball":
        return [np.flatnonzero(d < eps) for d in refs]
    if kind == "bf16":
        low = plain.distances(cell.distance, queries, data, control=True,
                              threads=threads)
        return [np.flatnonzero(d <= eps) for d in low]
    raise KeyError(f"unknown control {kind!r}")


def gaps(answer_, ref, eps: float) -> np.ndarray:
    return plain.verdict_gaps(answer_, ref, eps)


def kernel_work(run):
    rows = run.window.counters["rows"]
    if not rows:
        return None
    l, d = run.cell.config["data"]["l"], int(run.cell.config.get("d", 1))
    dist = run.cell.distance
    return (rows * costs.row_ops(dist, l, l, d),
            rows * costs.row_bytes(dist, l, l, d))
