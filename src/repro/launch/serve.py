"""Continuous-batching serve CLI — the front end of the PR-9 serve engine.

  PYTHONPATH=src python -m repro.launch.serve --dataset proteins \
      --n-windows 2000 --shards 4 --queries 32 --eps 2.0 --qps 16

  # or declaratively: the whole retrieval stack from one JSON config
  PYTHONPATH=src python -m repro.launch.serve --config fleet.json \
      --qps 16 --duration 2.0 --snapshot-dir /tmp/fleet-snaps

``--config path.json`` deserializes straight into
:class:`~repro.retrieval.RetrievalConfig` (the file is exactly
``RetrievalConfig.to_json()`` output).  The driver builds the fleet
through the :class:`~repro.retrieval.Retriever` facade, then serves an
open-loop Poisson request stream through the continuous-batching
:class:`~repro.serve.engine.ServeEngine`: asynchronous requests join the
shared frontier cadence mid-flight (one packed dispatch per merged
round), a mid-load ``resize()`` runs through the zero-downtime
snapshot-swap path, and every answer is cross-checked against the host
per-shard oracle loop.  Latency lands as p50/p95/p99 percentiles.

Timing methodology: an UNTIMED warmup batch over every distinct query
runs first, so the timed section measures warm serving — first-call
trace/compile never pollutes the reported qps (``traces_timed`` in the
output counts kernel traces inside the timed window; warm serving keeps it
at zero).  :func:`serve_open_loop` is that warmup-then-serve window, shared
with ``chip_smoke.py``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import time
from typing import Optional

import numpy as np

from repro.data import synthetic
from repro.kernels import registry as kernel_registry
from repro.launch import compile_cache
from repro.retrieval import RetrievalConfig, Retriever
from repro.serve import OpenLoopLoadGen


def build_config(args) -> RetrievalConfig:
    """``--config path.json`` round-trips the declarative config; otherwise
    the legacy flags assemble the same dataclass, serving from the device
    kernels (compiled on a TPU, interpreted elsewhere)."""
    if args.config:
        cfg = RetrievalConfig.from_json(
            pathlib.Path(args.config).read_text())
        if cfg.execution != "fleet":
            raise SystemExit(
                f"serve.py drives a fleet; config has "
                f"execution={cfg.execution!r}")
        return cfg
    _, default_dist = synthetic.DATASETS[args.dataset]
    return RetrievalConfig(
        distance=args.distance or default_dist or "erp",
        execution="fleet",
        workers=[f"worker{i}" for i in range(args.shards)],
        kernel_backend="pallas",
        tight_bounds=True)


def make_queries(data: np.ndarray, n: int, rng) -> np.ndarray:
    """Database rows perturbed into near-miss queries."""
    queries = data[rng.integers(0, len(data), n)].copy()
    if data.dtype.kind == "i":
        flips = rng.random(queries.shape) < 0.1
        queries[flips] = rng.integers(0, queries.max() + 1, flips.sum())
    else:
        queries += rng.normal(scale=0.1, size=queries.shape).astype(
            queries.dtype)
    return queries


@dataclasses.dataclass
class ServeRun:
    """One open-loop serving window: the requests (in submission order),
    the engine that served them, and the window's wall time and kernel
    traces (0 when the warmup covered every shape)."""
    requests: list
    engine: object
    serve_s: float
    traces_timed: int
    resized: bool


def serve_open_loop(fleet, queries, eps: float, qps: float,
                    n_requests: Optional[int] = None,
                    resize_to: int = 0) -> ServeRun:
    """Serve ``n_requests`` (``queries`` cycled) on an open-loop Poisson
    schedule through ``fleet.serve(eps)``.

    An UNTIMED warmup batch over every distinct query runs first, so the
    timed window measures warm serving.  ``resize_to`` (a worker count
    other than the current one; 0 = none) reshards mid-load through the
    zero-downtime snapshot-swap path."""
    workers = fleet.elastic().workers
    n_requests = len(queries) if n_requests is None else n_requests
    qlist = [queries[i % len(queries)] for i in range(n_requests)]

    fleet.batch(queries).range(eps)
    traces0 = kernel_registry.STATS["traces"]

    engine = fleet.serve(eps).start()
    load = OpenLoopLoadGen(engine, qlist, qps, eps=eps).start()
    t0 = time.time()
    resized = bool(resize_to) and resize_to != len(workers)
    if resized:
        # mid-load: snapshot -> reshard a clone off-path -> swap at a
        # round boundary; the stream keeps serving throughout
        time.sleep(0.5 / qps)
        new_workers = (workers[:resize_to] if resize_to < len(workers)
                       else workers + [f"w{i}" for i in
                                       range(resize_to - len(workers))])
        engine.resize(new_workers, block=False)
    reqs = load.join()
    if resized:
        deadline = time.time() + 60
        while engine.swaps == 0 and time.time() < deadline:
            time.sleep(1e-3)
    engine.close(drain=True)
    serve_s = time.time() - t0
    return ServeRun(reqs, engine, serve_s,
                    kernel_registry.STATS["traces"] - traces0, resized)


def latency_ms(engine) -> dict:
    """The engine's p50/p95/p99 request latencies, in milliseconds."""
    lat = engine.latency_stats()
    return {f"latency_{k}_ms": 1e3 * lat[k] for k in ("p50", "p95", "p99")}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None,
                    help="path to a RetrievalConfig JSON (to_json output); "
                         "replaces --distance/--shards")
    ap.add_argument("--dataset", default="proteins",
                    choices=["proteins", "songs", "traj"])
    ap.add_argument("--distance", default=None)
    ap.add_argument("--n-windows", type=int, default=2000)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--queries", type=int, default=32,
                    help="distinct query windows (cycled if --duration "
                         "asks for more requests)")
    ap.add_argument("--eps", type=float, default=2.0)
    ap.add_argument("--qps", type=float, default=8.0,
                    help="open-loop Poisson arrival rate")
    ap.add_argument("--duration", type=float, default=None,
                    help="seconds of load (default: queries/qps)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="fleet snapshot directory (default: a temp dir)")
    ap.add_argument("--resize-to", type=int, default=-1,
                    help="mid-load zero-downtime resize to this many "
                         "workers (-1 = one fewer than built; 0 = skip)")
    args = ap.parse_args()
    compile_cache.enable()

    config = build_config(args)
    if args.snapshot_dir:
        config = config.replace(serve_snapshot_dir=args.snapshot_dir)
    gen, _ = synthetic.DATASETS[args.dataset]
    data = gen(args.n_windows, seed=0)
    rng = np.random.default_rng(1)

    t0 = time.time()
    fleet = Retriever.build(config, data)
    build_s = time.time() - t0
    workers = fleet.elastic().workers

    queries = make_queries(data, args.queries, rng)
    n_requests = len(queries) if args.duration is None \
        else max(1, int(args.qps * args.duration))

    # oracle BEFORE serving: the host per-shard loop in ONE facade batch
    # call (hit sets are shard-layout-invariant, so it stays valid across
    # the mid-load resize below)
    oracle = fleet.batch(queries).via("host").range(args.eps).hits

    run = serve_open_loop(
        fleet, queries, args.eps, args.qps, n_requests,
        resize_to=(len(workers) - 1 if args.resize_to == -1
                   else args.resize_to))
    reqs, engine = run.requests, run.engine

    mismatched = [i for i, r in enumerate(reqs)
                  if not r.done or r.hits != oracle[i % len(queries)]]
    assert not mismatched, f"serving drifted from oracle: {mismatched}"
    if run.resized:
        assert engine.swaps == 1, "snapshot-swap resize did not complete"
        post = [engine.submit(q) for q in queries]
        engine.start()
        engine.close(drain=True)
        assert [r.result() for r in post] == oracle, \
            "post-swap serving must stay exact"

    lat = engine.latency_stats()
    stats = engine.engine_stats()
    evals = fleet.eval_stats()
    print(json.dumps({
        "dataset": args.dataset, "distance": config.dist.name,
        "config": config.to_dict(),
        "windows": len(data), "shards": len(workers),
        "build_s": round(build_s, 2),
        "requests": len(reqs),
        "serve_s": round(run.serve_s, 3),
        "warm_qps": round(len(reqs) / run.serve_s, 1),
        "traces_timed": run.traces_timed,
        "merged_rounds": stats["rounds"],
        "mean_rounds_per_request": lat.get("mean_rounds"),
        "swaps": stats["swaps"],
        **{k: round(v, 2) for k, v in latency_ms(engine).items()},
        "queue_p50_ms": round(1e3 * lat.get("queue_p50", 0.0), 2),
        "hits": sum(len(r.hits) for r in reqs),
        "query_evals": evals["query"],
        "build_evals": evals["build"],
    }, indent=2))


if __name__ == "__main__":
    main()
