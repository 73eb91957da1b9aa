"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only fig8,...]

Prints ``name,us_per_call,derived`` CSV rows (see benchmarks/common.py) and
writes reports/benchmarks.json.  Compiles land in the persistent cache of
``repro.launch.compile_cache``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

MODULES = [
    ("distances", "benchmarks.bench_distances"),   # fig 4
    ("space", "benchmarks.bench_space"),           # figs 5-7
    ("build", "benchmarks.bench_build"),           # bulk construction
    ("query", "benchmarks.bench_query"),           # figs 8-11
    ("matching", "benchmarks.bench_matching"),     # fig 12 + types II/III
    ("device", "benchmarks.bench_device"),         # TPU-adapted mode
    ("elastic", "benchmarks.bench_elastic"),       # fleet serving + resize
    ("kernels", "benchmarks.bench_kernels"),       # kernel registry + packing
    ("bounds", "benchmarks.bench_bounds"),         # tiered LB cascade
    ("serve", "benchmarks.bench_serve"),           # continuous batching
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: "
                         + ",".join(k for k, _ in MODULES))
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    from repro.launch import compile_cache
    compile_cache.enable()

    import importlib
    all_rows = []
    print("name,us_per_call,derived")
    for key, modname in MODULES:
        if only and key not in only:
            continue
        t0 = time.time()
        mod = importlib.import_module(modname)
        rows = mod.run(full=args.full)
        all_rows.extend({"suite": key, **r} for r in rows)
        print(f"# {key}: {len(rows)} rows in {time.time()-t0:.1f}s")
    out = pathlib.Path(__file__).resolve().parents[1] / "reports"
    out.mkdir(exist_ok=True)
    (out / "benchmarks.json").write_text(json.dumps(all_rows, indent=2))
    print(f"# wrote {out/'benchmarks.json'} ({len(all_rows)} rows)")


if __name__ == "__main__":
    main()
