"""Chip smoke: drive the served path once on one TPU chip and check it.

  python chip_smoke.py [--seed 0]

Every phase runs in this one process, which owns the chip, with
``kernel_backend="pallas"`` and interpret mode left to the platform (so
the Pallas wavefront runs compiled).  Data is generated from ``--seed``.

* **A. serve (the main path).**  Synthetic proteins under Levenshtein:
  65,536 windows of l = 20, about 1.3 M residues (one bacterial proteome,
  E. coli K-12 scale).  A 4-shard fleet on the one chip, built through
  ``Retriever.build`` (its build dispatches run the packed kernel), then
  64 open-loop requests at eps 2.0 through ``fleet.serve`` after an untimed
  warmup (``repro.launch.serve.serve_open_loop``).  Every answer must equal
  a brute-force numpy scan, and no kernel may compile in the timed window.
* **B. float distance with the device LB tier.**  Synthetic 2-D
  trajectories under ERP, 16,384 windows, ``lb_cascade="envelope"``, 4
  shards; one batch of 32 range queries must equal a brute-force scan.
* **C. the paper's type I query.**  A ``lam``-set matcher (batched
  execution) over synthetic protein sequences; its range hits must equal
  the same config on the numpy host loop.
* **Kernel parity.**  One multi-band shape per wavefront mode: compiled
  Pallas against the ``lax.scan`` twin on the chip (hits identical,
  distances within ``rtol=1e-5``).

Afterwards every entry of the kernel registry's jit cache must have run
compiled (``interpret=False``), and every wavefront entry through Pallas.

Each phase prints one JSON line; the last line of standard output is
``{"ok": true, "device": {...}}``.  A failed check raises.  With no TPU,
the script exits non-zero before any phase and prints no result.  No path
spans chips yet (fleet shards are logical slices of one device), so there
is no four-chip option.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.data import synthetic  # noqa: E402
from repro.distances import np_backend  # noqa: E402
from repro.kernels import registry  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch import serve as serve_cli  # noqa: E402
from repro.retrieval import RetrievalConfig, Retriever  # noqa: E402

WAVEFRONT_NAMES = ("dtw", "erp", "frechet", "levenshtein")


def brute_force_hits(dist_name: str, data: np.ndarray, queries,
                     eps: float, chunk: int = 1 << 16) -> list:
    """Sorted ids of the windows within ``eps`` of each query: a numpy scan
    over every window, independent of the index and kernels under test."""
    batch = np_backend.batch_for(dist_name)
    out = []
    for q in queries:
        ds = np.concatenate([
            batch(np.repeat(q[None], len(data[s:s + chunk]), 0),
                  data[s:s + chunk])
            for s in range(0, len(data), chunk)])
        out.append(np.flatnonzero(ds <= eps).tolist())
    return out


def _traces() -> int:
    return registry.STATS["traces"]


def phase_serve(seed: int, n_windows: int = 65536, n_requests: int = 64,
                eps: float = 2.0, shards: int = 4, qps: float = 64.0) -> dict:
    """Phase A: build a protein fleet and serve open-loop requests."""
    t0 = _traces()
    data = synthetic.proteins(n_windows, seed=seed)
    cfg = RetrievalConfig("levenshtein", execution="fleet", workers=shards,
                          kernel_backend="pallas", tight_bounds=True)
    start = time.perf_counter()
    fleet = Retriever.build(cfg, data)
    build_s = time.perf_counter() - start
    queries = serve_cli.make_queries(data, n_requests,
                                     np.random.default_rng(seed + 1))
    run = serve_cli.serve_open_loop(fleet, queries, eps, qps)
    want = brute_force_hits("levenshtein", data, queries, eps)
    mismatches = sum(not r.done or r.hits != w
                     for r, w in zip(run.requests, want))
    out = {"phase": "A_serve", "distance": "levenshtein",
           "windows": n_windows, "residues": int(data.size),
           "shards": shards, "requests": len(run.requests), "eps": eps,
           "build_s": build_s, "serve_s": run.serve_s,
           **serve_cli.latency_ms(run.engine),
           "hits": sum(len(r.hits) for r in run.requests),
           "mismatches": mismatches, "traces_timed": run.traces_timed,
           "compiles": _traces() - t0}
    assert len(run.requests) == n_requests, out
    assert mismatches == 0, out
    assert run.traces_timed == 0, out
    return out


def phase_float_lb(seed: int, n_windows: int = 16384, n_queries: int = 32,
                   eps: float = 4.0, shards: int = 4) -> dict:
    """Phase B: ERP trajectories behind the envelope LB tier."""
    t0 = _traces()
    data = synthetic.trajectories(n_windows, seed=seed)
    cfg = RetrievalConfig("erp", execution="fleet", workers=shards,
                          kernel_backend="pallas", tight_bounds=True,
                          lb_cascade="envelope")
    start = time.perf_counter()
    r = Retriever.build(cfg, data)
    build_s = time.perf_counter() - start
    queries = serve_cli.make_queries(data, n_queries,
                                     np.random.default_rng(seed + 2))
    start = time.perf_counter()
    got = r.batch(queries).range(eps)
    query_s = time.perf_counter() - start
    want = brute_force_hits("erp", data, queries, eps)
    mismatches = sum(g != w for g, w in zip(got.hits, want))
    out = {"phase": "B_float_lb", "distance": "erp", "d": 2,
           "windows": n_windows, "shards": shards, "queries": n_queries,
           "eps": eps, "build_s": build_s, "query_s": query_s,
           "hits": sum(map(len, got.hits)),
           "lb_pruned": r.elastic().device_stats["lb_pruned"],
           "mismatches": mismatches, "compiles": _traces() - t0}
    assert mismatches == 0, out
    return out


def phase_matcher(seed: int, n_seqs: int = 64, length: int = 400,
                  lam: int = 16, eps: float = 2.0) -> dict:
    """Phase C: the paper's type I query through the matching pipeline."""
    t0 = _traces()
    seqs = synthetic.protein_sequences(n_seqs, length=length, seed=seed)
    rng = np.random.default_rng(seed + 3)
    # a random query holding a lightly mutated 40-residue stretch of one
    # database sequence
    Q = rng.integers(0, 20, size=(80,)).astype(np.int32)
    Q[20:60] = seqs[n_seqs // 2][100:140]
    Q[31] = (Q[31] + 1) % 20
    cfg = RetrievalConfig("levenshtein", lam=lam, lambda0=1, index="refnet",
                          tight_bounds=True, num_max=5, execution="batched",
                          kernel_backend="pallas")
    start = time.perf_counter()
    got = Retriever.build(cfg, seqs).query(Q).range(eps)
    device_s = time.perf_counter() - start
    host = Retriever.build(
        cfg.replace(execution="host", kernel_backend=None, backend="numpy"),
        seqs).query(Q).range(eps)

    def key(hits):
        return sorted(p.key() + (p.distance,) for p in hits)

    mismatches = len(set(key(got.hits)) ^ set(key(host.hits)))
    out = {"phase": "C_matcher", "distance": "levenshtein", "lam": lam,
           "sequences": n_seqs, "residues": n_seqs * length, "eps": eps,
           "build_and_query_s": device_s, "hits": len(got.hits),
           "host_hits": len(host.hits), "mismatches": mismatches,
           "compiles": _traces() - t0}
    assert got.hits and mismatches == 0, out
    return out


def phase_kernel_parity(seed: int, B: int = 256, L: int = 64,
                        tile: int = 16) -> dict:
    """Compiled Pallas against the ``lax.scan`` twin, one multi-band shape
    per wavefront mode.  Jitted here, outside the registry's cache, so the
    cache check below sees only what the phases served."""
    import jax
    rng = np.random.default_rng(seed + 4)
    lx = rng.integers(L // 2, L + 1, B)
    ly = rng.integers(L // 2, L + 1, B)
    per_mode = {}
    for name in WAVEFRONT_NAMES:
        if name == "levenshtein":
            xs = rng.integers(0, 20, size=(B, L)).astype(np.int32)
            ys = rng.integers(0, 20, size=(B, L)).astype(np.int32)
        else:
            xs = rng.normal(size=(B, L, 2)).astype(np.float32)
            ys = rng.normal(size=(B, L, 2)).astype(np.float32)
        spec = registry.get(name)

        def run(exec_mode, eps):
            fn = jax.jit(lambda *a: spec.device_call(
                *a, exec=exec_mode, tile=tile if exec_mode == "pallas"
                else None))
            return [np.asarray(v) for v in fn(xs, ys, lx, ly, eps)]

        full = run("scan", np.full(B, np.inf, np.float32))[0]
        eps = np.full(B, np.median(full), np.float32)
        pal, ref = run("pallas", eps), run("scan", eps)
        hit_mismatch = int((pal[1] != ref[1]).sum())
        np.testing.assert_allclose(pal[0], ref[0], rtol=1e-5,
                                   err_msg=f"{name} pallas vs scan")
        per_mode[name] = {"hits": int(pal[1].sum()),
                          "hit_mismatches": hit_mismatch}
        assert hit_mismatch == 0, (name, per_mode[name])
    return {"phase": "kernel_parity", "batch": B, "L": L, "tile": tile,
            "bands": -(-2 * L // tile), "modes": per_mode}


def check_jit_cache() -> dict:
    """Every shape class the phases compiled ran compiled, and every
    wavefront one through Pallas."""
    keys = registry.cache_keys()
    interpreted = [k for k in keys if k.interpret]
    not_pallas = [k for k in keys if registry.get(k.name).kind
                  == "wavefront" and k.exec != "pallas"]
    out = {"phase": "jit_cache", "entries": len(keys),
           "wavefront_entries": sum(registry.get(k.name).kind == "wavefront"
                                    for k in keys),
           "interpreted": len(interpreted), "not_pallas": len(not_pallas)}
    assert keys and not interpreted and not not_pallas, out
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    compile_cache.enable()

    for phase in (phase_serve, phase_float_lb, phase_matcher):
        print(json.dumps(phase(args.seed)), flush=True)
    print(json.dumps(check_jit_cache()), flush=True)
    print(json.dumps(phase_kernel_parity(args.seed)), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
