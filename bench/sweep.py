"""Find an open-loop cell's knee on the chip: one build, rising rates.

  python3 bench/sweep.py --workload protein_lev.steady --seed 1 \
      --rates 4 6 8 11 14 18 23 30 --seconds 15

One process builds the cell's fleet once, then serves the cell's traffic
at each rate in turn for ``--seconds`` (each step on a seed of its own),
and prints one JSON line per step: the offered rate, the rate served
inside the step, the backlog (requests sent but not answered) at the
step's close, p50/p95 of due time to answer, and the rows of the
largest round (a traffic file's ``warm_rows`` covers it).  It stops
after the first step whose backlog grows: more than half a second of
arrivals left open at the close.  The knee is the highest rate before
that; the cell's traffic file freezes a share of it (PERF.md says
which, and why).
"""

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit("sweep.py finds the knee of an open-loop mix")
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"bench/sweep.py: {e}", file=sys.stderr)
        return 2
    harness.program()
    from repro.launch import compile_cache
    compile_cache.enable()
    t = time.perf_counter()
    served = harness.build(cell, args.seed)
    harness.emit({"built_s": time.perf_counter() - t,
                  "windows": len(served.data)})
    for step, rate in enumerate(args.rates):
        traffic = dict(cell.traffic, rate_rps=rate)
        w = harness.serve_window(served, traffic, args.seconds,
                                 args.seed + 1 + step, drain_s=20.0)
        answered_in = sum(s.done < w.end for s in w.answered())
        backlog = len(w.sent) - answered_in
        lat = harness.latencies(w)
        harness.emit({"rate_rps": rate, "served_rps": answered_in /
                      args.seconds, "backlog": backlog,
                      "sent": len(w.sent), "unanswered":
                      len(w.sent) - len(lat),
                      "p50_ms": harness.percentile_ms(lat, 50),
                      "p95_ms": harness.percentile_ms(lat, 95),
                      "largest_round_rows":
                      w.counters["largest_round_rows"],
                      "round_ms": 1e3 * sum(b - a for a, b in w.ticks)
                      / max(1, len(w.ticks))})
        if backlog > max(4, 0.5 * rate):
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
