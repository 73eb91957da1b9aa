"""Readings that a cell's ``correct`` limits are set from, on the chip.

  python3 bench/calibrate.py --workload traj_erp.steady \
      --seeds 101 102 103 --seconds 30

For each seed, in this one process: the cell's set-up and window exactly
as ``bench/run.py`` makes them, then the compared numbers read twice from
the same requests: once of the program's answers, once of the control's
(the configuration's ``control``: ``bf16``, the reference computed in
bfloat16; ``open_ball``, the reference with ``d < eps`` in place of
``d <= eps``).  One JSON line per seed.  The limits in the configuration
lie between the largest program reading and the smallest control
reading; ``PERF.md`` records both.
"""

import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"bench/calibrate.py: {e}", file=sys.stderr)
        return 2
    harness.program()
    from repro.launch import compile_cache
    compile_cache.enable()
    harness._count_traces()
    for seed in args.seeds:
        t = time.perf_counter()
        served = harness.build(cell, seed)
        setup_s = time.perf_counter() - t
        w = harness.serve_window(served, cell.traffic, args.seconds, seed)
        served.fleet = None
        t = time.perf_counter()
        prog = harness.check(cell, served, w)
        check_s = time.perf_counter() - t
        ctrl = harness.check(cell, served, w, control=True)
        lat = harness.latencies(w)
        harness.emit({
            "seed": seed, "correct": prog["correct"],
            "program": prog["read"], "control": ctrl["read"],
            "control_correct": ctrl["correct"], "limits":
            cell.config["check"], "requests": len(w.sent),
            "build_and_warm_s": setup_s, "check_s": check_s,
            "p50_ms": harness.percentile_ms(lat, 50),
            "p95_ms": harness.percentile_ms(lat, 95),
            "served_qps": sum(s.done < w.end for s in w.answered())
            / w.seconds, "counters": w.counters})
    return 0


if __name__ == "__main__":
    sys.exit(main())
