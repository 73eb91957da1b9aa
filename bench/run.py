"""Run one benchmark cell once, on the chips of this machine.

  python3 bench/run.py --workload protein_lev.steady --seed 7 \
      --seconds 30 --trace 0

Builds the cell's data and fleet from ``--seed``, warms every kernel shape
the window can reach, serves the cell's traffic through the engine its
request kind opens (``bench/kinds/``) for ``--seconds``, checks every
answer against the kind's plain reference, and prints one JSON object as
the last line of standard output: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics; with ``--trace
1`` its per-layer metrics, read from a profiler trace of the window),
``device`` and ``check`` (each compared number with its limit, also the
last lines of standard error).  Exits
non-zero, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for, and when anything was traced or compiled inside the
measured window.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    except harness.CompiledInWindow as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    for name, c in result["check"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
