"""Split a cell's served round by stage, from one traced run on the chip.

  python3 bench/split.py --workload traj_erp.steady --seed 7 --seconds 50

Builds the cell as ``bench/run.py`` does, serves one window of its traffic
with the profiler on, and prints one JSON line: the round's split per
``serve.tick`` from the program's own spans (``bench/progtrace.py``), the
harness's ``round_ms.lat`` beside its inside twin ``serve_tick_ms``, what
the stages leave of the tick, the share of the wavefront's device time
inside ``dispatch.launch`` and ``dispatch.fetch``, the device's idle time
by innermost host span, the window's p50, spans per tick, and the cost of
one span with the profiler off.  On a program without the spans the
split reads ``None``.  No answer is checked: ``bench/run.py`` does that.
"""

import argparse
import pathlib
import shutil
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import devtrace, harness, progtrace  # noqa: E402

#: stages that add up to ``serve_tick_ms``, less ``fleet.round``'s and
#: ``fleet.evaluate``'s own time
ADDENDS = ("engine_self_ms", "lb_screen_ms", "gather_ms", "plan_resume_ms",
           "pack_ms", "launch_ms", "fetch_ms")


def span_cost_us(n: int = 100_000) -> float:
    """Microseconds per span with two counts, the profiler off (the
    program's spans are ``jax.profiler.TraceAnnotation``s)."""
    import jax
    t = time.perf_counter()
    for _ in range(n):
        with jax.profiler.TraceAnnotation("bench.cost", rows=1) as sp:
            sp.set_metadata(padded_rows=2)
    return 1e6 * (time.perf_counter() - t) / n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"bench/split.py: {e}", file=sys.stderr)
        return 2
    harness.program()
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax
    served = harness.build(cell, args.seed)
    cost = span_cost_us()
    trace_dir = tempfile.mkdtemp(prefix="bench-split-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        window = harness.serve_window(served, cell.traffic, args.seconds,
                                      args.seed,
                                      on_close=jax.profiler.stop_trace)
        profile = devtrace.load(trace_dir)
        reduced = devtrace.reduce(profile)
        spans = progtrace.program_spans(profile)
        inside = progtrace.kernel_inside(
            profile, ("dispatch.launch", "dispatch.fetch"))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    stages = progtrace.stages(spans)
    run = harness.Run(cell, 0.0, window, reduced, devices[0].device_kind, {})
    round_ms = harness.reader("round_ms.lat")(run)
    tick = stages["serve_tick_ms"]
    parts = [stages[k] for k in ADDENDS if stages[k] is not None]
    harness.emit({
        "workload": cell.name, "seed": args.seed,
        "device": harness.device_info(devices, cell.chips),
        "stages": stages,
        "round_ms.lat": round_ms,
        "stage_sum_ms": sum(parts),
        "stage_sum_share": sum(parts) / tick if tick else None,
        "tick_over_round": tick / round_ms if tick and round_ms else None,
        "kernel_inside_launch_fetch": inside,
        "idle_s": reduced["window_s"] - reduced["busy_s"],
        "idle_gaps": reduced["idle_gaps"],
        "busy_s": reduced["busy_s"], "window_s": reduced["window_s"],
        "kernel_s": reduced["kernel_s"], "device_ops": reduced["device_ops"],
        "p50_ms": harness.percentile_ms(harness.latencies(window), 50),
        "ticks": len(window.ticks),
        "spans_per_tick": progtrace.spans_per_tick(spans),
        "span_cost_us": cost,
        "spans": spans,
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
