"""Wavefront kernel: the least time the chip could take for the real rows
dispatched in the window (``bench.costs``), over the summed device time of
the kernel's events in the trace.  Notes which bound binds."""

from bench import costs


def read(run):
    t = run.trace
    kernel_s = (t or {}).get("kernel_s", {}).get("wavefront", 0.0)
    rows = run.window.counters["rows"]
    if not kernel_s or not rows:
        return None
    data = run.cell.config["data"]
    d = 2 if data["generator"] == "trajectories" else 1
    share, bound = costs.roofline(run.cell.distance, rows, data["l"],
                                  data["l"], d, kernel_s, run.device_kind)
    run.notes["wavefront_roofline"] = {"bound": bound, "rows": rows,
                                       "kernel_s": kernel_s}
    return share
