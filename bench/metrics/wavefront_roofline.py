"""Wavefront kernel: the least time the chip could take for the real work
dispatched in the window, over the summed device time of the kernel's
events in the trace.  The work (operations and bytes) is counted by the
cell's request kind (``kernel_work``), which knows what its rows are.
Notes which bound binds."""

from bench import costs


def read(run):
    kernel_s = (run.trace or {}).get("kernel_s", {}).get("wavefront", 0.0)
    work = run.cell.kind.kernel_work(run) if kernel_s else None
    if not work or not work[0]:
        return None
    ops, nbytes = work
    share, bound = costs.share(ops, nbytes, kernel_s, run.device_kind)
    run.notes["wavefront_roofline"] = {
        "bound": bound, "rows": run.window.counters["rows"], "ops": ops,
        "bytes": nbytes, "kernel_s": kernel_s}
    return share
