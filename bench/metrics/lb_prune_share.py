"""LB cascade: share of the rows the envelope tier screened that it
pruned, from ``ServeEngine.engine_stats()`` over the window."""


def read(run):
    e = run.window.engine
    return 100.0 * e["lb_pruned"] / e["lb_rows"] if e["lb_rows"] else None
