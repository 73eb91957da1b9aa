"""Segment filter: rows the fleet rounds evaluate for one match request,
its query segments against the windows their range plans reach
(``filter_rows`` over ``match_requests`` of
``ServeEngine.engine_stats()`` in the window)."""


def read(run):
    e = run.window.engine
    n = e.get("match_requests")
    return e["filter_rows"] / n if n else None
