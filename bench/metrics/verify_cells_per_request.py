"""Verify stage: DP cells of the start-pair rows a match request sends to
the band-mode wavefront, at their real lengths (``verify_cells`` over
``match_requests`` of ``ServeEngine.engine_stats()`` in the window)."""


def read(run):
    e = run.window.engine
    n = e.get("match_requests")
    return e["verify_cells"] / n if n else None
