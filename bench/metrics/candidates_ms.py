"""Verify stage: host time that turns a match request's (segment,
window) hits into its candidate envelope grouped by start pair (the
``match.candidates`` span's ``candidates_ns`` over ``match_requests`` of
``ServeEngine.engine_stats()`` in the window)."""


def read(run):
    e = run.window.engine
    n = e.get("match_requests")
    return e["candidates_ns"] / n / 1e6 if n else None
