"""Fleet rounds: host wall time per engine tick (admission and one merged
round), from the harness's span around each ``ServeEngine.tick``."""


def read(run):
    w = run.window
    ticks = [(a, b) for a, b in w.ticks if a < w.end]
    return 1e3 * sum(b - a for a, b in ticks) / len(ticks) if ticks \
        else None
