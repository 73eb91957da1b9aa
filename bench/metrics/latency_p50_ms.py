"""Median, over every request due in the window, of due time to answer."""

from bench.harness import latencies, percentile_ms


def read(run):
    return percentile_ms(latencies(run.window), 50)
