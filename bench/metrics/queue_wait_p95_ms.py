"""Serve engine: 95th percentile of due time to the first round that
carries the request's rows (``Request.t_first_dispatch``)."""

import math

from bench.harness import percentile_ms


def read(run):
    waits = [s.req.t_first_dispatch - s.due for s in run.window.sent
             if not math.isnan(s.req.t_first_dispatch)]
    return percentile_ms(waits, 95)
