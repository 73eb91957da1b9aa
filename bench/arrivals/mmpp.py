"""Bursts on a Poisson schedule: a two-state modulated Poisson process
whose states follow a fixed clock.

``traffic["mmpp"]`` holds ``peak_x``, the burst's rate as a multiple of
the mean ``rate_rps``; ``burst_s``, each burst's length; and
``period_s``, the time from one burst's start to the next (the first
starts at 0).  Between bursts the rate is what keeps the mean at
``rate_rps`` over whole periods: ``rate * (period - peak_x * burst) /
(period - burst)``, so ``peak_x * burst_s <= period_s``.

The schedule is the ``poisson`` pattern's, the same count and the same
gaps from ``schedule_seed``, laid on the modulated rate's clock: each
arrival's share of the expected count is kept, and its time is where the
modulated rate's running integral reaches that share.
"""

import numpy as np

from bench import byname


def rate_at(traffic: dict, t: np.ndarray) -> np.ndarray:
    """The modulated rate at times ``t``."""
    m = traffic["mmpp"]
    rate, x = float(traffic["rate_rps"]), float(m["peak_x"])
    burst, period = float(m["burst_s"]), float(m["period_s"])
    if not (0 < burst < period and x * burst <= period):
        raise ValueError(f"mmpp needs 0 < burst_s < period_s and "
                         f"peak_x * burst_s <= period_s: {m}")
    low = rate * (period - x * burst) / (period - burst)
    return np.where(np.mod(t, period) < burst, x * rate, low)


def arrivals(traffic: dict, seconds: float) -> np.ndarray:
    plain = byname.module("arrivals", "poisson").arrivals(traffic, seconds)
    m = traffic["mmpp"]
    period, burst = float(m["period_s"]), float(m["burst_s"])
    # the rate is constant between these points, so its integral is
    # piecewise linear and np.interp inverts it exactly
    starts = np.arange(0.0, seconds, period)
    knots = np.unique(np.clip(np.r_[starts, starts + burst, seconds],
                              0.0, seconds))
    steps = rate_at(traffic, knots[:-1]) * np.diff(knots)
    area = np.r_[0.0, np.cumsum(steps)]
    return np.interp(plain / seconds * area[-1], area, knots)
