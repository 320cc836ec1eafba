"""Arithmetic on a window's round times."""

from __future__ import annotations

import math


def rounds_per_s(window_start: float, completions: list) -> float:
    """Whole rounds completed in the window over the time from the window's
    start to the completion of the last of them: all the work over all the
    time, stalls included."""
    if not completions:
        raise ValueError("no round completed in the window")
    return len(completions) / (completions[-1] - window_start)


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile, ``q`` in (0, 100]."""
    if not values:
        raise ValueError("no values")
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def iqr_share(values: list) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)`` (the driver's spread)."""
    import statistics

    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
