"""Percentile and rate arithmetic shared by the drivers and the knee
sweep."""
from __future__ import annotations

import math
from typing import Iterable, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the ceil(q/100 * n)-th smallest value.
    Every sample counts, so a request that never got an answer (carried
    as its wait so far) lands in the tail."""
    if not values:
        raise ValueError("percentile of no samples")
    xs = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[min(k, len(xs)) - 1])


def rate_over_window(work: Iterable[Tuple[float, float, float]],
                     window_start: float) -> Tuple[float, float, int]:
    """Work per second over whole units begun in a window.

    ``work`` holds ``(begin, end, amount)`` per unit; the caller passes
    every unit it began before the deadline, including the one in flight
    at the deadline, which ran to its end.  The rate is the total amount
    over the time from the window's start to the end of the last unit.
    Returns ``(rate, seconds, units)``."""
    units = list(work)
    if not units:
        raise ValueError("no unit of work began in the window")
    end = max(e for _, e, _ in units)
    span = end - window_start
    if span <= 0:
        raise ValueError("the window has no length")
    return sum(a for _, _, a in units) / span, span, len(units)


__all__ = ["percentile", "rate_over_window"]
