"""The reading rule: a rate is all the work of the measured window over
all of its time; the window is a run of intervals, each closed by a device
sync, and their readings' median and quartiles stand beside the rate.

Pure arithmetic, no JAX: the harness hands in ``(units, seconds)`` pairs,
one per device-synced interval of the measured window."""

from __future__ import annotations

import statistics


def quartiles(values: list[float]) -> tuple[float, float, float]:
    """First quartile, median, third quartile, as
    ``statistics.quantiles(values, n=4)`` gives them (the driver's rule)."""
    if len(values) == 1:
        return values[0], values[0], values[0]
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return q1, q2, q3


def summarize(readings: list[tuple[float, float]], chips: int = 1) -> dict:
    """``readings`` are ``(units, seconds)`` per interval.  The metric is
    ``total_over_window``: the units of every interval over the seconds of
    every interval, so a stall in any of them moves it.  The median and the
    quartiles of the per-interval rates say whether a difference between
    two runs is a stall inside one or the pace of the whole."""
    if not readings:
        raise ValueError("no readings: the window closed before an interval")
    rates = [units / seconds / chips for units, seconds in readings]
    q1, median, q3 = quartiles(rates)
    units = sum(u for u, _ in readings)
    seconds = sum(s for _, s in readings)
    return {
        "readings": len(rates),
        "rates": [round(rate, 1) for rate in rates],
        "q1": q1,
        "median": median,
        "q3": q3,
        "min": min(rates),
        "max": max(rates),
        "total_over_window": units / seconds / chips,
        "units": units,
        "window_s": seconds,
        "interval_s_median": statistics.median(s for _, s in readings),
    }
