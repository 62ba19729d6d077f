"""Percentiles of request latencies, each timed from its due time.

A request is due when the traffic's schedule says it is sent.  Timing it
from then, and not from when it was actually sent or queued, counts the wait
that a stall puts on every request behind it.  A request that never finished
counts as infinitely late.
"""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of ``values``."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    return vals[max(0, math.ceil(q / 100.0 * len(vals)) - 1)]


def latencies_from_due(due, done) -> list:
    """Per-request ``done - due`` in seconds; ``done`` is None where the
    request never finished."""
    return [math.inf if d is None else d - t for t, d in zip(due, done)]
