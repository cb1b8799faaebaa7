"""Arithmetic from samples to the end-to-end metrics. Pure Python.

A rate is all the work over all the time: from the first send of any
connection to the last reply of any connection (which waits for the device).
A percentile is over every call of every connection, by nearest rank on the
exact nanoseconds; never a median of chunks or of per-connection medians.
"""

from __future__ import annotations

import math


def percentile(samples, p: float) -> float:
    """Nearest-rank ``p``-th percentile (0 < p <= 100) of ``samples``."""
    if not samples:
        raise ValueError("no samples")
    s = sorted(samples)
    return s[max(0, math.ceil(p / 100.0 * len(s)) - 1)]


def window(results: list[dict]) -> tuple[float, float]:
    """``(first send, last reply)`` over the clients' results, in seconds on
    the host's monotonic clock (one clock for all processes of a host)."""
    return (min(r["t_first_send"] for r in results),
            max(r["t_last_reply"] for r in results))


def rate(work: float, results: list[dict]) -> float:
    first, last = window(results)
    if last <= first:
        raise ValueError("empty window")
    return work / (last - first)


def latencies_ms(results: list[dict]) -> list[float]:
    return [ns / 1e6 for r in results for ns in r.get("latency_ns", ())]
