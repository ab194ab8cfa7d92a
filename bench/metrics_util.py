"""Arithmetic shared by the metric readers in ``metrics/``."""

from __future__ import annotations

import math


def band_cells(length: int, w: int) -> int:
    """Cells ``(i, j)`` with ``|i - j| <= w`` in an ``L x L`` DTW matrix."""
    b = min(w, length - 1)
    return length * (2 * b + 1) - b * (b + 1)


def latency_percentile_ms(run, pct: float) -> float:
    """Nearest-rank percentile of the request latencies, failed ones as
    +inf."""
    lat = sorted(math.inf if r.failed else r.latency_s * 1e3
                 for r in run.requests)
    rank = max(1, math.ceil(pct / 100 * len(lat)))
    return lat[rank - 1]


def idle_frac(run):
    if run.trace is None:
        return None
    return 1.0 - run.trace.busy_s / run.trace.window_s


def per_query_ms(run, seconds: float) -> float:
    return seconds * 1e3 / run.queries


def kernel_ms_per_query(run, kernels) -> float | None:
    if run.trace is None:
        return None
    found = [run.trace.kernel_s[k] for k in kernels if k in run.trace.kernel_s]
    if not found:
        return None
    return per_query_ms(run, sum(found))
