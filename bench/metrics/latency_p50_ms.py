"""Median request latency over every request of the window (host clock);
a failed request counts as missing (+inf)."""

from bench.metrics_util import latency_percentile_ms


def read(run):
    return latency_percentile_ms(run, 50)
