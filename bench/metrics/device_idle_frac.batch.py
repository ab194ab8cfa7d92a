"""1 - device busy time (union of op intervals) / traced window."""

from bench.metrics_util import idle_frac


def read(run):
    return idle_frac(run)
