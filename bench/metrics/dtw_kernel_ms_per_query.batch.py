"""Device time of the banded-DTW kernel per query."""

from bench.metrics_util import kernel_ms_per_query


def read(run):
    return kernel_ms_per_query(run, ("dtw_band_pallas",))
