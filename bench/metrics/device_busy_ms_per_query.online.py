"""Device-busy time of the traced window per query; request latency
minus this is the host's share."""

from bench.metrics_util import per_query_ms


def read(run):
    return None if run.trace is None else per_query_ms(run, run.trace.busy_s)
