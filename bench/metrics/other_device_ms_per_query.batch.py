"""Device-busy time in no ``*_pallas`` kernel (argsort, gathers, operand
packing and the other XLA ops) per query."""

from bench.metrics_util import per_query_ms


def read(run):
    return None if run.trace is None else per_query_ms(run, run.trace.other_s)
