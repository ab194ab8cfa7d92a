"""Wall time of the span ``repro.engine.bounds`` per request: the
cascade's eager dispatch of every tier, the compaction, the pairwise
chunk loop and the seed verification."""

from bench.program_counters import span_ms_per_request


def read(run):
    return span_ms_per_request(run, "repro.engine.bounds")
