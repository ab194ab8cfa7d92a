"""Wall time of the span ``repro.nn_search.guards`` per request: the
guard merge and ``GuardReport.tripped()``, whose host sync waits for the
request's device work still queued."""

from bench.program_counters import span_ms_per_request


def read(run):
    return span_ms_per_request(run, "repro.nn_search.guards")
