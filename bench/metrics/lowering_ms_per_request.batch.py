"""Host time of jaxpr tracing and lowering per request inside
``nn_search`` (the ``repro.obs`` counter ``lowering_s``)."""

from bench.program_counters import per_request


def read(run):
    return per_request(run, "lowering_s", 1e3)
