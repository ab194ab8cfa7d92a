"""Jaxprs lowered to MLIR per request inside ``nn_search`` (the
``repro.obs`` counter ``lowerings``): a lowering on every call is a
program the engine builds anew each time."""

from bench.program_counters import per_request


def read(run):
    return per_request(run, "lowerings")
