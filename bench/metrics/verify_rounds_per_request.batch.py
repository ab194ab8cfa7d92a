"""Rounds of the engine's verification loop per request (the
``repro.obs`` counter ``verify_rounds``); a round verifies up to
``batch * verify_chunk`` pairs."""

from bench.program_counters import per_request


def read(run):
    return per_request(run, "verify_rounds")
