"""Kernel op calls served by their jnp reference per request (the
``repro.obs`` counter ``kernel_fallbacks``, summed over kernels): 0 when
every kernel serves this deployment's shapes.  A program without the
counter reads nothing."""

from bench.program_counters import traced


def read(run):
    counts = traced(run)
    if counts is None or "kernel_fallbacks" not in counts:
        return None
    return float(sum(counts["kernel_fallbacks"].values())) / len(
        run.requests)
