"""``bound_kernels_ms_per_query.batch``'s reading, device time of the
bound-pass kernels per query, in the cells that report latency."""

from bench.harness import Catalog


def read(run):
    return Catalog().reader("bound_kernels_ms_per_query.batch")(run)
