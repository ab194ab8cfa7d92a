"""``dtw_kernel_ms_per_query.batch``'s reading, device time of the
banded-DTW kernel per query, in the cells that report latency.  Past the
residency crossover the kernel runs its streaming grid inside the same
jitted wrapper, so the trace names it ``dtw_band_pallas`` too."""

from bench.harness import Catalog


def read(run):
    return Catalog().reader("dtw_kernel_ms_per_query.batch")(run)
