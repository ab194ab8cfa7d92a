"""Device time of the bound-pass kernels (sketch, LB_ENHANCED cross-block
and pairwise) per query."""

from bench.metrics_util import kernel_ms_per_query

KERNELS = ("sketch_bound_pallas", "lb_enhanced_pallas",
           "lb_enhanced_pairwise_pallas")


def read(run):
    return kernel_ms_per_query(run, KERNELS)
