"""``verified_frac.batch``'s reading, mean ``SearchResult.n_dtw`` over
the store size, in the cells that report latency: at w = L the share of
the store the bounds leave to DTW, their tightness."""

from bench.harness import Catalog


def read(run):
    return Catalog().reader("verified_frac.batch")(run)
