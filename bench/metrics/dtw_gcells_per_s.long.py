"""DP cells of the verified pairs (sum of ``n_dtw`` times the L^2 cells
of one full-window DTW, ``band_cells(L, w)`` at w = L) per second of
``dtw_band_pallas`` device time, in 1e9."""

import numpy as np

from bench.metrics_util import band_cells


def read(run):
    t = None if run.trace is None else run.trace.kernel_s.get(
        "dtw_band_pallas")
    if not t:
        return None
    pairs = float(sum(np.sum(r.n_dtw) for r in run.requests
                      if r.n_dtw is not None))
    cfg = run.config
    return pairs * band_cells(cfg["length"], cfg["w"]) / t / 1e9
