"""Mean ``SearchResult.n_dtw`` over the store size: the share of pairs
the cascade left to DTW verification (a program counter, exact per
seed)."""

import numpy as np


def read(run):
    n_dtw = [r.n_dtw for r in run.requests if r.n_dtw is not None]
    if not n_dtw:
        return None
    return float(np.mean(np.concatenate(n_dtw))) / run.config["n_series"]
