"""What a serve returns, and the run record that metric readers see."""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Answer:
    """One request's answers, on the device until fetched."""
    idx: object            # (n, k) served neighbour ids
    dists: object          # (n, k) served distances, ascending
    n_dtw: object          # (n,) DTW verifications
    degraded: object       # scalar: > 0 when the guards served it otherwise


@dataclasses.dataclass
class Request:
    i: int                 # batch index in the traffic source
    t0: float
    t1: float
    n: int                 # queries in the request
    idx: np.ndarray        # (n, k) served neighbour ids
    dists: np.ndarray      # (n, k) served distances
    n_dtw: np.ndarray | None = None   # (n,) verifications (after window)
    failed: bool = False   # degraded, fallback or a wrong checked answer

    @property
    def latency_s(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Run:
    cell: str
    config: dict
    traffic: dict
    setup_s: float
    requests: list[Request]
    trace: "object | None" = None        # trace_reduce.Summary

    @property
    def window_s(self) -> float:
        return self.requests[-1].t1 - self.requests[0].t0

    @property
    def queries(self) -> int:
        return sum(r.n for r in self.requests)
