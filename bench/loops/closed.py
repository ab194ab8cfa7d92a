"""``loop: closed``: one client; the next request goes out when the last
one's answers are on the host.  Runs until ``seconds`` have passed (at
least one request); the last request is waited for."""

from __future__ import annotations

import time
import warnings

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench.record import Request


def _ready(x):
    return jax.block_until_ready(x) if isinstance(x, jax.Array) else x


def run(serve, source, seconds: float, batch: int,
        loud: tuple[type, ...]) -> tuple[list[Request], list]:
    """The window's requests, and the loud warnings it raised."""
    reqs: list[Request] = []
    pending = []   # (request, n_dtw on device, degraded on device)
    start = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        i = 0
        while not reqs or time.perf_counter() - start < seconds:
            with TraceAnnotation("bench.prepare"):
                q = _ready(source.batch(i))
            seen = len(caught)
            with TraceAnnotation("bench.request"):
                t0 = time.perf_counter()
                with TraceAnnotation("bench.nn_search"):
                    ans = serve(q)
                with TraceAnnotation("bench.fetch"):
                    idx = np.asarray(ans.idx)
                    dists = np.asarray(ans.dists)
                t1 = time.perf_counter()
            warned = any(issubclass(w.category, loud)
                         for w in caught[seen:])
            r = Request(i=i, t0=t0, t1=t1, n=batch, idx=idx, dists=dists,
                        failed=warned)
            reqs.append(r)
            pending.append((r, ans.n_dtw, ans.degraded))
            del ans
            i += 1
        loud_caught = [w for w in caught if issubclass(w.category, loud)]
    for r, n_dtw, degraded in pending:
        r.n_dtw = np.asarray(n_dtw)
        if float(degraded) > 0:
            r.failed = True
    return reqs, loud_caught
