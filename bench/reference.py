"""Plain reference for exact k-NN under banded DTW.

A straightforward brute force, independent of the search stack it checks:
it imports nothing of ``repro``.  DTW is the squared-cost Sakoe-Chiba
distance over ``|i - j| <= w`` (``w >= L`` is unconstrained, ``w = 0`` the
squared Euclidean distance), evaluated cell by cell,

    D(i, j) = (a_i - b_j)^2 + min(D(i-1, j), D(i, j-1), D(i-1, j-1)),

one anti-diagonal ``d = i + j`` per step, each diagonal held as a
length-``L`` vector indexed by ``i``.  Cells outside the band are +inf.
Every candidate of the store is scanned; nothing is pruned.

``dtype`` is the arithmetic type: float32 is the reference, and bfloat16
is the benchmark's control (the precision step below float32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@functools.partial(jax.jit, static_argnames=("w", "dtype"))
def dtw_pairs(a, b, w: int, dtype=jnp.float32):
    """Banded DTW of row ``p`` of ``a`` against row ``p`` of ``b``:
    ``(P, L) x (P, L) -> (P,)`` float32."""
    a = a.astype(dtype)
    b = b.astype(dtype)
    P, L = a.shape
    inf = jnp.asarray(jnp.inf, dtype)
    # b[d - i] for i = 0..L-1 is the slice of the reversed, padded row
    # that starts at 2L - 1 - d
    rev = jnp.concatenate(
        [jnp.zeros((P, L), dtype), b[:, ::-1], jnp.zeros((P, L), dtype)],
        axis=1)
    i = jnp.arange(L)
    inf_col = jnp.full((P, 1), inf, dtype)

    def diagonal(d, carry):
        d1, d2 = carry                        # diagonals d-1 and d-2
        j = d - i
        valid = (j >= 0) & (j < L) & (jnp.abs(i - j) <= w)
        bj = lax.dynamic_slice(rev, (0, 2 * L - 1 - d), (P, L))
        cost = (a - bj) * (a - bj)
        up = jnp.concatenate([inf_col, d1[:, :-1]], axis=1)     # (i-1, j)
        left = d1                                               # (i, j-1)
        diag = jnp.concatenate([inf_col, d2[:, :-1]], axis=1)   # (i-1, j-1)
        best = jnp.minimum(jnp.minimum(up, left), diag)
        best = jnp.where((d == 0) & (i == 0), jnp.zeros((), dtype), best)
        cur = jnp.where(valid, cost + best, inf)
        return cur, d1

    full = jnp.full((P, L), inf, dtype)
    last, _ = lax.fori_loop(0, 2 * L - 1, diagonal, (full, full))
    return last[:, L - 1].astype(jnp.float32)


def nearest(queries, store, w: int, *, k: int = 1, dtype=jnp.float32,
            max_pairs: int = 32768):
    """Brute-force k-NN of each query over the whole store.

    Returns host arrays ``(dist (S, k), idx (S, k))``, ascending by
    distance, ties to the lower index.  The work goes in calls of at most
    ``max_pairs`` pairs: blocks of queries against equal chunks of store
    rows, padded so that every call has one shape.
    """
    queries = jnp.asarray(queries, jnp.float32)
    S = queries.shape[0]
    N = store.shape[0]
    n_chunks = -(-N // max(1, min(N, max_pairs)))
    chunk = -(-N // n_chunks)
    qb = max(1, min(S, max_pairs // chunk))
    best_d = np.full((S, k), np.inf, np.float32)
    best_i = np.full((S, k), N, np.int64)
    for q0 in range(0, S, qb):
        qs = queries[q0:q0 + qb]
        nq = qs.shape[0]
        if nq < qb:
            qs = jnp.concatenate([qs, jnp.repeat(qs[:1], qb - nq, axis=0)])
        a = jnp.repeat(qs, chunk, axis=0)
        for s in range(0, N, chunk):
            rows = store[s:s + chunk]
            C = rows.shape[0]
            if C < chunk:
                rows = jnp.concatenate([rows, store[:chunk - C]])
            d = np.asarray(dtw_pairs(a, jnp.tile(rows, (qb, 1)), w, dtype))
            d = d.reshape(qb, chunk)[:nq, :C]
            sl = slice(q0, q0 + nq)
            cand_d = np.concatenate([best_d[sl], d], axis=1)
            cand_i = np.concatenate(
                [best_i[sl], np.broadcast_to(s + np.arange(C), (nq, C))],
                axis=1)
            order = np.lexsort((cand_i, cand_d), axis=-1)[:, :k]
            best_d[sl] = np.take_along_axis(cand_d, order, axis=1)
            best_i[sl] = np.take_along_axis(cand_i, order, axis=1)
    return best_d, np.where(best_i < N, best_i, -1)


def distances_to(queries, store, ids, w: int, *, dtype=jnp.float32):
    """DTW of each query to the store rows ``ids[s]`` (an answer's rows):
    ``ids`` of shape ``(S,)`` or ``(S, k)``, distances of its shape; +inf
    where an id is not a row of the store."""
    ids = np.asarray(ids)
    flat = ids.reshape(ids.shape[0], -1)
    k = flat.shape[1]
    ok = (flat >= 0) & (flat < store.shape[0])
    rows = store[jnp.asarray(np.where(ok, flat, 0).reshape(-1))]
    q = jnp.repeat(jnp.asarray(queries, jnp.float32), k, axis=0)
    d = np.asarray(dtw_pairs(q, rows, w, dtype)).reshape(flat.shape)
    return np.where(ok, d, np.inf).reshape(ids.shape)
