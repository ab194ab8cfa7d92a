"""Banded (Sakoe-Chiba) Dynamic Time Warping in pure JAX.

Paper Eqs. 1-2 with the warping-window constraint ``|i - j| <= w``
(SS II-A).  We minimise ``D(L, L)`` directly — squared-cost, no sqrt.

TPU adaptation (DESIGN.md SS3): the DP recurrence has an intra-row sequential
dependency (``D(i, j)`` needs ``D(i, j-1)``), so rows cannot be vectorised.
Cells on one *anti-diagonal* ``d = i + j`` depend only on diagonals ``d-1``
and ``d-2``, so we scan over the ``2L - 1`` anti-diagonals.

Band-packed layout (this is what makes work O(L*W), not O(L^2)): a cell is
addressed by its anti-diagonal ``d`` and its *diagonal offset*
``k = i - j + w`` in ``[0, 2w]``.  The state per diagonal is a dense
``Wb = 2w + 1`` vector instead of a length-``L`` one, and the recurrence is
pure shifts in ``k``:

    S_d[k] = cost(i, j) + min(S_{d-1}[k-1], S_{d-1}[k+1], S_{d-2}[k])

with ``i = (d + k - w) / 2`` (cells exist only when ``d + k - w`` is even —
half the lanes idle, which still wins for ``w << L``).  The cost gathers
``a[(d+k-w)//2]`` / ``b[(d-k+w)//2]`` — contiguous slices of the
*2x-duplicated* series ``A2[t] = a[t // 2]`` (and the flipped duplicate of
``b``), so every step is two ``dynamic_slice`` calls, no gathers.

Early abandon (PrunedDTW-style, arXiv:2102.05221): every warping path
crosses anti-diagonals ``d`` or ``d-1``, and path prefixes only grow, so
``min(S_d, S_{d-1})`` lower-bounds the final DTW.  When a ``cutoff`` is
given and that frontier minimum exceeds it, the state is poisoned to +inf
and the call returns +inf — the caller learns "distance > cutoff" without
paying for the rest of the matrix.

Row-block layout (``dtw_band_blocked``): the Pallas kernel's early-exit
grid (kernels/dtw_band.py) groups the ``2L - 1`` anti-diagonals into
``row_block_policy(L)``-sized blocks and makes abandon decisions only at
block boundaries.  Because the frontier minimum is *monotone
non-decreasing* in ``d`` (each new cell is ``cost + min`` of frontier
entries), checking at block boundaries abandons exactly the same lanes as
checking every step — the coarser granularity trades a later poison for a
much cheaper inner loop and real block skipping.  ``dtw_band_blocked`` is
the batched jnp mirror of that layout: same block boundaries, same
frontier test, so kernel and reference stay bit-comparable.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array

_INF = jnp.inf


@functools.partial(jax.jit, static_argnames=("w",))
def dtw(a: Array, b: Array, w: int | None = None, cutoff=None) -> Array:
    """``DTW_w(a, b)`` for two equal-length 1-D series (squared cost).

    Args:
      a, b: ``(L,)`` series.
      w: Sakoe-Chiba half-width; ``None`` or ``>= L`` means unconstrained.
         ``w == 0`` is the squared Euclidean distance.
      cutoff: optional scalar early-abandon threshold.  Whenever the true
        distance is strictly below ``cutoff`` the result is exact; otherwise
        the result is ``>= cutoff`` (usually +inf — the lane abandons as
        soon as the frontier minimum proves the cutoff unreachable).

    Returns:
      Scalar ``D(L, L)`` (or +inf on abandon).
    """
    L = a.shape[-1]
    if w is None or w >= L:
        w = L
    wb = min(w, L - 1)                 # |i - j| <= L - 1 always holds
    Wb = 2 * wb + 1
    dt = a.dtype
    if cutoff is None:
        cutoff = jnp.asarray(_INF, dt)
    # 2x-duplicated series, shifted so slice starts stay non-negative:
    #   a2p[wb + t] = a[t // 2]     b2p[wb + t] = b[(2L - 1 - t) // 2]
    pad_len = 2 * L + Wb + wb
    a2 = jnp.repeat(a, 2, axis=-1)
    b2f = jnp.flip(jnp.repeat(b, 2, axis=-1), axis=-1)
    a2p = jnp.zeros((pad_len,), dt).at[wb:wb + 2 * L].set(a2)
    b2p = jnp.zeros((pad_len,), dt).at[wb:wb + 2 * L].set(b2f)
    kk = jnp.arange(Wb)

    def step(carry, d):
        d1, d2 = carry                                   # S_{d-1}, S_{d-2}
        a_at = lax.dynamic_slice(a2p, (d,), (Wb,))       # a[(d + k - wb)//2]
        b_at = lax.dynamic_slice(b2p, (2 * L - 1 - d,), (Wb,))
        cost = (a_at - b_at) ** 2
        inf1 = jnp.full((1,), _INF, dt)
        dep_l = jnp.concatenate([inf1, d1[:-1]])         # S_{d-1}[k-1]
        dep_r = jnp.concatenate([d1[1:], inf1])          # S_{d-1}[k+1]
        best = jnp.minimum(jnp.minimum(dep_l, dep_r), d2)
        origin = (d == 0) & (kk == wb)
        nd = cost + jnp.where(origin, 0.0, best)
        t = d + kk - wb                                  # 2i
        s = d - kk + wb                                  # 2j
        valid = ((t & 1) == 0) & (t >= 0) & (t <= 2 * L - 2) \
            & (s >= 0) & (s <= 2 * L - 2)
        nd = jnp.where(valid, nd, _INF)
        # every path crosses diagonal d or d-1 -> frontier min is a LB
        dead = jnp.min(jnp.minimum(nd, d1)) > cutoff
        nd = jnp.where(dead, _INF, nd)
        d1 = jnp.where(dead, _INF, d1)
        return (nd, d1), None

    init = (jnp.full((Wb,), _INF, dt), jnp.full((Wb,), _INF, dt))
    (dlast, _), _ = lax.scan(step, init, jnp.arange(2 * L - 1))
    return dlast[wb]


def row_block_policy(L: int) -> int:
    """Anti-diagonals per row block for the early-exit banded sweep.

    Shared by the Pallas kernel (kernels/dtw_band.py) and the jnp reference
    (``dtw_band_blocked``) so abandon decisions land on identical block
    boundaries.  ~8 blocks per sweep, 64-step multiples: coarse enough that
    the per-block frontier reduction is amortised, fine enough that a
    poisoned tile skips most of its remaining anti-diagonals.
    """
    D = 2 * L - 1
    return min(D, max(64, -(-(D // 8) // 64) * 64))


def band_step(d, carry, a_at, b_at, kk, *, L: int, w: int):
    """One anti-diagonal of the band-packed recurrence (no abandon test).

    ``carry = (S_{d-1}, S_{d-2})`` as ``(P, Wb)`` blocks; returns
    ``(S_d, S_{d-1})``.  Shared verbatim by the Pallas kernel bodies
    (kernels/dtw_band.py) and the jnp reference below — one definition is
    what keeps kernel and oracle bit-comparable by construction.  ``kk`` is
    the per-lane diagonal-offset iota; lanes beyond ``2w`` (the kernel's
    128-multiple padding) are masked invalid.

    ``a_at``/``b_at`` are the ``(P, Wb)`` operand windows of this
    anti-diagonal: ``a2p[:, d : d + Wb]`` and ``b2p[:, 2L-1-d : ... + Wb]``
    of the packed operands.  The caller loads them — ``lax.dynamic_slice``
    here, an aligned ref load plus a lane rotate in the kernels (Mosaic
    has no value-level dynamic lane slice) — so the window *values*, and
    therefore every result, are the same on both paths.
    """
    d1, d2 = carry
    tp, Wb = d1.shape
    dt = d1.dtype
    diff = a_at - b_at
    cost = diff * diff
    inf_col = jnp.full((tp, 1), _INF, dt)
    dep_l = jnp.concatenate([inf_col, d1[:, :-1]], axis=-1)  # S_{d-1}[k-1]
    dep_r = jnp.concatenate([d1[:, 1:], inf_col], axis=-1)   # S_{d-1}[k+1]
    best = jnp.minimum(jnp.minimum(dep_l, dep_r), d2)
    origin = (d == 0) & (kk == w)
    nd = cost + jnp.where(origin, 0.0, best)
    t = d + kk - w                                       # 2i
    s = d - kk + w                                       # 2j
    valid = ((t & 1) == 0) & (t >= 0) & (t <= 2 * L - 2) \
        & (s >= 0) & (s <= 2 * L - 2) & (kk <= 2 * w)
    nd = jnp.where(valid, nd, _INF)
    return nd, d1


def _band_blocked_scan(
    a: Array,
    b: Array,
    w: int | None,
    cutoff: Array | None,
    row_block: int | None,
) -> tuple[Array, Array]:
    """Shared row-block-checked band sweep: ``((P,) values, (P,) death)``.

    The single definition of the blocked abandon schedule — the same block
    boundaries, frontier test, and poisoning the Pallas kernel's early-exit
    grid uses — consumed by both ``dtw_band_blocked`` (values) and
    ``dtw_band_death_blocks`` (liveness mirror), so the two cannot drift.
    ``death[p]`` is the index of the first row block whose boundary check
    abandoned lane ``p`` (``n_blocks - 1`` for survivors).
    """
    P, L = a.shape
    if w is None or w >= L:
        w = L
    wb = min(w, L - 1)
    Wb = 2 * wb + 1
    dt = a.dtype
    if cutoff is None:
        cutoff = jnp.full((P,), _INF, dt)
    else:
        cutoff = jnp.broadcast_to(jnp.asarray(cutoff, dt), (P,))
    cut = cutoff[:, None]
    R = row_block if row_block is not None else row_block_policy(L)
    D = 2 * L - 1
    n_blocks = -(-D // R)
    pad_len = 2 * L + Wb + wb
    a2 = jnp.repeat(a, 2, axis=-1)
    b2f = jnp.flip(jnp.repeat(b, 2, axis=-1), axis=-1)
    a2p = jnp.zeros((P, pad_len), dt).at[:, wb:wb + 2 * L].set(a2)
    b2p = jnp.zeros((P, pad_len), dt).at[:, wb:wb + 2 * L].set(b2f)
    kk = lax.broadcasted_iota(jnp.int32, (P, Wb), 1)

    def step(carry, d):
        (d1, d2), death, found = carry
        a_at = lax.dynamic_slice(a2p, (0, d), (P, Wb))
        b_at = lax.dynamic_slice(b2p, (0, 2 * L - 1 - d), (P, Wb))
        nd, d1 = band_step(d, (d1, d2), a_at, b_at, kk, L=L, w=wb)
        # abandon only at row-block boundaries (the kernel's grid layout)
        check = ((d + 1) % R == 0) | (d == D - 1)
        fmin = jnp.min(jnp.minimum(nd, d1), axis=-1, keepdims=True)
        dead = check & (fmin > cut)
        newly = dead[:, 0] & jnp.logical_not(found)
        death = jnp.where(newly, d // R, death)
        found = found | dead[:, 0]
        nd = jnp.where(dead, _INF, nd)
        d1 = jnp.where(dead, _INF, d1)
        return ((nd, d1), death, found), None

    init = (
        (jnp.full((P, Wb), _INF, dt), jnp.full((P, Wb), _INF, dt)),
        jnp.full((P,), n_blocks - 1, jnp.int32),
        jnp.zeros((P,), bool),
    )
    ((dlast, _), death, _), _ = lax.scan(step, init, jnp.arange(D))
    return dlast[:, wb], death


@functools.partial(jax.jit, static_argnames=("w", "row_block"))
def dtw_band_blocked(
    a: Array,
    b: Array,
    w: int | None = None,
    cutoff: Array | None = None,
    *,
    row_block: int | None = None,
) -> Array:
    """Batched band-packed DTW with row-block abandon checks.

    ``(P, L) x (P, L) -> (P,)`` — the pure-jnp mirror of the Pallas
    kernel's ``(pair_tile, row_block)`` early-exit grid: the frontier test
    runs only at row-block boundaries (every ``row_block`` anti-diagonals
    and at the final one), poisoning dead lanes to +inf there.  Outputs are
    identical to the per-step-checked scalar ``dtw`` (frontier minima are
    monotone), but the decision *points* match the kernel exactly, which is
    what keeps the two bit-comparable at abandon boundaries.
    """
    values, _ = _band_blocked_scan(a, b, w, cutoff, row_block)
    return values


@functools.partial(jax.jit, static_argnames=("w", "row_block"))
def dtw_band_death_blocks(
    a: Array,
    b: Array,
    w: int | None = None,
    cutoff: Array | None = None,
    *,
    row_block: int | None = None,
) -> Array:
    """(P,) index of the first row block whose boundary check abandons each
    lane (``n_blocks - 1`` for lanes that never abandon).

    The host-side mirror of the Pallas kernel's liveness schedule
    (kernels/dtw_band.py): a pair tile executes row blocks until *every*
    lane in it is dead, so a tile's last executed block is the max death
    block over its lanes.  ``tile_skip_rate`` turns these per-lane death
    blocks into the fraction of (tile, block) grid cells the early-exit
    grid skips for a given pair packing — the scheduler observability
    metric BENCH_kernels.json tracks for the bound-ordered vs unsorted
    verification schedules.
    """
    _, death = _band_blocked_scan(a, b, w, cutoff, row_block)
    return death


def tile_skip_rate(death_blocks, n_blocks: int, tile_p: int) -> float:
    """Fraction of (pair_tile, row_block) grid cells the early-exit grid
    skips, given per-lane death blocks in *packed* order.

    A tile runs blocks ``0..max(death_blocks over its lanes)`` and skips
    the rest; pad lanes (short final tile) die at block 0 like the
    kernel's -inf-cutoff padding, so they never hold a tile open.
    """
    import numpy as np

    death = np.asarray(death_blocks)
    pad = (-death.shape[0]) % tile_p
    if pad:
        death = np.concatenate([death, np.zeros(pad, death.dtype)])
    last = death.reshape(-1, tile_p).max(axis=1)
    n_tiles = last.shape[0]
    skipped = (n_blocks - 1 - last).sum()
    return float(skipped) / float(n_tiles * n_blocks)


@functools.partial(jax.jit, static_argnames=("w",))
def dtw_batch(a: Array, b: Array, w: int | None = None) -> Array:
    """Batched ``DTW_w`` over leading axes: ``(..., L) x (..., L) -> (...)``."""
    fn = dtw
    for _ in range(max(a.ndim, b.ndim) - 1):
        fn = jax.vmap(fn, in_axes=(0, 0, None))
    return fn(a, b, w)


def dtw_pairs(q: Array, c: Array, w: int | None = None) -> Array:
    """All-pairs ``DTW_w``: ``(Q, L) x (C, L) -> (Q, C)``.

    This is the expensive verification step the lower-bound cascade exists to
    avoid; the engine only calls it on cascade survivors.
    """
    per_q = jax.vmap(dtw, in_axes=(None, 0, None))     # (C,)
    return jax.vmap(per_q, in_axes=(0, None, None))(q, c, w)


def cost_matrix(a: Array, b: Array, w: int | None = None) -> Array:
    """Full DP matrix ``D`` (O(L^2) memory) — debugging / figures only."""
    L = a.shape[-1]
    if w is None or w >= L:
        w = L
    delta = (a[:, None] - b[None, :]) ** 2
    band = jnp.abs(jnp.arange(L)[:, None] - jnp.arange(L)[None, :]) <= w
    delta = jnp.where(band, delta, _INF)

    def row_step(prev_row, xs):
        drow, i = xs

        def col_step(left_val, xs2):
            dij, up, diag_ = xs2
            best = jnp.minimum(jnp.minimum(left_val, up), diag_)
            val = dij + best
            return val, val

        diag_prev = jnp.concatenate(
            [jnp.where(i == 0, 0.0, _INF)[None], prev_row[:-1]]
        )
        _, row = lax.scan(col_step, _INF, (drow, prev_row, diag_prev))
        return row, row

    init = jnp.full((L,), _INF)
    _, rows = lax.scan(row_step, init, (delta, jnp.arange(L)))
    return rows


def dtw_envelope_bound_gap(a: Array, b: Array, lb: Array, w: int | None = None) -> Array:
    """Tightness ``lb / DTW_w(a, b)`` (paper Eq. 15) for diagnostics."""
    d = dtw(a, b, w)
    return jnp.where(d > 0, lb / d, 1.0)
