"""Pallas TPU kernel: fused LB_ENHANCED^V *cross blocks* (paper Eq. 14 /
Alg. 1).

The paper's contribution as a single fused kernel: for a ``(TQ, L)`` query
tile against a ``(TC, L)`` candidate tile (plus the candidates' envelopes),
each program emits the ``(TQ, TC)`` block of LB_ENHANCED^V bounds — elastic
left/right band minima *and* the Keogh bridge in one VMEM round trip.

Two kernel shapes serve LB_ENHANCED (see search/cascade.py DESIGN notes):

  * **cross-block** (this file): ``(TQ, L) x (TC, L) -> (TQ, TC)`` — every
    query row meets every candidate row.  The cascade uses it for the
    all-pairs tiers (dense tier 2 and the bands-only tier 1 prefilter),
    where the full (Q, N) bound matrix is the product.
  * **pairwise** (lb_enhanced_pairwise.py): packed ``(P, L)`` batches in,
    ``(P,)`` bounds out — row ``p`` of the query batch pairs with row
    ``p`` of the candidate batch.  The staged cascade's tier-2 refinement
    runs on *gather-compacted survivor pairs*, which is exactly this
    diagonal shape; the cross-block kernel would pay ``TQ x TC`` work for
    ``min(TQ, TC)`` answers there.

Band structure (SS III): band ``i < nb`` is L-shaped with arm width
``i + 1 <= nb`` — because ``nb = min(L/2, W, V)`` is a small compile-time
constant, the two band arms unroll into ``O(nb^2)`` static-slice vector ops
over the ``(TC,)`` lane axis: no gathers, no data-dependent control flow.
The paper's per-pair early abandon (Alg. 1 line 12) is deliberately absent:
on TPU it becomes cascade-tier compaction (see search/cascade.py), and the
bands-only tier is exposed separately via ``bands_only=True``.

Per-candidate liveness (``live``): liveness parity with the pairwise
kernel (PR 4) for the *dense* tier — the planner (search/planner.py) can
limit-mask a cross-block tier the same way it limit-masks the packed
tiers.  ``live`` is a ``(C,)`` per-candidate mask: dead candidates emit
``-inf`` down their whole output column (the running-max identity, so a
masked dense tier folds into the cascade as a no-op on dead candidates),
and a candidate tile whose lanes are *all* dead skips the band/bridge
compute entirely via the same SMEM-flag ``pl.when`` mechanism the
pairwise and DTW tiles use.

VMEM: q (TQ, L) + c/u/lo (3*TC, L) + out (TQ, TC).
TQ=8, TC=128, L=4096 -> ~6.4 MB f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_INF = float(jnp.inf)


def _block_rows(q_ref, ct_ref, u_ref, l_ref, out_ref, *, nb: int,
                bands_only: bool, live=None):
    """Write the (TQ, TC) bound block (shared by the live-gated and
    ungated kernel bodies); ``live`` masks dead candidate lanes to
    ``-inf``.

    ``ct`` holds the candidates' band columns transposed, candidates on
    lanes: rows ``0..nb-1`` are columns ``0..nb-1``, rows ``nb..2nb-1``
    columns ``L-nb..L-1``.  Each band cell is then a ``(TQ, 1)`` query
    column against a ``(1, TC)`` candidate row — a broadcast, where
    reading single elements of a row would be a gather Mosaic cannot
    lower.
    """
    q = q_ref[...]                                      # (TQ, L)
    ct = ct_ref[...]                                    # (2 nb, TC)
    tq, L = q.shape
    acc = jnp.zeros(out_ref.shape, out_ref.dtype)
    # --- elastic bands: unrolled static slices (nb is tiny) ---
    for bi in range(nb):
        # left band bi: cells (a_{bi-t}, b_bi) and (a_bi, b_{bi-t})
        m = jnp.full(acc.shape, jnp.inf, dtype=acc.dtype)
        for t in range(bi + 1):
            d1 = q[:, bi - t:bi - t + 1] - ct[bi:bi + 1]
            d2 = q[:, bi:bi + 1] - ct[bi - t:bi - t + 1]
            m = jnp.minimum(m, jnp.minimum(d1 * d1, d2 * d2))
        acc = acc + m
        # right band (mirror around L-1): column L-1-s is ct row 2nb-1-s
        ir, rr = L - 1 - bi, 2 * nb - 1 - bi
        m = jnp.full(acc.shape, jnp.inf, dtype=acc.dtype)
        for t in range(bi + 1):
            d1 = q[:, ir + t:ir + t + 1] - ct[rr:rr + 1]
            d2 = q[:, ir:ir + 1] - ct[rr + t:rr + t + 1]
            m = jnp.minimum(m, jnp.minimum(d1 * d1, d2 * d2))
        acc = acc + m
    out_ref[...] = acc
    # --- Keogh bridge over [nb, L - nb), one query row at a time ---
    if not bands_only:
        u = u_ref[:, nb:L - nb]
        lo = l_ref[:, nb:L - nb]

        def row(i, _):
            qb = q_ref[pl.ds(i, 1), nb:L - nb]              # (1, L - 2nb)
            over = jnp.maximum(qb - u, 0.0)
            under = jnp.maximum(lo - qb, 0.0)
            out_ref[i, :] = out_ref[i, :] + jnp.sum(
                over * over + under * under, axis=-1)
            return 0

        lax.fori_loop(0, tq, row, 0, unroll=True)
    if live is not None:
        out_ref[...] = jnp.where(live, out_ref[...], -_INF)


def _lb_enhanced_kernel(
    q_ref, ct_ref, u_ref, l_ref, out_ref, *, nb: int, bands_only: bool
):
    _block_rows(q_ref, ct_ref, u_ref, l_ref, out_ref, nb=nb,
                bands_only=bands_only)


def _lb_enhanced_kernel_live(
    q_ref, ct_ref, u_ref, l_ref, live_ref, out_ref, flag_ref, *, nb: int,
    bands_only: bool
):
    """Live-gated candidate tile: dead candidates emit -inf columns,
    all-dead tiles skip the band/bridge compute entirely (SMEM flag +
    ``pl.when`` — the pairwise/DTW tiles' liveness mechanism)."""
    live = live_ref[...] != 0                           # (1, TC)
    flag_ref[0] = jnp.any(live).astype(jnp.int32)
    out_ref[...] = jnp.full(out_ref.shape, -_INF, out_ref.dtype)

    @pl.when(flag_ref[0] == 1)
    def _compute():
        _block_rows(q_ref, ct_ref, u_ref, l_ref, out_ref, nb=nb,
                    bands_only=bands_only, live=live)


@functools.partial(
    jax.jit,
    static_argnames=("w", "v", "bands_only", "tile_q", "tile_c", "interpret"),
)
def lb_enhanced_pallas(
    q: Array,
    c: Array,
    u: Array,
    lo: Array,
    w: int,
    v: int,
    *,
    live: Array | None = None,
    bands_only: bool = False,
    tile_q: int = 8,
    tile_c: int = 128,
    interpret: bool = False,
) -> Array:
    """``(Q, L) x (C, L) -> (Q, C)`` fused LB_ENHANCED^V matrix.

    ``live`` (optional ``(C,)`` bool/int) marks which candidates are worth
    scoring: dead candidates return ``-inf`` for every query and
    fully-dead candidate tiles skip their compute (module docstring).
    ``None`` scores every candidate.
    """
    Q, L = q.shape
    C, _ = c.shape
    nb = max(0, min(L // 2, w, v))
    tile_q = min(tile_q, Q)
    tile_c = min(tile_c, C)
    if live is not None:
        live = jnp.broadcast_to(jnp.asarray(live), (C,)).astype(jnp.int32)
    # band columns, transposed so candidates sit on lanes (_block_rows);
    # one dummy row when there are no bands keeps the block non-empty
    ct = (jnp.concatenate([c[:, :nb], c[:, L - nb:]], axis=1).T if nb
          else jnp.zeros((1, C), c.dtype))
    pq, pc = (-Q) % tile_q, (-C) % tile_c
    if pq:
        q = jnp.pad(q, ((0, pq), (0, 0)))
    if pc:
        ct = jnp.pad(ct, ((0, 0), (0, pc)))
        u = jnp.pad(u, ((0, pc), (0, 0)), constant_values=jnp.inf)
        lo = jnp.pad(lo, ((0, pc), (0, 0)), constant_values=-jnp.inf)
        if live is not None:
            # pad candidates are dead, so they never hold a tile's flag up
            live = jnp.pad(live, (0, pc))
    Qp, Cp = Q + pq, C + pc
    grid = (Qp // tile_q, Cp // tile_c)
    out_shape = jax.ShapeDtypeStruct((Qp, Cp), q.dtype)
    in_specs = [
        pl.BlockSpec((tile_q, L), lambda i, j: (i, 0)),
        pl.BlockSpec((ct.shape[0], tile_c), lambda i, j: (0, j)),
        pl.BlockSpec((tile_c, L), lambda i, j: (j, 0)),
        pl.BlockSpec((tile_c, L), lambda i, j: (j, 0)),
    ]
    out_specs = pl.BlockSpec((tile_q, tile_c), lambda i, j: (i, j))
    if live is None:
        out = pl.pallas_call(
            functools.partial(_lb_enhanced_kernel, nb=nb,
                              bands_only=bands_only),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
        )(q, ct, u, lo)
    else:
        out = pl.pallas_call(
            functools.partial(_lb_enhanced_kernel_live, nb=nb,
                              bands_only=bands_only),
            grid=grid,
            in_specs=in_specs + [pl.BlockSpec((1, tile_c), lambda i, j: (0, j))],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
            interpret=interpret,
        )(q, ct, u, lo, live[None, :])
    return out[:Q, :C]
