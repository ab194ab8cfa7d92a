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
The bands read only the first and last ``nb`` columns of each series,
which the wrapper passes as small blocks of their own (``qt`` with queries
on sublanes, ``ct`` transposed with candidates on lanes), so they are
independent of how the rest of the series is blocked.
The paper's per-pair early abandon (Alg. 1 line 12) is deliberately absent:
on TPU it becomes cascade-tier compaction (see search/cascade.py), and the
bands-only tier is exposed separately via ``bands_only=True``, which reads
the band columns alone.

L blocks: the Keogh bridge over ``[nb, L - nb)`` runs on a third grid
axis over blocks of ``TL = tiling.lb_block_l(L)`` columns, the last and
reduction axis: the output block stays in VMEM across it, the first L
block writes the bands into it and every block adds its share of the
bridge.  Up to ``LB_BLOCK_L`` columns the series is one block and the
bridge a static slice, as before the axis existed; past it each block
masks its columns to the bridge (the first block drops the left band's
columns, the last the right band's and its ragged padding).

Per-candidate liveness (``live``): liveness parity with the pairwise
kernel (PR 4) for the *dense* tier — the planner (search/planner.py) can
limit-mask a cross-block tier the same way it limit-masks the packed
tiers.  ``live`` is a ``(C,)`` per-candidate mask: dead candidates emit
``-inf`` down their whole output column (the running-max identity, so a
masked dense tier folds into the cascade as a no-op on dead candidates),
and a candidate tile whose lanes are *all* dead skips the band/bridge
compute entirely via the same SMEM-flag ``pl.when`` mechanism the
pairwise and DTW tiles use.

VMEM: q (TQ, TL) + u/lo (2*TC, TL) + out (TQ, TC), double-buffered.
TQ=8, TC=128, TL=2048 -> ~4.3 MB f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import l_block_keep, lb_block_l

Array = jax.Array

_INF = float(jnp.inf)


def _bands(qt, ct, nb: int, shape, dt):
    """(TQ, TC) elastic band minima of the first and last ``nb`` columns.

    ``qt`` ``(TQ, 2nb)`` and ``ct`` ``(2nb, TC)`` hold those columns,
    ``ct`` transposed so candidates sit on lanes: index ``s < nb`` is
    column ``s``, index ``2nb-1-s`` column ``L-1-s``.  Each band cell is
    then a ``(TQ, 1)`` query column against a ``(1, TC)`` candidate row —
    a broadcast, where reading single elements of a row would be a gather
    Mosaic cannot lower.
    """
    acc = jnp.zeros(shape, dt)
    for bi in range(nb):
        # left band bi: cells (a_{bi-t}, b_bi) and (a_bi, b_{bi-t})
        m = jnp.full(shape, jnp.inf, dtype=dt)
        for t in range(bi + 1):
            d1 = qt[:, bi - t:bi - t + 1] - ct[bi:bi + 1]
            d2 = qt[:, bi:bi + 1] - ct[bi - t:bi - t + 1]
            m = jnp.minimum(m, jnp.minimum(d1 * d1, d2 * d2))
        acc = acc + m
        # right band (mirror around L-1): column L-1-s is index 2nb-1-s
        rr = 2 * nb - 1 - bi
        m = jnp.full(shape, jnp.inf, dtype=dt)
        for t in range(bi + 1):
            d1 = qt[:, rr + t:rr + t + 1] - ct[rr:rr + 1]
            d2 = qt[:, rr:rr + 1] - ct[rr + t:rr + t + 1]
            m = jnp.minimum(m, jnp.minimum(d1 * d1, d2 * d2))
        acc = acc + m
    return acc


def _block_rows(qt_ref, ct_ref, q_ref, u_ref, l_ref, out_ref, l_blk, *,
                nb: int, L: int, n_l: int, live=None):
    """Add this grid step's share of the (TQ, TC) bound block (shared by
    the live-gated and ungated kernel bodies): the bands at the first L
    block, then the block's Keogh bridge columns (none with
    ``bands_only``, where ``q_ref`` is ``None``); ``live`` masks dead
    candidate lanes to ``-inf`` at the last L block.  ``l_blk`` is the
    L-block index, read at the kernel's top level (interpret mode lowers
    ``pl.program_id`` only there), of ``n_l`` blocks."""
    shape, dt = out_ref.shape, out_ref.dtype

    def bands():
        out_ref[...] = _bands(qt_ref[...], ct_ref[...], nb, shape, dt)

    def mask():
        out_ref[...] = jnp.where(live, out_ref[...], -_INF)

    if q_ref is None:
        bands()
        if live is not None:
            mask()
        return
    pl.when(l_blk == 0)(bands)
    # --- Keogh bridge over [nb, L - nb), one query row at a time ---
    TL = q_ref.shape[1]
    keep = l_block_keep(L, TL, nb, L - nb, l_blk)
    cols = slice(nb, L - nb) if keep is None else slice(None)
    u = u_ref[:, cols]
    lo = l_ref[:, cols]

    def row(i, _):
        qb = q_ref[pl.ds(i, 1), cols]               # (1, bridge cols)
        over = jnp.maximum(qb - u, 0.0)
        under = jnp.maximum(lo - qb, 0.0)
        cell = over * over + under * under
        if keep is not None:
            cell = jnp.where(keep, cell, 0.0)
        out_ref[i, :] = out_ref[i, :] + jnp.sum(cell, axis=-1)
        return 0

    lax.fori_loop(0, shape[0], row, 0, unroll=True)
    if live is not None:
        pl.when(l_blk == n_l - 1)(mask)


def _lb_enhanced_kernel(*refs, nb: int, L: int, n_l: int,
                        bands_only: bool, gated: bool):
    """One grid step.  Operands: the band columns ``qt``/``ct``, then
    (unless ``bands_only``) the ``q``/``u``/``lo`` L blocks, then (when
    ``gated``) the ``(1, TC)`` liveness row; outputs: the bound block,
    then (when ``gated``) an SMEM flag scratch.

    Live-gated candidate tile: dead candidates emit -inf columns,
    all-dead tiles skip the band/bridge compute entirely (SMEM flag +
    ``pl.when`` — the pairwise/DTW tiles' liveness mechanism)."""
    qt_ref, ct_ref = refs[:2]
    q_ref = u_ref = l_ref = l_blk = None
    if not bands_only:
        q_ref, u_ref, l_ref = refs[2:5]
        l_blk = pl.program_id(2)
    rest = refs[2 if bands_only else 5:]
    kw = dict(nb=nb, L=L, n_l=n_l)
    if not gated:
        _block_rows(qt_ref, ct_ref, q_ref, u_ref, l_ref, rest[0], l_blk,
                    **kw)
        return
    live_ref, out_ref, flag_ref = rest
    live = live_ref[...] != 0                           # (1, TC)
    flag_ref[0] = jnp.any(live).astype(jnp.int32)

    @pl.when(flag_ref[0] == 0)
    def _dead():
        out_ref[...] = jnp.full(out_ref.shape, -_INF, out_ref.dtype)

    @pl.when(flag_ref[0] == 1)
    def _compute():
        _block_rows(qt_ref, ct_ref, q_ref, u_ref, l_ref, out_ref, l_blk,
                    live=live, **kw)


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
    TL = lb_block_l(L)
    if live is not None:
        live = jnp.broadcast_to(jnp.asarray(live), (C,)).astype(jnp.int32)
    # band columns: the queries' with queries on sublanes, the
    # candidates' transposed so candidates sit on lanes (_bands); one
    # dummy column when there are no bands keeps the blocks non-empty
    if nb:
        qt = jnp.concatenate([q[:, :nb], q[:, L - nb:]], axis=1)
        ct = jnp.concatenate([c[:, :nb], c[:, L - nb:]], axis=1).T
    else:
        qt, ct = jnp.zeros((Q, 1), q.dtype), jnp.zeros((1, C), c.dtype)
    pq, pc = (-Q) % tile_q, (-C) % tile_c
    if pq:
        qt = jnp.pad(qt, ((0, pq), (0, 0)))
        if not bands_only:
            q = jnp.pad(q, ((0, pq), (0, 0)))
    if pc:
        ct = jnp.pad(ct, ((0, 0), (0, pc)))
        if not bands_only:
            u = jnp.pad(u, ((0, pc), (0, 0)), constant_values=jnp.inf)
            lo = jnp.pad(lo, ((0, pc), (0, 0)), constant_values=-jnp.inf)
        if live is not None:
            # pad candidates are dead, so they never hold a tile's flag up
            live = jnp.pad(live, (0, pc))
    Qp, Cp = Q + pq, C + pc
    grid = (Qp // tile_q, Cp // tile_c)
    if not bands_only:
        grid += (pl.cdiv(L, TL),)
    operands = [qt, ct]
    in_specs = [
        pl.BlockSpec((tile_q, qt.shape[1]), lambda i, j, *_: (i, 0)),
        pl.BlockSpec((ct.shape[0], tile_c), lambda i, j, *_: (0, j)),
    ]
    if not bands_only:
        operands += [q, u, lo]
        in_specs += [
            pl.BlockSpec((tile_q, TL), lambda i, j, l: (i, l)),
            pl.BlockSpec((tile_c, TL), lambda i, j, l: (j, l)),
            pl.BlockSpec((tile_c, TL), lambda i, j, l: (j, l)),
        ]
    scratch = []
    if live is not None:
        operands.append(live[None, :])
        in_specs.append(pl.BlockSpec((1, tile_c), lambda i, j, *_: (0, j)))
        scratch = [pltpu.SMEM((1,), jnp.int32)]
    out = pl.pallas_call(
        functools.partial(_lb_enhanced_kernel, nb=nb, L=L,
                          n_l=pl.cdiv(L, TL), bands_only=bands_only,
                          gated=live is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_q, tile_c), lambda i, j, *_: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Qp, Cp), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)
    return out[:Q, :C]
