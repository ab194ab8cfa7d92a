"""Pallas TPU kernel: pairwise LB_ENHANCED^V over packed survivor batches.

The staged cascade's tier-2 refinement (search/cascade.py) gather-compacts
its survivors into *paired* ``(P, L)`` batches — row ``p`` of the query
batch goes with row ``p`` of the candidate batch — which is the transpose
of the problem the cross-block kernel (lb_enhanced.py) solves: there every
query row meets every candidate row and the output is a ``(TQ, TC)``
block.  Running the cross-block kernel on compacted survivors would pay
``TQ x TC`` work for a diagonal's worth of answers, so this kernel
specialises the *pairwise* shape instead: a tile of ``TP`` packed pairs
(their queries, candidates' band columns and candidate envelopes) in,
one ``(TP,)`` vector of bounds out.

Band structure is identical to the cross-block kernel (paper SS III):
band ``i < nb`` is L-shaped with arm width ``i + 1 <= nb``, and because
``nb = min(L/2, W, V)`` is a tiny compile-time constant the two arms are
*contiguous column prefixes/suffixes*: the left band ``bi`` is the
columns ``[0, bi]`` against column ``bi`` (and its transpose), the right
band the mirror around ``L - 1``.  Each band is therefore two
``(TP, bi + 1)`` slices, an elementwise min, and a lane reduction — no
per-cell column indexing (the per-cell form emitted O(nb^2) scalar-column
ops, which is also why the kernel used to lose to the fused jnp path at
the bench shape).  Everything is elementwise in the pair axis, so the
whole tile is one batch of VPU ops.

Per-slot liveness (``live``): the global survivor budget
(search/distributed.py) allocates per-query *refine limits* over the
packed slots; slots past the limit keep their tier-0/1 bound, so
computing them is pure waste.  ``live`` threads that allocation into the
kernel as a per-slot input: dead slots emit ``-inf`` (the identity of the
caller's scatter-max), and — the point — a pair tile whose slots are
*all* dead skips the band/bridge compute entirely, via the same SMEM-flag
``pl.when`` mechanism the DTW tiles use for their liveness exit.  The
compacted packing keeps one query's slots contiguous, so light-shard
queries produce whole dead tiles and the budget allocation turns into
genuinely skipped work, not masked outputs.

L blocks: as in the cross-block kernel, the bands read only the first
and last ``nb`` columns of each row, passed as ``(P, 2nb)`` blocks of
their own, and the Keogh bridge runs on a second grid axis over blocks
of ``TL = tiling.lb_block_l(L)`` columns, the last and reduction axis:
the ``(TP, 1)`` output block stays in VMEM across it, the first L block
writes the bands and every block adds its share of the bridge.  Up to
``LB_BLOCK_L`` columns the row is one block (a static bridge slice, the
tile as before the axis existed); past it each block masks its columns
to the bridge.  ``bands_only`` reads the band columns alone.

VMEM: the q/u/lo blocks are ``3 * TP * TL`` f32, double-buffered, plus
``O(TP)`` accumulators.  TP=128, TL=2048 -> ~6.3 MB; ``tile_p``
auto-shrinks (multiples of 8) to keep ``4 * TP * TL`` f32 inside
``_VMEM_BUDGET``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import l_block_keep, lb_block_l, pick_pair_tile

Array = jax.Array

_INF = float(jnp.inf)
_VMEM_BUDGET = 8 * 2**20           # bytes for four (TP, TL) row blocks


def _bands(qt, ct, nb: int, dt):
    """(TP, 1) elastic band minima of one pair tile.  ``qt``/``ct`` are
    the ``(TP, 2nb)`` band columns: index ``s < nb`` is column ``s``,
    index ``2nb-1-s`` column ``L-1-s``; pairs stay on the sublane axis,
    like the (tile_p, 1) out and live blocks."""
    acc = jnp.zeros((qt.shape[0], 1), dtype=dt)
    for bi in range(nb):
        rr = 2 * nb - 1 - bi
        # left band bi: cells (a_t, b_bi) and (a_bi, b_t) for t <= bi
        dl1 = qt[:, :bi + 1] - ct[:, bi:bi + 1]
        dl2 = qt[:, bi:bi + 1] - ct[:, :bi + 1]
        ml = jnp.min(jnp.minimum(dl1 * dl1, dl2 * dl2), axis=-1,
                     keepdims=True)
        # right band (mirror around L-1): columns [L-1-bi, L)
        dr1 = qt[:, rr:] - ct[:, rr:rr + 1]
        dr2 = qt[:, rr:rr + 1] - ct[:, rr:]
        mr = jnp.min(jnp.minimum(dr1 * dr1, dr2 * dr2), axis=-1,
                     keepdims=True)
        acc = acc + ml + mr
    return acc


def _tile(qt_ref, ct_ref, q_ref, u_ref, l_ref, out_ref, l_blk, *, nb: int,
          L: int, n_l: int, live=None):
    """Add this grid step's share of one pair tile's (TP, 1) bounds
    (shared by the live-gated and ungated kernel bodies): the bands at
    the first L block, then the block's Keogh bridge columns (none with
    ``bands_only``, where ``q_ref`` is ``None``); ``live`` masks dead
    slots to ``-inf`` at the last L block.  ``l_blk`` is the L-block
    index, read at the kernel's top level (interpret mode lowers
    ``pl.program_id`` only there), of ``n_l`` blocks."""
    dt = out_ref.dtype

    def bands():
        out_ref[...] = _bands(qt_ref[...], ct_ref[...], nb, dt)

    def mask():
        out_ref[...] = jnp.where(live, out_ref[...], -_INF)

    if q_ref is None:
        bands()
        if live is not None:
            mask()
        return

    def bridge():
        # --- Keogh bridge over [nb, L - nb) ---
        TL = q_ref.shape[1]
        keep = l_block_keep(L, TL, nb, L - nb, l_blk)
        cols = slice(nb, L - nb) if keep is None else slice(None)
        qb = q_ref[:, cols]
        over = jnp.maximum(qb - u_ref[:, cols], 0.0)
        under = jnp.maximum(l_ref[:, cols] - qb, 0.0)
        cell = over * over + under * under
        if keep is not None:
            cell = jnp.where(keep, cell, 0.0)
        out_ref[...] = out_ref[...] + jnp.sum(cell, axis=-1, keepdims=True)

    if n_l == 1:
        bands()
        bridge()
        if live is not None:
            mask()
        return
    pl.when(l_blk == 0)(bands)
    bridge()
    if live is not None:
        pl.when(l_blk == n_l - 1)(mask)


def _lb_enhanced_pairwise_kernel(*refs, nb: int, L: int, n_l: int,
                                 bands_only: bool, gated: bool):
    """One grid step.  Operands: the band columns ``qt``/``ct``, then
    (unless ``bands_only``) the ``q``/``u``/``lo`` L blocks, then (when
    ``gated``) the ``(TP, 1)`` liveness block; outputs: the bounds, then
    (when ``gated``) an SMEM flag scratch.

    Live-gated tile: dead slots emit -inf, all-dead tiles skip the
    band/bridge compute entirely (SMEM flag + ``pl.when``, the DTW tiles'
    liveness mechanism)."""
    qt_ref, ct_ref = refs[:2]
    q_ref = u_ref = l_ref = None
    if not bands_only:
        q_ref, u_ref, l_ref = refs[2:5]
    l_blk = pl.program_id(1) if n_l > 1 else 0
    rest = refs[2 if bands_only else 5:]
    kw = dict(nb=nb, L=L, n_l=n_l)
    if not gated:
        _tile(qt_ref, ct_ref, q_ref, u_ref, l_ref, rest[0], l_blk, **kw)
        return
    live_ref, out_ref, flag_ref = rest
    live = live_ref[...] != 0                           # (TP, 1)
    flag_ref[0] = jnp.any(live).astype(jnp.int32)

    @pl.when(flag_ref[0] == 0)
    def _dead():
        out_ref[...] = jnp.full(out_ref.shape, -_INF, out_ref.dtype)

    @pl.when(flag_ref[0] == 1)
    def _compute():
        _tile(qt_ref, ct_ref, q_ref, u_ref, l_ref, out_ref, l_blk, live=live,
              **kw)


@functools.partial(
    jax.jit,
    static_argnames=("w", "v", "bands_only", "tile_p", "interpret"),
)
def lb_enhanced_pairwise_pallas(
    q: Array,
    c: Array,
    u: Array,
    lo: Array,
    w: int,
    v: int,
    *,
    live: Array | None = None,
    bands_only: bool = False,
    tile_p: int = 128,
    interpret: bool = False,
) -> Array:
    """``(P, L) x (P, L) -> (P,)`` pairwise LB_ENHANCED^V bounds.

    ``live`` (optional ``(P,)`` bool/int) marks which packed slots are
    worth refining: dead slots return ``-inf`` and fully-dead pair tiles
    skip their compute (module docstring).  ``None`` refines every slot.
    """
    P, L = q.shape
    nb = max(0, min(L // 2, w, v))
    TL = lb_block_l(L)
    n_l = pl.cdiv(L, TL)
    # auto-shrink the pair tile so four (TP, TL) blocks fit VMEM
    tile_p = pick_pair_tile(tile_p, P, 4 * TL * 4, _VMEM_BUDGET)
    if live is not None:
        live = jnp.broadcast_to(jnp.asarray(live), (P,)).astype(jnp.int32)
    # band columns of each row; one dummy column when there are no bands
    # keeps the blocks non-empty
    if nb:
        qt = jnp.concatenate([q[:, :nb], q[:, L - nb:]], axis=1)
        ct = jnp.concatenate([c[:, :nb], c[:, L - nb:]], axis=1)
    else:
        qt = ct = jnp.zeros((P, 1), q.dtype)
    pp = (-P) % tile_p
    if pp:
        pad = ((0, pp), (0, 0))
        qt, ct = jnp.pad(qt, pad), jnp.pad(ct, pad)
        if not bands_only:
            q = jnp.pad(q, pad)
            u = jnp.pad(u, pad, constant_values=_INF)
            lo = jnp.pad(lo, pad, constant_values=-_INF)
        if live is not None:
            # pad slots are dead, so they never hold a tile's flag up
            live = jnp.pad(live, (0, pp))
    Pp = P + pp
    # pairs on the sublane axis: (tile_p, 1) blocks, legal at any tile
    out_shape = jax.ShapeDtypeStruct((Pp, 1), q.dtype)
    pair_spec = pl.BlockSpec((tile_p, 1), lambda i, *_: (i, 0))
    band_spec = pl.BlockSpec((tile_p, qt.shape[1]), lambda i, *_: (i, 0))
    row_spec = pl.BlockSpec((tile_p, TL), lambda i, l: (i, l))
    operands, in_specs = [qt, ct], [band_spec, band_spec]
    if not bands_only:
        operands += [q, u, lo]
        in_specs += [row_spec] * 3
    scratch = []
    if live is not None:
        operands.append(live[:, None])
        in_specs.append(pair_spec)
        scratch = [pltpu.SMEM((1,), jnp.int32)]
    kern = functools.partial(
        _lb_enhanced_pairwise_kernel, nb=nb, L=L,
        n_l=1 if bands_only else n_l, bands_only=bands_only,
        gated=live is not None)
    # single-tile batches drop the grid entirely: the tile is the whole
    # problem, so the grid scaffolding (index maps, per-step block
    # slicing) is pure overhead — this is what puts the kernel ahead of
    # the fused jnp path at the bench shape (P=128, L=256)
    if Pp == tile_p and (bands_only or n_l == 1):
        out = pl.pallas_call(kern, out_shape=out_shape,
                             scratch_shapes=scratch,
                             interpret=interpret)(*operands)
    else:
        grid = (Pp // tile_p,) + (() if bands_only else (n_l,))
        out = pl.pallas_call(
            kern,
            grid=grid,
            in_specs=in_specs,
            out_specs=pair_spec,
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
        )(*operands)
    return out[:P, 0]
