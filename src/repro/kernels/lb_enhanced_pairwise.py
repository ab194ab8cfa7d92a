"""Pallas TPU kernel: pairwise LB_ENHANCED^V over packed survivor batches.

The staged cascade's tier-2 refinement (search/cascade.py) gather-compacts
its survivors into *paired* ``(P, L)`` batches — row ``p`` of the query
batch goes with row ``p`` of the candidate batch — which is the transpose
of the problem the cross-block kernel (lb_enhanced.py) solves: there every
query row meets every candidate row and the output is a ``(TQ, TC)``
block.  Running the cross-block kernel on compacted survivors would pay
``TQ x TC`` work for a diagonal's worth of answers, so this kernel
specialises the *pairwise* shape instead: one ``(TP, L)`` tile of queries,
candidates and candidate envelopes in, one ``(TP,)`` vector of bounds out,
a single VMEM round trip per tile.

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

VMEM: q/c/u/lo are ``4 * TP * L`` f32 plus ``O(TP)`` accumulators.
TP=128, L=4096 -> ~8.4 MB; ``tile_p`` auto-shrinks (multiples of 8) to
stay inside ``_VMEM_BUDGET`` for longer series.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import pick_pair_tile

Array = jax.Array

_INF = float(jnp.inf)
_VMEM_BUDGET = 8 * 2**20           # bytes for the four (TP, L) operands


def _bands_and_bridge(q_ref, c_ref, u_ref, l_ref, *, nb: int,
                      bands_only: bool, dt):
    """(TP, 1) LB_ENHANCED^V accumulator for one pair tile (shared by
    the live-gated and ungated kernel bodies); pairs stay on the sublane
    axis, like the (tile_p, 1) out and live blocks."""
    q = q_ref[...]                                      # (TP, L)
    c = c_ref[...]
    L = q.shape[1]
    acc = jnp.zeros((q.shape[0], 1), dtype=dt)
    # --- elastic bands: each arm is a contiguous column slice ---
    for bi in range(nb):
        ir = L - 1 - bi
        # left band bi: cells (a_t, b_bi) and (a_bi, b_t) for t <= bi
        dl1 = q[:, :bi + 1] - c[:, bi:bi + 1]
        dl2 = q[:, bi:bi + 1] - c[:, :bi + 1]
        ml = jnp.min(jnp.minimum(dl1 * dl1, dl2 * dl2), axis=-1,
                     keepdims=True)
        # right band (mirror around L-1): columns [ir, L)
        dr1 = q[:, ir:] - c[:, ir:ir + 1]
        dr2 = q[:, ir:ir + 1] - c[:, ir:]
        mr = jnp.min(jnp.minimum(dr1 * dr1, dr2 * dr2), axis=-1,
                     keepdims=True)
        acc = acc + ml + mr
    # --- Keogh bridge over [nb, L - nb) ---
    if not bands_only:
        qb = q[:, nb:L - nb]
        over = jnp.maximum(qb - u_ref[:, nb:L - nb], 0.0)
        under = jnp.maximum(l_ref[:, nb:L - nb] - qb, 0.0)
        acc = acc + jnp.sum(over * over + under * under, axis=-1,
                            keepdims=True)
    return acc


def _lb_enhanced_pairwise_kernel(
    q_ref, c_ref, u_ref, l_ref, out_ref, *, nb: int, bands_only: bool
):
    out_ref[...] = _bands_and_bridge(
        q_ref, c_ref, u_ref, l_ref, nb=nb, bands_only=bands_only,
        dt=out_ref.dtype,
    )


def _lb_enhanced_pairwise_kernel_live(
    q_ref, c_ref, u_ref, l_ref, live_ref, out_ref, flag_ref, *, nb: int,
    bands_only: bool
):
    """Live-gated tile: dead slots emit -inf, all-dead tiles skip the
    band/bridge compute entirely (SMEM flag + ``pl.when``, the DTW tiles'
    liveness mechanism)."""
    live = live_ref[...] != 0                           # (TP, 1)
    flag_ref[0] = jnp.any(live).astype(jnp.int32)
    out_ref[...] = jnp.full(out_ref.shape, -_INF, out_ref.dtype)

    @pl.when(flag_ref[0] == 1)
    def _compute():
        acc = _bands_and_bridge(
            q_ref, c_ref, u_ref, l_ref, nb=nb, bands_only=bands_only,
            dt=out_ref.dtype,
        )
        out_ref[...] = jnp.where(live, acc, -_INF)


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
    # auto-shrink the pair tile so the four operands fit VMEM
    tile_p = pick_pair_tile(tile_p, P, 4 * L * 4, _VMEM_BUDGET)
    if live is not None:
        live = jnp.broadcast_to(jnp.asarray(live), (P,)).astype(jnp.int32)
    pp = (-P) % tile_p
    if pp:
        q = jnp.pad(q, ((0, pp), (0, 0)))
        c = jnp.pad(c, ((0, pp), (0, 0)))
        u = jnp.pad(u, ((0, pp), (0, 0)), constant_values=_INF)
        lo = jnp.pad(lo, ((0, pp), (0, 0)), constant_values=-_INF)
        if live is not None:
            # pad slots are dead, so they never hold a tile's flag up
            live = jnp.pad(live, (0, pp))
    Pp = P + pp
    # pairs on the sublane axis: (tile_p, 1) blocks, legal at any tile
    out_shape = jax.ShapeDtypeStruct((Pp, 1), q.dtype)
    row_spec = pl.BlockSpec((tile_p, L), lambda i: (i, 0))
    out_spec = pl.BlockSpec((tile_p, 1), lambda i: (i, 0))
    if live is not None:
        live = live[:, None]
    # single-tile batches drop the grid entirely: the tile is the whole
    # problem, so the grid scaffolding (index maps, per-step block
    # slicing) is pure overhead — this is what puts the kernel ahead of
    # the fused jnp path at the bench shape (P=128, L=256)
    single = Pp == tile_p
    if live is None:
        kern = functools.partial(
            _lb_enhanced_pairwise_kernel, nb=nb, bands_only=bands_only
        )
        if single:
            out = pl.pallas_call(kern, out_shape=out_shape,
                                 interpret=interpret)(q, c, u, lo)
        else:
            out = pl.pallas_call(
                kern,
                grid=(Pp // tile_p,),
                in_specs=[row_spec] * 4,
                out_specs=out_spec,
                out_shape=out_shape,
                interpret=interpret,
            )(q, c, u, lo)
    else:
        kern = functools.partial(
            _lb_enhanced_pairwise_kernel_live, nb=nb, bands_only=bands_only
        )
        scratch = [pltpu.SMEM((1,), jnp.int32)]
        if single:
            out = pl.pallas_call(
                kern, out_shape=out_shape, scratch_shapes=scratch,
                interpret=interpret,
            )(q, c, u, lo, live)
        else:
            out = pl.pallas_call(
                kern,
                grid=(Pp // tile_p,),
                in_specs=[row_spec] * 4 + [out_spec],
                out_specs=out_spec,
                out_shape=out_shape,
                scratch_shapes=scratch,
                interpret=interpret,
            )(q, c, u, lo, live)
    return out[:P, 0]
