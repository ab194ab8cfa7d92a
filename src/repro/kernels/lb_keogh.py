"""Pallas TPU kernel: batched LB_KEOGH blocks (paper Eq. 7).

Computes the ``(Q, C)`` matrix of Keogh bounds between a tile of queries and
a tile of candidate envelopes.  This is the cascade's O(L) tier and the
workhorse the paper's Fig. 1 timings are dominated by.

Layout: grid ``(Q/TQ, C/TC, L/TL)``; each program holds ``q`` ``(TQ, TL)``
and the envelope blocks ``(TC, TL)`` in VMEM and loops over the TQ query
rows, adding one ``(TC,)`` row of partial bounds per iteration to the
output block, which stays in VMEM across the L-block axis (the last,
reduction axis).  ``TL = tiling.lb_block_l(L)``: the whole series up to
``LB_BLOCK_L`` columns (one L block), else blocks of that width with the
ragged last block's columns past ``L`` masked out.  The inner body is pure
clamped-difference VPU math (branch-free version of the paper's
``if A_i > U_i``).  The workload has no inner product structure, so the MXU
is idle by construction — this tier is VPU/VMEM-bandwidth-bound.

VMEM: (TQ + 2*TC) rows of TL f32, double-buffered: TQ=8, TC=128,
TL=2048 -> ~4.3 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels.tiling import l_block_keep, lb_block_l

Array = jax.Array


def _lb_keogh_kernel(q_ref, u_ref, l_ref, out_ref, *, L: int):
    u = u_ref[...]            # (TC, TL)
    lo = l_ref[...]           # (TC, TL)
    tq, TL = q_ref.shape
    l_blk = pl.program_id(2)
    keep = l_block_keep(L, TL, 0, L, l_blk)

    @pl.when(l_blk == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    def row(i, _):
        qi = q_ref[pl.ds(i, 1), :]                      # (1, TL)
        over = jnp.maximum(qi - u, 0.0)
        under = jnp.maximum(lo - qi, 0.0)
        cell = over * over + under * under
        if keep is not None:
            cell = jnp.where(keep, cell, 0.0)
        out_ref[i, :] = out_ref[i, :] + jnp.sum(cell, axis=-1)
        return 0

    lax.fori_loop(0, tq, row, 0, unroll=True)


@functools.partial(
    jax.jit, static_argnames=("tile_q", "tile_c", "interpret")
)
def lb_keogh_pallas(
    q: Array,
    u: Array,
    lo: Array,
    *,
    tile_q: int = 8,
    tile_c: int = 128,
    interpret: bool = False,
) -> Array:
    """``(Q, L) x (C, L) envelopes -> (Q, C)`` LB_KEOGH matrix."""
    Q, L = q.shape
    C, _ = u.shape
    tile_q = min(tile_q, Q)
    tile_c = min(tile_c, C)
    TL = lb_block_l(L)
    pq, pc = (-Q) % tile_q, (-C) % tile_c
    if pq:
        q = jnp.pad(q, ((0, pq), (0, 0)))
    if pc:
        # pad candidates with an infinitely-wide envelope -> bound 0
        u = jnp.pad(u, ((0, pc), (0, 0)), constant_values=jnp.inf)
        lo = jnp.pad(lo, ((0, pc), (0, 0)), constant_values=-jnp.inf)
    Qp, Cp = Q + pq, C + pc
    out = pl.pallas_call(
        functools.partial(_lb_keogh_kernel, L=L),
        grid=(Qp // tile_q, Cp // tile_c, pl.cdiv(L, TL)),
        in_specs=[
            pl.BlockSpec((tile_q, TL), lambda i, j, l: (i, l)),
            pl.BlockSpec((tile_c, TL), lambda i, j, l: (j, l)),
            pl.BlockSpec((tile_c, TL), lambda i, j, l: (j, l)),
        ],
        out_specs=pl.BlockSpec((tile_q, tile_c), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Qp, Cp), q.dtype),
        interpret=interpret,
    )(q, u, lo)
    return out[:Q, :C]
