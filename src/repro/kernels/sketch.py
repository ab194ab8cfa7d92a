"""Pallas TPU kernel: tier-(-1) quantised sketch bounds, Q x N in one pass.

The sketch tier (search/pipeline.py) is the first tier whose *memory
format* differs from the store's: instead of the ``(N, L)`` float32
series, each candidate contributes ``2 S`` int8 cells — the outward-
quantised per-segment means of its w-envelope (search/index.py documents
the layout and the admissibility argument).  At ``S = 16`` that is 32
bytes/candidate, so a 10M-candidate sketch store is ~320 MB and stays
VMEM/HBM-resident where the raw series cannot; the kernel streams
``(TQ, TC)`` blocks of the ``(Q, N)`` bound matrix, reading the int8
features of ``TC`` candidates and ``TQ`` query rows per block.

Scaled-units formulation: rather than dequantising the features and
carrying ``scale`` into the kernel, the host pre-divides the query
segment means by ``scale`` and folds ``scale^2`` into the per-segment
Cauchy-Schwarz weights::

    qs   = qbar / scale                       (Q, S) f32
    wseg = n_j * scale^2                      (S,)  f32
    out[q, n] = sum_j wseg[j] * max(qs[q,j] - sk_hi[n,j],
                                    sk_lo[n,j] - qs[q,j], 0)^2

so the kernel touches only the int8 features (cast to f32 in-register),
a ``(TQ, S)`` query block and the ``(S,)`` weights in SMEM.  The
jnp reference (ref.sketch_bound_ref) computes the *same* formulation, so
kernel/oracle parity is exact up to summation order.

Layout: the wrapper transposes the features to ``(S, N)``, candidates
on lanes, so segment ``j`` of a candidate tile is a ``(1, TC)`` row and
segment ``j`` of the query block a ``(TQ, 1)`` column.  The segment loop
is a static Python loop (``S <= 16``): each step is one ``(TQ, TC)``
broadcast max + multiply-accumulate, all VPU-elementwise — no per-cell
indexing and no column-to-row moves.  ``tiling.sketch_tiles`` fixes the
block (128 x 512 at most, ~256 KB of f32 output), which bounds both VMEM
and the length of the unrolled program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import sketch_tiles

Array = jax.Array


def _sketch_kernel(qs_ref, wseg_ref, lo_ref, hi_ref, out_ref, *, S: int):
    qs = qs_ref[...]                                    # (TQ, S)
    lo = lo_ref[...].astype(jnp.float32)                # (S, TC)
    hi = hi_ref[...].astype(jnp.float32)
    acc = jnp.zeros(out_ref.shape, out_ref.dtype)       # (TQ, TC)
    for j in range(S):                                  # static, S <= 16
        q = qs[:, j:j + 1]                              # (TQ, 1)
        d = jnp.maximum(
            jnp.maximum(q - hi[j:j + 1], lo[j:j + 1] - q),  # (TQ, TC)
            0.0,
        )
        acc = acc + wseg_ref[j] * d * d
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("tile_c", "interpret"))
def sketch_bound_pallas(
    qs: Array,
    sk_lo: Array,
    sk_hi: Array,
    wseg: Array,
    *,
    tile_c: int | None = None,
    interpret: bool = False,
) -> Array:
    """``(Q, S) x (N, S) int8 -> (Q, N)`` sketch bounds (scaled units).

    Inputs are the scaled-units operands (module docstring): ``qs`` the
    scale-divided query segment means, ``wseg`` the ``n_j * scale^2``
    weights.  ``ops.sketch_bound_op`` builds them from the raw index
    features; call that, not this, unless you already have them.
    """
    Q, S = qs.shape
    N = sk_lo.shape[0]
    tq, tc = sketch_tiles(Q, N)
    tc = tc if tile_c is None else tile_c
    # candidates on lanes: (S, N) features, so a segment is a (1, TC) row
    lo_t, hi_t = sk_lo.T, sk_hi.T
    # pad the candidate axis to a tile multiple with an *inverted*
    # envelope (lo=+127 > hi=-127): pad columns score a huge finite
    # bound and are sliced off below either way
    pc, pq = (-N) % tc, (-Q) % tq
    if pc:
        lo_t = jnp.pad(lo_t, ((0, 0), (0, pc)), constant_values=127)
        hi_t = jnp.pad(hi_t, ((0, 0), (0, pc)), constant_values=-127)
    if pq:
        qs = jnp.pad(qs, ((0, pq), (0, 0)))
    Qp, Np = Q + pq, N + pc
    out = pl.pallas_call(
        functools.partial(_sketch_kernel, S=S),
        grid=(Qp // tq, Np // tc),
        in_specs=[
            pl.BlockSpec((tq, S), lambda i, j: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((S, tc), lambda i, j: (0, j)),
            pl.BlockSpec((S, tc), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tq, tc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Qp, Np), jnp.float32),
        interpret=interpret,
    )(qs, jnp.asarray(wseg, jnp.float32).reshape(S), lo_t, hi_t)
    return out[:Q, :N]
