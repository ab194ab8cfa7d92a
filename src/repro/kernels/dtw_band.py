"""Pallas TPU kernel: banded DTW, band-packed wavefront with row-block
early exit.

This is the cascade's expensive verification step (paper Eq. 1-2 with the
Sakoe-Chiba window).  GPU DTW implementations put one *pair* per thread
block and wavefront within the matrix; the TPU-native layout is the
transpose (DESIGN.md SS3): a *batch of pairs* fills the sublanes and the DP
sweeps anti-diagonals sequentially with no data-dependent control flow.

Band-packed state (the O(L*W) layout): a DP cell is addressed by its
anti-diagonal ``d = i + j`` and diagonal offset ``k = i - j + w``; the state
per anti-diagonal is a dense ``(TP, Wb)`` block with ``Wb = 2w + 1`` rounded
up to the 128-lane multiple — *not* a ``(TP, L)`` full-width wavefront.
The recurrence is pure lane shifts:

    S_d[k] = cost(i, j) + min(S_{d-1}[k-1], S_{d-1}[k+1], S_{d-2}[k])

with ``i = (d + k - w)/2`` (cells exist only at matching parity).  The cost
operands are *contiguous* slices of the 2x-duplicated series
``A2[t] = a[t//2]`` and the flipped duplicate of ``b`` — both packed on the
host, so each anti-diagonal step is two windowed loads plus a handful of
``(TP, Wb)`` VPU ops.  Mosaic loads only at 128-aligned dynamic lane
offsets, so a window is an aligned ``Wb + 128``-lane load rotated into
place (``_lane_window``); its values are those of the jnp mirror's
``dynamic_slice``.  Pairs sit on the sublane axis everywhere, including
the ``(tile_p, 1)`` cutoff and output blocks, so any multiple-of-8 pair
tile is legal.

Row-block early exit (this file's grid): PR 1's kernel poisoned abandoned
lanes to +inf but still swept all ``2L - 1`` anti-diagonals per pair tile —
dead lanes *rode along*.  Herrmann & Webb (arXiv:2102.05221) show pruned
DTW wins come from skipping work blocks, so the grid here is
``(pair_tile, row_block)``: the anti-diagonals are grouped into
``row_block_policy(L)``-sized blocks, the DP frontier (two ``(TP, Wb)``
buffers) is *carried across grid steps in VMEM scratch*, and a scalar
liveness flag in SMEM steers each block:

  * block 0 resets the frontier and raises the flag;
  * every block runs its sweep under ``pl.when(live)`` — once the flag
    drops, remaining blocks return immediately (the whole anti-diagonal
    sweep is genuinely skipped, not masked);
  * at each block boundary the per-pair frontier minimum
    ``min(S_d, S_{d-1})`` — a valid DTW lower bound, since every warping
    path crosses anti-diagonal ``d`` or ``d-1`` and prefix costs only grow
    — is tested against the per-pair ``cutoff``; dead lanes are poisoned
    to +inf, and the flag drops when every lane in the tile is dead;
  * the last block writes the output (poisoned tiles emit +inf).

Because the frontier minimum is monotone non-decreasing in ``d``, the
block-boundary test abandons exactly the lanes the per-step test would —
outputs are identical, decisions just land on block boundaries.  The jnp
reference (core/dtw.py ``dtw_band_blocked``) shares both the per-step
recurrence (``core.dtw.band_step`` — one definition, used verbatim by the
kernel bodies below) and the block boundaries (``row_block_policy``),
keeping kernel and oracle bit-comparable by construction.
Moving the cross-lane frontier reduction out of the inner loop also
shrinks the per-step op count: the hot loop is now slices + shifts + adds
only.

``early_exit=False`` keeps PR 1's one-grid-step-per-pair-tile kernel with
per-step lane poisoning — the baseline the benchmark trajectory
(BENCH_kernels.json ``dtw_band_pr1_*`` rows) measures the early-exit grid
against.

Streaming grid (``stream=True``): the resident grid above keeps the whole
packed operands ``a2p``/``b2p`` (``~2 * TP * (2L + Wb)`` f32) in VMEM for
the entire sweep, which is why ``dtw_band_op`` runs it only up to the
residency crossover ``ops._DTW_RESIDENT_MAX_L = 16384``.  The streaming
kernel has no length ceiling: it leaves the operands in HBM
(``MemorySpace.ANY``) and turns the row-block grid into a true DMA
pipeline: row block ``j`` only ever
touches the operand windows ``a2p[:, jR : jR + R + Wb)`` and
``b2p[:, 2L - min(D, (j+1)R) : ... + R + Wb)``, so each ``(pair_tile,
row_block)`` step double-buffers those windows — block ``j + 1``'s async
copies are issued *before* block ``j``'s sweep and waited at the top of
step ``j + 1``, overlapping DMA with compute everywhere except the
warm-up block.  The DP frontier is carried in VMEM scratch exactly as in
the resident grid, and the sweep runs the same ``band_step`` recurrence
(with the window origins, rounded down to 128 lanes for the DMA, passed
as ``a_off``/``b_off``), so streaming,
resident, and the jnp ``dtw_band_blocked`` reference stay bit-comparable
by construction.  Two SMEM flags steer the pipeline: ``live`` (as in the
resident grid) and ``pending`` (a DMA pair is in flight for the current
block).  A fully-poisoned tile stops *issuing* DMAs as well as computing:
the step that kills the tile has already issued block ``j + 1``'s copies,
so the next step drains them (keeping semaphores balanced) and every
block after that is a pure no-op until the final block emits the +inf
outputs.

DMA-pipeline budget (per grid step — this is the whole point: the
working set no longer contains ``L``): 2 double-buffer slots x 2 operand
windows of ``Wwin ~ R + Wb + 128`` lanes (``tiling.stream_window``), plus
the 2-buffer frontier and ~4
``band_step`` temporaries of ``Wb`` lanes — ``(4 Wwin + ~8 Wb) * TP * 4``
bytes, independent of series length.  ``tiling.stream_geometry`` picks
the largest ``(tile_p, R)`` that fits the chip's streaming budget
(``tiling.stream_vmem_budget``, 32 MiB of a v5e's 128 MiB of VMEM;
the kernel passes the matching ``vmem_limit_bytes``, 96 MiB, since
Mosaic's default scoped limit is far below it), preferring the shared
``row_block_policy`` block so abandon boundaries match the reference and
halving ``R`` in 64-step multiples when the window is too wide.  Only
when the band state itself (``~8 Wb`` lanes at the 8-sublane floor)
exceeds the budget — ``w = L`` at ``L = 64k`` — does ops.py fall back
to the jnp reference.  The full band at L=17984 (``w = L``, Wb=35968)
fits: R=4544 and TP=16 hold ~28.8 MB, where the old 10 MB budget, at
13.8 MB for the smallest block at the sublane floor, refused it.
L=65536, w=0.01L (Wb=1408): the policy picks TP=96, R=16384 for a
batch of 1024 pairs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dtw import band_step
from repro.kernels.tiling import (
    Wb_pad,
    resident_geometry,
    round_up,
    stream_geometry,
    stream_vmem_budget,
    stream_vmem_limit,
    stream_window,
)

Array = jax.Array

_INF = float(jnp.inf)
_VMEM_BUDGET = 10 * 2**20          # resident grid: packed operands + state


def _lane_window(ref, off, width: int):
    """``ref[:, off : off + width]`` at a traced lane offset ``off``.

    Mosaic loads only at 128-aligned dynamic lane offsets, so load the
    aligned ``width + 128``-lane window that covers the slice and rotate
    the wanted lanes to its front: the same values ``lax.dynamic_slice``
    gives the jnp mirror (core/dtw.py).  The operand must hold
    ``round_down(off, 128) + width + 128`` lanes.
    """
    base = pl.multiple_of(off - lax.rem(off, 128), 128)
    span = width + 128
    win = ref[:, pl.ds(base, span)]
    return pltpu.roll(win, lax.rem(span - (off - base), span), 1)[:, :width]


def _band_sweep(a_ref, b_ref, d0, n_steps, carry, kk, *, L: int, w: int,
                Wb: int, a_off=0, b_off=0):
    """``n_steps`` anti-diagonals from ``d0`` over packed operand refs.

    ``a_off``/``b_off`` are the global columns where ``a_ref``/``b_ref``
    start (the streaming kernel's per-block windows; 0 for whole
    operands), so each step reads ``a2p[:, d : d + Wb]`` and
    ``b2p[:, 2L-1-d : ... + Wb]`` exactly as the jnp mirror slices them.
    """

    def step(t, c):
        d = d0 + t
        a_at = _lane_window(a_ref, d - a_off, Wb)
        b_at = _lane_window(b_ref, 2 * L - 1 - d - b_off, Wb)
        return band_step(d, c, a_at, b_at, kk, L=L, w=w)

    return lax.fori_loop(0, n_steps, step, carry)


def _dtw_band_kernel(a2p_ref, b2p_ref, cut_ref, out_ref, *, L: int, w: int,
                     Wb: int):
    """PR 1 baseline: one grid step per pair tile, per-step lane poisoning.

    Kept as the ``early_exit=False`` path so the benchmark trajectory can
    measure the row-block grid against it.
    """
    cut = cut_ref[...]                                   # (TP, 1)
    tp = cut.shape[0]
    dt = out_ref.dtype
    kk = lax.broadcasted_iota(jnp.int32, (tp, Wb), 1)

    def step(d, carry):
        nd, d1 = _band_sweep(a2p_ref, b2p_ref, d, 1, carry, kk, L=L, w=w,
                             Wb=Wb)
        # every path crosses diagonal d or d-1 -> frontier min is a LB
        fmin = jnp.min(jnp.minimum(nd, d1), axis=-1, keepdims=True)
        dead = fmin > cut
        nd = jnp.where(dead, _INF, nd)
        d1 = jnp.where(dead, _INF, d1)
        return nd, d1

    init = (jnp.full((tp, Wb), _INF, dt), jnp.full((tp, Wb), _INF, dt))
    dlast, _ = lax.fori_loop(0, 2 * L - 1, step, init)
    out_ref[...] = dlast[:, w:w + 1]


def _dtw_band_kernel_blocked(a2p_ref, b2p_ref, cut_ref, out_ref,
                             s1_ref, s2_ref, live_ref, *, L: int, w: int,
                             Wb: int, R: int):
    """Row-block grid step: sweep ``R`` anti-diagonals iff the tile lives.

    ``s1/s2`` carry the DP frontier across grid steps; ``live`` is the SMEM
    liveness flag that turns a fully-poisoned tile's remaining blocks into
    immediate returns.
    """
    j = pl.program_id(1)
    n_blocks = pl.num_programs(1)
    D = 2 * L - 1

    @pl.when(j == 0)
    def _reset():
        s1_ref[...] = jnp.full(s1_ref.shape, _INF, s1_ref.dtype)
        s2_ref[...] = jnp.full(s2_ref.shape, _INF, s2_ref.dtype)
        live_ref[0] = 1

    @pl.when(live_ref[0] == 1)
    def _sweep():
        cut = cut_ref[...]                               # (TP, 1)
        tp = cut.shape[0]
        kk = lax.broadcasted_iota(jnp.int32, (tp, Wb), 1)
        d0 = j * R
        n_steps = jnp.minimum(R, D - d0)                 # last block is short
        d1, d2 = _band_sweep(a2p_ref, b2p_ref, d0, n_steps,
                             (s1_ref[...], s2_ref[...]), kk, L=L, w=w, Wb=Wb)
        # block-boundary abandon: min(S_d, S_{d-1}) lower-bounds final DTW
        fmin = jnp.min(jnp.minimum(d1, d2), axis=-1, keepdims=True)
        dead = fmin > cut
        s1_ref[...] = jnp.where(dead, _INF, d1)
        s2_ref[...] = jnp.where(dead, _INF, d2)
        live_ref[0] = jnp.any(jnp.logical_not(dead)).astype(jnp.int32)

    @pl.when(j == n_blocks - 1)
    def _emit():
        out_ref[...] = s1_ref[...][:, w:w + 1]


def _dtw_band_kernel_stream(a2p_ref, b2p_ref, cut_ref, out_ref,
                            abuf, bbuf, s1_ref, s2_ref, flags_ref,
                            asem, bsem, *, L: int, w: int, Wb: int, R: int,
                            Wwin: int, TP: int):
    """Streaming row-block grid step: HBM-resident operands, double-
    buffered per-block windows, DMA overlapped with the previous block's
    sweep.

    ``flags_ref[0]`` is the liveness flag (as in the resident grid);
    ``flags_ref[1]`` records that a DMA pair for the *current* block is
    in flight, so the one copy issued before the tile died still gets
    drained and the semaphores stay balanced.
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    n_blocks = pl.num_programs(1)
    D = 2 * L - 1

    def window_origins(blk):
        # block `blk` sweeps d in [blk*R, min(D, (blk+1)*R)): band_step
        # reads a2p from column d and b2p from 2L-1-d, so its windows
        # start at these columns, rounded down to the 128-lane tiling a
        # DMA and an aligned window load need (tiling.stream_window
        # sizes Wwin to cover the rounding)
        aoff = blk * R
        boff = 2 * L - jnp.minimum(D, (blk + 1) * R)
        return (pl.multiple_of(aoff - lax.rem(aoff, 128), 128),
                pl.multiple_of(boff - lax.rem(boff, 128), 128))

    def window_dmas(blk, slot):
        aoff, boff = window_origins(blk)
        rows = pl.ds(i * TP, TP)
        da = pltpu.make_async_copy(
            a2p_ref.at[rows, pl.ds(aoff, Wwin)], abuf.at[slot],
            asem.at[slot])
        db = pltpu.make_async_copy(
            b2p_ref.at[rows, pl.ds(boff, Wwin)], bbuf.at[slot],
            bsem.at[slot])
        return da, db

    @pl.when(j == 0)
    def _reset():
        s1_ref[...] = jnp.full(s1_ref.shape, _INF, s1_ref.dtype)
        s2_ref[...] = jnp.full(s2_ref.shape, _INF, s2_ref.dtype)
        flags_ref[0] = 1
        da, db = window_dmas(0, 0)        # warm-up block: no overlap
        da.start()
        db.start()
        flags_ref[1] = 1

    @pl.when(flags_ref[1] == 1)
    def _arrive():
        # wait for the current block's windows (issued at step j-1, or by
        # the warm-up above); runs even when the tile is already dead so
        # the last issued copy is always drained exactly once
        slot = lax.rem(j, 2)
        da, db = window_dmas(j, slot)
        da.wait()
        db.wait()
        flags_ref[1] = 0

    @pl.when(flags_ref[0] == 1)
    def _sweep():
        slot = lax.rem(j, 2)

        @pl.when(j + 1 < n_blocks)
        def _prefetch():
            # issue block j+1's windows before this block's sweep so the
            # copies fly while we compute; dead tiles never reach here,
            # which is what turns the liveness exit into skipped DMA too
            da, db = window_dmas(j + 1, lax.rem(j + 1, 2))
            da.start()
            db.start()
            flags_ref[1] = 1

        cut = cut_ref[...]                               # (TP, 1)
        kk = lax.broadcasted_iota(jnp.int32, (TP, Wb), 1)
        d0 = j * R
        aoff, boff = window_origins(j)
        n_steps = jnp.minimum(R, D - d0)                 # last block is short
        d1, d2 = _band_sweep(abuf.at[slot], bbuf.at[slot], d0, n_steps,
                             (s1_ref[...], s2_ref[...]), kk, L=L, w=w, Wb=Wb,
                             a_off=aoff, b_off=boff)
        # block-boundary abandon: min(S_d, S_{d-1}) lower-bounds final DTW
        fmin = jnp.min(jnp.minimum(d1, d2), axis=-1, keepdims=True)
        dead = fmin > cut
        s1_ref[...] = jnp.where(dead, _INF, d1)
        s2_ref[...] = jnp.where(dead, _INF, d2)
        flags_ref[0] = jnp.any(jnp.logical_not(dead)).astype(jnp.int32)

    @pl.when(j == n_blocks - 1)
    def _emit():
        out_ref[...] = s1_ref[...][:, w:w + 1]


def _pack_band_operands(a: Array, b: Array, cutoff: Array | None, wb: int,
                        pad_len: int, tile_p: int):
    """Host-side band packing shared by the resident and streaming paths
    (one definition — the two grids' bit-equality depends on identical
    operand layout): pad the pair axis to the tile, build the
    2x-duplicated shifted operands ``a2p[wb + t] = a[t//2]`` /
    ``b2p[wb + t] = b[(2L-1-t)//2]``.  Pad lanes get a -inf cutoff so
    they die at the first abandon check — a +inf cutoff would keep them
    alive forever and pin the liveness flag up, disabling early exit for
    the remainder tile.  Returns ``(a2p, b2p, cutoff, Pp)``.
    """
    P, L = a.shape
    if cutoff is None:
        cutoff = jnp.full((P,), _INF, a.dtype)
    else:
        cutoff = jnp.broadcast_to(jnp.asarray(cutoff, a.dtype), (P,))
    pp = (-P) % tile_p
    if pp:
        a = jnp.pad(a, ((0, pp), (0, 0)))
        b = jnp.pad(b, ((0, pp), (0, 0)))
        cutoff = jnp.pad(cutoff, (0, pp), constant_values=-_INF)
    Pp = P + pp
    a2 = jnp.repeat(a, 2, axis=-1)
    b2f = jnp.flip(jnp.repeat(b, 2, axis=-1), axis=-1)
    zl = jnp.zeros((Pp, wb), a.dtype)
    zr = jnp.zeros((Pp, pad_len - wb - 2 * L), a.dtype)
    a2p = jnp.concatenate([zl, a2, zr], axis=-1)         # (Pp, pad_len)
    b2p = jnp.concatenate([zl, b2f, zr], axis=-1)
    return a2p, b2p, cutoff[:, None], Pp


@functools.partial(
    jax.jit,
    static_argnames=("w", "tile_p", "interpret", "early_exit", "row_block",
                     "stream"),
)
def dtw_band_pallas(
    a: Array,
    b: Array,
    w: int | None = None,
    cutoff: Array | None = None,
    *,
    tile_p: int = 128,
    interpret: bool = False,
    early_exit: bool = True,
    row_block: int | None = None,
    stream: bool = False,
) -> Array:
    """Pairwise banded DTW: ``(P, L), (P, L) -> (P,)`` squared-cost values.

    ``cutoff`` is an optional per-pair ``(P,)`` early-abandon threshold:
    pairs whose true distance is strictly below their cutoff return the
    exact value; others return ``>= cutoff`` (normally +inf).

    ``early_exit`` selects the ``(pair_tile, row_block)`` grid whose
    fully-poisoned tiles skip their remaining anti-diagonal blocks;
    ``False`` runs PR 1's single-step grid with per-step lane poisoning
    (same results, no block skipping).  ``row_block`` overrides the
    ``row_block_policy(L)`` block size (testing/benchmarks).

    ``stream`` runs the DMA-pipelined grid instead: operands stay in HBM
    and each row block double-buffers its operand windows (module
    docstring), so VMEM holds only the per-block working set and there is
    no length ceiling.  Implies the early-exit liveness behaviour; the
    caller (ops.dtw_band_op) picks this path automatically for series
    beyond the resident budget.  Raises ``ValueError`` when even the
    minimum streaming block cannot fit VMEM (band state wider than the
    budget) — ops.py routes those shapes to the jnp reference instead.
    """
    P, L = a.shape
    if w is None or w >= L:
        w = L
    wb = min(w, L - 1)                 # |i - j| <= L - 1 always holds
    Wb = Wb_pad(wb)
    if stream:
        return _dtw_band_pallas_stream(
            a, b, wb, cutoff, tile_p=tile_p, interpret=interpret,
            row_block=row_block,
        )
    # auto-shrink the pair tile so packed operands + state fit VMEM
    tile_p, R, pad_len = resident_geometry(L, wb, tile_p, P, _VMEM_BUDGET,
                                           row_block=row_block)
    a2p, b2p, cutoff, Pp = _pack_band_operands(a, b, cutoff, wb, pad_len,
                                               tile_p)
    # pairs on the sublane axis: (tile_p, 1) blocks stay legal for the
    # scheduler's sub-128 pair tiles, where a rank-1 block would not
    pair_spec = pl.BlockSpec((tile_p, 1), lambda i, *_: (i, 0))
    out_shape = jax.ShapeDtypeStruct((Pp, 1), a.dtype)
    if not early_exit:
        out = pl.pallas_call(
            functools.partial(_dtw_band_kernel, L=L, w=wb, Wb=Wb),
            grid=(Pp // tile_p,),
            in_specs=[
                pl.BlockSpec((tile_p, pad_len), lambda i: (i, 0)),
                pl.BlockSpec((tile_p, pad_len), lambda i: (i, 0)),
                pair_spec,
            ],
            out_specs=pair_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(a2p, b2p, cutoff)
        return out[:P, 0]
    n_blocks = -(-(2 * L - 1) // R)
    out = pl.pallas_call(
        functools.partial(_dtw_band_kernel_blocked, L=L, w=wb, Wb=Wb, R=R),
        grid=(Pp // tile_p, n_blocks),
        in_specs=[
            pl.BlockSpec((tile_p, pad_len), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_p, pad_len), lambda i, j: (i, 0)),
            pair_spec,
        ],
        out_specs=pair_spec,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((tile_p, Wb), a.dtype),
            pltpu.VMEM((tile_p, Wb), a.dtype),
            pltpu.SMEM((1,), jnp.int32),
        ],
        interpret=interpret,
    )(a2p, b2p, cutoff)
    return out[:P, 0]


def _dtw_band_pallas_stream(
    a: Array,
    b: Array,
    wb: int,
    cutoff: Array | None,
    *,
    tile_p: int,
    interpret: bool,
    row_block: int | None,
) -> Array:
    """Streaming path of ``dtw_band_pallas`` (already inside its jit)."""
    P, L = a.shape
    Wb = Wb_pad(wb)
    D = 2 * L - 1
    geom = stream_geometry(L, wb, tile_p, P, stream_vmem_budget(),
                           row_block=row_block)
    if geom is None:
        raise ValueError(
            f"streaming dtw_band: band state (~8 x {Wb} lanes) exceeds the "
            f"VMEM budget at the sublane floor (L={L}, w={wb}); use the "
            "jnp reference for this shape (ops.dtw_band_op does)"
        )
    tile_p, R = geom
    n_blocks = -(-D // R)
    Wwin = stream_window(R, wb)
    # the host packing must cover every block window: block j reads
    # Wwin lanes from jR and from 2L - min(D, (j+1)R), each rounded down
    # to 128 lanes
    pad_len = round_up(
        max(2 * L + Wb + wb, (n_blocks - 1) * R + Wwin,
            2 * L - min(D, R) + Wwin),
        128,
    )
    a2p, b2p, cutoff, Pp = _pack_band_operands(a, b, cutoff, wb, pad_len,
                                               tile_p)
    pair_spec = pl.BlockSpec((tile_p, 1), lambda i, j: (i, 0))
    out = pl.pallas_call(
        functools.partial(_dtw_band_kernel_stream, L=L, w=wb, Wb=Wb, R=R,
                          Wwin=Wwin, TP=tile_p),
        grid=(Pp // tile_p, n_blocks),
        in_specs=[
            # operands stay in HBM; the kernel DMAs its own windows
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pair_spec,
        ],
        out_specs=pair_spec,
        out_shape=jax.ShapeDtypeStruct((Pp, 1), a.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, tile_p, Wwin), a.dtype),      # A2 window slots
            pltpu.VMEM((2, tile_p, Wwin), a.dtype),      # B2 window slots
            pltpu.VMEM((tile_p, Wb), a.dtype),           # frontier S_{d-1}
            pltpu.VMEM((tile_p, Wb), a.dtype),           # frontier S_{d-2}
            pltpu.SMEM((2,), jnp.int32),                 # live, pending
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=stream_vmem_limit()),
        interpret=interpret,
    )(a2p, b2p, cutoff)
    return out[:P, 0]
