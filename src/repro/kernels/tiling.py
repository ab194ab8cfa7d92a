"""Shared VMEM tile-sizing and pair-packing policy for the pair-batched
kernels.

Every pair-batched kernel (dtw_band, lb_enhanced_pairwise) tiles the pair
axis in sublane multiples of 8 and auto-shrinks the tile so its per-pair
VMEM footprint stays inside the kernel's budget — one policy, defined
once, so a change to the floor or the rounding applies everywhere.

Pair-packing permutation: which lanes share a pair tile is a *scheduling*
decision (the engine's bound-ordered verification schedule argsorts each
round's flat batch so doomed pairs cluster into the same tiles — see
search/engine.py), but the *mechanism* lives here: gather the operand rows
by ``perm`` before the kernel, scatter the outputs back after.  Per-lane
results are independent of tile composition, so the permutation is
result-invariant by construction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def permute_pairs(perm: Array, *arrays):
    """Gather each array's pair axis (axis 0) by ``perm``; ``None`` entries
    pass through (an absent per-pair operand, e.g. a missing cutoff)."""
    return tuple(None if x is None else x[perm] for x in arrays)


def unpermute_pairs(perm: Array, out: Array) -> Array:
    """Scatter a packed kernel output back to pre-``perm`` pair order
    (the inverse gather: ``result[perm[i]] = out[i]``)."""
    return jnp.zeros_like(out).at[perm].set(out)


def apply_pair_perm(fn, perm: Array, a: Array, b: Array,
                    cutoff: Array | None) -> Array:
    """The whole perm round trip for a pair-batched call: broadcast a
    scalar cutoff to per-pair (scalars are legal without ``perm``, so they
    must stay legal with it), gather the operands, run
    ``fn(a, b, cutoff)``, scatter the output back.  One definition shared
    by the Pallas op and the jnp reference so their ``perm=`` semantics
    cannot diverge."""
    if cutoff is not None:
        cutoff = jnp.broadcast_to(jnp.asarray(cutoff, a.dtype),
                                  (a.shape[0],))
    pa, pb, pcut = permute_pairs(perm, a, b, cutoff)
    return unpermute_pairs(perm, fn(pa, pb, pcut))


# VMEM of one v5e TensorCore, what the budgets below assume where no chip
# is attached (the CPU tests, a compile for a described v5e)
_V5E_VMEM_BYTES = 128 * 2**20


def chip_vmem_bytes() -> int:
    """VMEM of one TensorCore of the default device: the chip's own
    figure on tpu (``pltpu.get_tpu_info``), a v5e's 128 MiB elsewhere."""
    if jax.default_backend() == "tpu":
        from jax.experimental.pallas import tpu as pltpu

        try:
            return int(pltpu.get_tpu_info().vmem_capacity_bytes)
        except ValueError:          # a device kind the table lacks
            pass
    return _V5E_VMEM_BYTES


def stream_vmem_limit() -> int:
    """``vmem_limit_bytes`` of the streaming DTW kernel: three quarters of
    the core's VMEM (96 MiB on a v5e), far above Mosaic's default scoped
    limit, so a full band's state (``w = L``) can stay on chip."""
    return chip_vmem_bytes() * 3 // 4


def stream_vmem_budget() -> int:
    """Working-set budget ``stream_geometry`` sizes the streaming grid to:
    a third of ``stream_vmem_limit`` (32 MiB on a v5e).  The rest of the
    limit is headroom for what the estimate leaves out: Mosaic's spills of
    the ``band_step`` temporaries past the ~8 ``Wb`` it counts."""
    return stream_vmem_limit() // 3


def pick_pair_tile(tile_p: int, P: int, per_row_bytes: int,
                   budget_bytes: int) -> int:
    """Largest pair-tile <= ``tile_p`` (multiple of 8, floor 8) whose
    ``per_row_bytes`` footprint fits ``budget_bytes``, clamped so a short
    batch is a single tile."""
    tile_p = min(tile_p, max(8, (budget_bytes // per_row_bytes) // 8 * 8))
    return min(tile_p, round_up(P, 8))


def resident_geometry(L: int, wb: int, tile_p: int, P: int,
                      budget_bytes: int, row_block: int | None = None
                      ) -> tuple[int, int, int]:
    """``(tile, R, pad_len)`` of the VMEM-resident DTW grid: the pair tile
    whose two packed operands of ``pad_len`` lanes plus ~8 ``Wb`` of band
    state fit ``budget_bytes``, the shared ``row_block_policy`` block, and
    the operand width that keeps every aligned window load
    (kernels/dtw_band.py ``_lane_window``) inside the operand."""
    from repro.core.dtw import row_block_policy

    Wb = Wb_pad(wb)
    pad_len = round_up(2 * L + Wb + max(wb, 128), 128)
    tile = pick_pair_tile(tile_p, P, (2 * pad_len + 8 * Wb) * 4,
                          budget_bytes)
    D = 2 * L - 1
    R = row_block if row_block is not None else row_block_policy(L)
    return tile, max(1, min(R, D)), pad_len


# The bound kernels (lb_keogh, lb_enhanced, lb_enhanced_pairwise) hold
# blocks of whole rows up to this many columns; a longer series is cut
# into blocks of this width along a reduction grid axis, the partial
# sums kept in the output block.  Per grid step the widest of them, the
# pairwise kernel (its tile sized for four (128, TL) f32 row blocks; it
# reads three), then holds at most 4 x 128 x 2048 x 4 B = 4 MiB, 8 MiB
# double-buffered: inside Mosaic's default scoped VMEM (16 MiB on a v5e)
# at any length.
LB_BLOCK_L = 2048


def lb_block_l(L: int) -> int:
    """Columns per L block of the bound kernels: the whole series up to
    ``LB_BLOCK_L`` (one block, the kernels' tiles unchanged), else
    ``LB_BLOCK_L`` (a 128-lane multiple), the last block ragged."""
    return L if L <= LB_BLOCK_L else LB_BLOCK_L


def l_block_keep(L: int, TL: int, lo: int, hi: int, l_blk):
    """``(1, TL)`` mask of the columns of L block ``l_blk`` (the kernel's
    ``pl.program_id`` of its L-block axis, read at the kernel's top level)
    that lie in ``[lo, hi)`` of the series, or ``None`` when the series is
    one block and static slices serve instead.  ``hi <= L`` also drops
    the ragged last block's columns past ``L``, whose values a partial
    block leaves undefined (``jnp.where``, so a NaN there stays out).
    Shared by the three L-blocked bound kernels."""
    if TL == L:
        return None
    col = l_blk * TL + jax.lax.broadcasted_iota(jnp.int32, (1, TL), 1)
    return (col >= lo) & (col < hi)


# sketch kernel output block: queries on sublanes, candidates on lanes
_SKETCH_TILE_Q = 128
_SKETCH_TILE_C = 512


def sketch_tiles(Q: int, C: int) -> tuple[int, int]:
    """``(tile_q, tile_c)`` output block of the sketch kernel
    (kernels/sketch.py): queries on sublanes (multiples of 8), candidates
    on lanes (multiples of 128), clamped so a short batch or store is a
    single tile.  The block is fixed, not budget-sized: the kernel
    unrolls its segment loop over the whole block, so a wider block only
    lengthens the program (and its compile) without saving HBM traffic.
    """
    return (min(_SKETCH_TILE_Q, round_up(Q, 8)),
            min(_SKETCH_TILE_C, round_up(C, 128)))


def sched_pair_tile(P: int, default: int = 128) -> int:
    """Pair-tile size for a *bound-ordered* verification round.

    Under the engine's ascending-bound packing the doomed tail of a round
    clusters into contiguous lanes, but the cluster boundary rarely lands
    on a tile boundary — a tile exits only when *every* lane in it is
    dead, so at the kernel's default 128-lane tiles one straggler holds
    31 doomed neighbours hostage.  Smaller tiles localise the exit to the
    cluster boundary at the cost of more grid steps; this policy scales
    the tile with the round size so big rounds (absolute cluster sizes
    grow with ``P``) keep wide tiles while typical engine rounds
    (``P = Q * verify_chunk`` ~ a few hundred) drop to 32 lanes.  Unsorted
    (``"index"``) rounds gain nothing from finer granularity and keep the
    kernel default.  Tile size is packing geometry only — per-lane DTW
    values, and therefore results and ``n_dtw``, are invariant under it.
    """
    return max(8, min(default, round_up(max(32, P // 16), 8)))


# minimum streaming row block: one anti-diagonal sweep per DMA round trip
# is all overhead, so the block never shrinks below 64 steps
_STREAM_MIN_BLOCK = 64

# Fixed per-block pipeline cost of the streaming grid, expressed in
# single-lane-width anti-diagonal sweep steps: issuing a block's two
# operand-window copies plus pipeline warm-up costs about as much as this
# many steps of band-width-128 sweep work.  Measured from the committed
# dtw_band_stream_L2048_* vs *_resident paired timings
# (benchmarks/kernel_bench.py): the pipeline overhead that put streaming
# at ~0.95x resident under the old hard-coded 1024-step floor is ~4
# block issues over 4095 steps.
_STREAM_DMA_ISSUE_STEPS = 64

# per-block fixed cost must stay under this fraction of the block's
# sweep work for the pipeline to track the resident grid within ~10%
_STREAM_OVERHEAD_FRAC = 1.0 / 16.0


def stream_pref_block(
    wb: int,
    *,
    dma_issue_steps: int = _STREAM_DMA_ISSUE_STEPS,
    overhead_frac: float = _STREAM_OVERHEAD_FRAC,
) -> int:
    """Preferred streaming row-block floor for band halfwidth ``wb``.

    Replaces the old hard-coded 1024-step floor: the block only needs to
    be large enough that the fixed per-block pipeline cost
    (``dma_issue_steps``, measured — see the constant above) stays under
    ``overhead_frac`` of the block's sweep work.  A step sweeps
    ``2 wb + 1`` band lanes, so wide bands do more work per step and
    amortise the issue cost with *smaller* blocks — narrow bands
    (``2 wb + 1 <= 128``, one VPU lane group) still get the old
    1024-step floor, which falls out of the same arithmetic.  Abandon
    boundaries moving with the floor never changes values (frontier
    minima are monotone — core/dtw.py), only how soon a dead tile stops.
    """
    work_per_step = max(1.0, (2 * wb + 1) / 128.0)
    need = dma_issue_steps / (overhead_frac * work_per_step)
    return round_up(max(_STREAM_MIN_BLOCK, int(need)), _STREAM_MIN_BLOCK)


def stream_geometry(
    L: int,
    wb: int,
    tile_p: int,
    P: int,
    budget_bytes: int,
    row_block: int | None = None,
    pref_block: int | None = None,
) -> tuple[int, int] | None:
    """Per-block working-set budget for the streaming DTW kernel.

    The streaming kernel's VMEM footprint is *per row block*, not per
    sweep: 2 double-buffer slots x 2 operand windows of
    ``stream_window(R, wb)`` lanes plus the frontier/temporary state of
    ``~8 Wb`` lanes, all times the pair tile.  Returns ``(tile, R)`` — the largest pair tile (sublane
    multiples, floor 8) and row block (64-step multiples) that fit
    ``budget_bytes`` — or ``None`` when even the minimum block at the
    sublane floor cannot fit (the band state itself exceeds VMEM; ops.py
    falls back to the jnp reference there).

    The default block is the shared ``row_block_policy`` (abandon
    boundaries match the jnp reference) floored at ``pref_block`` steps —
    by default the band-width-aware ``stream_pref_block(wb)`` policy:
    short sweeps amortise the fixed per-block pipeline cost (DMA issue +
    warm-up) poorly, so the floor is sized so that cost stays a bounded
    fraction of each block's sweep work.  Callers with their own
    measured issue cost pass ``pref_block`` explicitly.
    """
    from repro.core.dtw import row_block_policy

    D = 2 * L - 1
    pref = stream_pref_block(wb) if pref_block is None else pref_block
    R = row_block if row_block is not None else max(
        row_block_policy(L), min(pref, D))
    R = max(1, min(R, D))
    while True:
        Wwin = stream_window(R, wb)
        per_row = (4 * Wwin + 8 * Wb_pad(wb)) * 4
        tile = pick_pair_tile(tile_p, P, per_row, budget_bytes)
        if tile * per_row <= budget_bytes:
            return tile, R
        if R <= _STREAM_MIN_BLOCK:
            return None
        R = max(_STREAM_MIN_BLOCK, round_up(R // 2, _STREAM_MIN_BLOCK))


def stream_window(R: int, wb: int) -> int:
    """Lanes of one streaming operand window for row block ``R``.

    A block's sweep reads ``R + Wb`` lanes from its origin; the kernel
    rounds the origin down to the 128-lane tiling (up to 127 more lanes)
    and reads each step's band through a ``Wb + 128``-lane aligned load
    (kernels/dtw_band.py ``_lane_window``), which this width covers.
    """
    return round_up(R + 127, 128) + Wb_pad(wb)


def Wb_pad(wb: int) -> int:
    """Lane-padded band-state width ``2 wb + 1`` (128-lane multiples) —
    one definition shared by the resident/streaming kernels and the
    budget policies above."""
    return round_up(2 * wb + 1, 128)
