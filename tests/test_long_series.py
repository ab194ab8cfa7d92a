"""Long series through the served path: the bound kernels' L-block grid,
the streaming DTW grid's full band, and ``nn_search`` at w = L on both.

The L-blocked and streaming paths start at lengths the CPU cannot afford
in interpret mode (``tiling.LB_BLOCK_L`` columns, the residency crossover
``ops._DTW_RESIDENT_MAX_L``), so the end-to-end test lowers both limits
to run them at L of about 600 through the normal dispatch.
"""

import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import ops, ref, tiling
from repro.kernels.dtw_band import _VMEM_BUDGET
from repro.kernels.lb_enhanced import lb_enhanced_pallas
from repro.kernels.lb_enhanced_pairwise import lb_enhanced_pairwise_pallas
from repro.kernels.lb_keogh import lb_keogh_pallas
from repro.search import (CascadeConfig, EngineConfig, brute_force,
                          build_index, nn_search)
from repro.search import engine

BOUND_KERNELS = (lb_keogh_pallas, lb_enhanced_pallas,
                 lb_enhanced_pairwise_pallas)


def _clear_programs():
    for fn in (*BOUND_KERNELS, engine._bound_pass, engine._verify_loop):
        fn.clear_cache()


@pytest.fixture
def short_blocks(monkeypatch):
    """L blocks of 256 columns: L = 600 is three blocks, the last ragged
    (88 columns).  Programs traced under another block width go first,
    and these go after."""
    _clear_programs()
    monkeypatch.setattr(tiling, "LB_BLOCK_L", 256)
    yield 256
    _clear_programs()


def _series(rng, n, L):
    return jnp.array(rng.normal(size=(n, L)).astype(np.float32))


def _delta(before, after, name):
    return {k: after[name].get(k, 0) - before[name].get(k, 0)
            for k in set(after[name]) | set(before[name])
            if after[name].get(k, 0) != before[name].get(k, 0)}


# ---------------------------------------------------------------------------
# bound kernels over L blocks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("w", [7, 600])
@pytest.mark.parametrize("live", [False, True])
@pytest.mark.parametrize("bands_only", [False, True])
def test_lb_enhanced_l_blocked_matches_reference(rng, short_blocks, w, live,
                                                 bands_only):
    L = 600
    q, c = _series(rng, 9, L), _series(rng, 130, L)
    u, lo = ops.envelope_op(c, w)
    alive = jnp.array(rng.random(130) < 0.6) if live else None
    before = obs.snapshot()
    got = ops.lb_enhanced_op(q, c, u, lo, w, 4, live=alive,
                             bands_only=bands_only)
    grid = "resident" if bands_only else "l_blocked"
    assert _delta(before, obs.snapshot(), "bound_grid") == {grid: 1}
    want = ref.lb_enhanced_ref(q, c, u, lo, w, 4, live=alive,
                               bands_only=bands_only)
    np.testing.assert_allclose(np.array(got), np.array(want), rtol=1e-5,
                               atol=1e-4)


@pytest.mark.parametrize("w", [7, 600])
@pytest.mark.parametrize("live", [False, True])
@pytest.mark.parametrize("P", [9, 130])
def test_lb_enhanced_pairwise_l_blocked_matches_reference(
        rng, short_blocks, w, live, P):
    L = 600
    q, c = _series(rng, P, L), _series(rng, P, L)
    u, lo = ops.envelope_op(c, w)
    alive = jnp.array(rng.random(P) < 0.6) if live else None
    got = ops.lb_enhanced_pairwise_op(q, c, u, lo, w, 4, live=alive)
    want = ref.lb_enhanced_pairwise_ref(q, c, u, lo, w, 4, live=alive)
    np.testing.assert_allclose(np.array(got), np.array(want), rtol=1e-5,
                               atol=1e-4)


@pytest.mark.parametrize("w", [7, 600])
def test_lb_keogh_l_blocked_matches_reference(rng, short_blocks, w):
    L = 600
    q, c = _series(rng, 5, L), _series(rng, 140, L)
    u, lo = ops.envelope_op(c, w)
    np.testing.assert_allclose(np.array(ops.lb_keogh_op(q, u, lo)),
                               np.array(ref.lb_keogh_ref(q, u, lo)),
                               rtol=1e-5, atol=1e-4)


def test_bound_kernels_block_at_the_real_width(rng):
    """At the shipped block width a series of 4500 columns is three L
    blocks (2048, 2048, 404) and every kernel still matches."""
    L, w = 4500, 4500
    assert tiling.lb_block_l(L) == tiling.LB_BLOCK_L == 2048
    q, c = _series(rng, 3, L), _series(rng, 20, L)
    u, lo = ops.envelope_op(c, w)
    np.testing.assert_allclose(np.array(ops.lb_keogh_op(q, u, lo)),
                               np.array(ref.lb_keogh_ref(q, u, lo)),
                               rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(
        np.array(ops.lb_enhanced_op(q, c, u, lo, w, 4)),
        np.array(ref.lb_enhanced_ref(q, c, u, lo, w, 4)), rtol=1e-5,
        atol=1e-3)
    qp = jnp.repeat(q, 20, axis=0)
    cp, up, lp = (jnp.tile(x, (3, 1)) for x in (c, u, lo))
    np.testing.assert_allclose(
        np.array(ops.lb_enhanced_pairwise_op(qp, cp, up, lp, w, 4)),
        np.array(ref.lb_enhanced_pairwise_ref(qp, cp, up, lp, w, 4)),
        rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("L", [96, 512, 2048])
def test_short_series_stay_one_block(L):
    """The benchmark's lengths (96, 512) and anything up to the block
    width keep one L block, so the kernels' tiles are those of before the
    L-block axis: the pairwise tile is still sized from whole rows."""
    assert tiling.lb_block_l(L) == L
    assert tiling.pick_pair_tile(128, 4096, 4 * tiling.lb_block_l(L) * 4,
                                 8 * 2**20) == min(128, 2**21 // L // 8 * 8)


# ---------------------------------------------------------------------------
# DTW geometry: the full band streams, the cells' shapes do not move
# ---------------------------------------------------------------------------

def test_full_band_streams_at_eigenworms_length():
    """w = L at L = 17984: the old 10 MiB budget refused even the
    smallest block at the sublane floor; the chip's budget fits it."""
    L = 17984
    assert tiling.stream_geometry(L, L - 1, 32, 32, _VMEM_BUDGET) is None
    geom = tiling.stream_geometry(L, L - 1, 32, 32,
                                  tiling.stream_vmem_budget())
    assert geom is not None
    tile, R = geom
    Wwin = tiling.stream_window(R, L - 1)
    per_row = (4 * Wwin + 8 * tiling.Wb_pad(L - 1)) * 4
    assert tile * per_row <= tiling.stream_vmem_budget()
    assert tiling.stream_vmem_budget() < tiling.stream_vmem_limit() \
        <= tiling.chip_vmem_bytes()


# (L, w, requested tile, pairs) -> (resident (tile, R), stream (tile, R)),
# as these shapes chose them before the streaming budget came from the
# chip: rw/neardup rounds and seeds at L=512, the online cell at L=96
CELL_GEOMETRY = {
    (512, 154, 64, 1024): ((64, 128), (64, 448)),
    (512, 154, 128, 1024): ((128, 128), (128, 448)),
    (512, 154, 128, 32): ((32, 128), (32, 448)),
    (512, 154, 32, 32): ((32, 128), (32, 448)),
    (96, 10, 32, 32): ((32, 64), (32, 191)),
    (96, 10, 128, 1): ((8, 64), (8, 191)),
}


@pytest.mark.parametrize("shape", sorted(CELL_GEOMETRY))
def test_cells_geometry_unchanged(shape):
    L, w, tp, P = shape
    resident, stream = CELL_GEOMETRY[shape]
    tile, R, _ = tiling.resident_geometry(L, w, tp, P, _VMEM_BUDGET)
    assert (tile, R) == resident
    assert tiling.stream_geometry(L, w, tp, P,
                                  tiling.stream_vmem_budget()) == stream


# ---------------------------------------------------------------------------
# nn_search at w = L through the L-blocked bounds and the streaming DTW
# ---------------------------------------------------------------------------

def test_nn_search_full_window_long_paths_exact(rng, short_blocks,
                                                monkeypatch):
    monkeypatch.setattr(ops, "_DTW_RESIDENT_MAX_L", 256)
    N, L, k = 40, 600, 2
    walks = np.cumsum(rng.normal(size=(N + 3, L)), axis=1)
    walks = (walks - walks.mean(1, keepdims=True)) / walks.std(
        1, keepdims=True)
    store = jnp.array(walks[:N].astype(np.float32))
    q = jnp.array(walks[N:].astype(np.float32))
    cfg = EngineConfig(
        cascade=CascadeConfig(w=L, v=4, use_sketch=True, candidate_chunk=32),
        verify_chunk=8, k=k, auto_plan=True)
    with warnings.catch_warnings():
        warnings.simplefilter("error", ops.KernelFallbackWarning)
        index = build_index(store, L, calibrate=cfg, sketch=8)
        before = obs.snapshot()
        res, guard = nn_search(index, q, cfg, with_guards=True)
        after = obs.snapshot()
    assert not guard.tripped()
    assert _delta(before, after, "kernel_fallbacks") == {}
    assert _delta(before, after, "bound_grid").get("l_blocked", 0) > 0
    assert _delta(before, after, "verify_grid").get("stream", 0) > 0
    assert "resident" not in _delta(before, after, "verify_grid")
    bd, bi = brute_force(index, q, L, k, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(res.idx), np.asarray(bi))
    np.testing.assert_allclose(np.asarray(res.dists), np.asarray(bd),
                               rtol=1e-6)


def test_fallbacks_are_counted_by_kernel(rng, monkeypatch):
    monkeypatch.setattr(ops, "_ENVELOPE_MAX_L", 8)
    before = obs.snapshot()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", ops.KernelFallbackWarning)
        for _ in range(2):
            ops.envelope_op(_series(rng, 2, 16), 3)
    assert _delta(before, obs.snapshot(), "kernel_fallbacks") == \
        {"envelope": 2}
