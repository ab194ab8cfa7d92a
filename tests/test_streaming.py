"""Streaming DMA-pipeline DTW: parity, geometry budget, tile threading.

Parity bar: the streaming grid runs the same ``band_step`` recurrence on
the same ``row_block_policy`` boundaries as the resident grid — only the
memory movement differs — so streaming and resident kernels must be
**bit-equal in every configuration** (windows, cutoffs, odd lengths, tile
padding, dead tiles).  Against the jnp ``dtw_band_blocked`` reference the
assertion is float-exact up to XLA re-fusion: the shared recurrence can
be contracted differently across compilation contexts (the *resident*
kernel shows the same occasional 1-ulp drift vs the ref), so vs-ref
checks use ``rtol=1e-6`` — far below any semantic difference.

The exhaustive w in {0, 1, L/4, L} x cutoff x odd-length cross product
runs at small L with the streaming path *forced* (the grids are
length-independent, so small-L coverage exercises every code path);
lengths straddling the old 16384 ceiling run the cheap windows only —
w = L/4 at L = 32k is a ~16k-lane band state swept 65k times, beyond
what interpret mode can pay per test.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dtw import dtw_band_blocked, row_block_policy
from repro.kernels import ops, ref
from repro.kernels.dtw_band import _VMEM_BUDGET, dtw_band_pallas
from repro.kernels.tiling import sched_pair_tile, stream_geometry

L_SMALL = 129                       # odd: exercises parity masking


def _pair(rng, P, L):
    a = jnp.array(rng.normal(size=(P, L)).astype(np.float32))
    b = jnp.array(rng.normal(size=(P, L)).astype(np.float32))
    return a, b


# ---------------------------------------------------------------------------
# forced-streaming parity sweep at small L (full cross product)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("P,L", [(13, L_SMALL), (8, 96), (1, 40)])
@pytest.mark.parametrize("wsel", ["0", "1", "L/4", "L"])
@pytest.mark.parametrize("with_cutoff", [False, True])
def test_stream_matches_resident_bitwise(rng, P, L, wsel, with_cutoff):
    w = {"0": 0, "1": 1, "L/4": L // 4, "L": L}[wsel]
    a, b = _pair(rng, P, L)
    if with_cutoff:
        plain = np.array(ref.dtw_band_ref(a, b, w))
        # mixed liveness: even lanes abandon, odd lanes finish exactly
        cut = jnp.array(np.where(np.arange(P) % 2 == 0, plain * 0.5,
                                 plain * 2.0 + 1.0).astype(np.float32))
    else:
        cut = None
    st = np.array(dtw_band_pallas(a, b, w, cut, stream=True, tile_p=8,
                                  interpret=True))
    rs = np.array(dtw_band_pallas(a, b, w, cut, tile_p=8, interpret=True))
    np.testing.assert_array_equal(st, rs)
    want = np.array(dtw_band_blocked(a, b, w, cut))
    np.testing.assert_allclose(st, want, rtol=1e-6)


def test_stream_lone_survivor_tile(rng):
    """One live lane pins its tile: every other lane is poisoned, the
    survivor's value is exact — across the streaming DMA pipeline."""
    P, L, w = 16, 64, 8
    a, b = _pair(rng, P, L)
    plain = np.array(ref.dtw_band_ref(a, b, w))
    cut_np = (plain * 1e-3).astype(np.float32)
    cut_np[7] = np.inf
    got = np.array(dtw_band_pallas(a, b, w, jnp.array(cut_np), stream=True,
                                   row_block=8, tile_p=8, interpret=True))
    np.testing.assert_allclose(got[7], plain[7], rtol=1e-4, atol=1e-5)
    assert np.all(np.isinf(np.delete(got, 7)))


def test_stream_all_dead_tile(rng):
    """A fully-poisoned tile stops issuing DMAs and still emits +inf for
    every lane (the drained-pipeline output path)."""
    P, L, w = 8, 64, 16
    a, b = _pair(rng, P, L)
    plain = np.array(ref.dtw_band_ref(a, b, w))
    cut = jnp.array((plain * 1e-3).astype(np.float32))
    got = np.array(dtw_band_pallas(a, b, w, cut, stream=True, row_block=16,
                                   interpret=True))
    assert np.all(np.isinf(got))
    want = np.array(ref.dtw_band_ref(a, b, w, cut, row_block=16))
    np.testing.assert_allclose(got, want)


def test_stream_row_block_override_is_result_invariant(rng):
    """Abandon decisions move with the block boundary but values do not
    (frontier minima are monotone) — any row_block gives the same output."""
    P, L, w = 9, 80, 12
    a, b = _pair(rng, P, L)
    plain = np.array(ref.dtw_band_ref(a, b, w))
    cut = jnp.array((plain * np.linspace(0.3, 3.0, P)).astype(np.float32))
    outs = [
        np.array(dtw_band_pallas(a, b, w, cut, stream=True, row_block=rb,
                                 tile_p=8, interpret=True))
        for rb in (8, 32, None)
    ]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


# ---------------------------------------------------------------------------
# lengths straddling the old 16384 ceiling (cheap windows only — see header)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("L", [16384, 16392, 32768])
def test_stream_across_old_length_ceiling(rng, L):
    P, w = 2, 1
    a, b = _pair(rng, P, L)
    st = np.array(dtw_band_pallas(a, b, w, stream=True, interpret=True))
    want = np.array(dtw_band_blocked(a, b, w))
    np.testing.assert_allclose(st, want, rtol=1e-6)
    # cutoff: lane 0 exact, lane 1 abandons
    cut = jnp.array([want[0] * 2 + 1, want[1] * 0.5], dtype=jnp.float32)
    st_c = np.array(dtw_band_pallas(a, b, w, cut, stream=True,
                                    interpret=True))
    want_c = np.array(dtw_band_blocked(a, b, w, cut))
    np.testing.assert_allclose(st_c, want_c, rtol=1e-6)
    assert np.isinf(st_c[1]) and np.isfinite(st_c[0])


def test_dtw_band_op_accepts_L65536(rng):
    """The acceptance bar: no _DTW_MAX_L — the op streams at L = 65536."""
    P, L, w = 2, 65536, 1
    a, b = _pair(rng, P, L)
    got = np.array(ops.dtw_band_op(a, b, w))
    want = np.array(dtw_band_blocked(a, b, w))
    assert got.shape == (P,) and np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_dtw_band_op_streams_past_residency(rng):
    """Just past the crossover the op routes to the streaming kernel and
    matches the reference (cutoff semantics included)."""
    P, L, w = 3, ops._DTW_RESIDENT_MAX_L + 8, 2
    a, b = _pair(rng, P, L)
    want = np.array(dtw_band_blocked(a, b, w))
    got = np.array(ops.dtw_band_op(a, b, w))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    cut = jnp.array([np.inf, 0.0, np.inf], jnp.float32)
    got_c = np.array(ops.dtw_band_op(a, b, w, cut))
    assert np.isinf(got_c[1]) and np.isfinite(got_c[0])


def test_stream_unfittable_band_falls_back_to_ref(rng, monkeypatch):
    """w so wide the band state alone exceeds VMEM at the sublane floor:
    stream_geometry says None and the op routes to the jnp reference.
    (Executing that shape is O(L^2) work on any path — too costly for a
    test — so the dispatch decision is asserted via a sentinel.)"""
    L = ops._DTW_RESIDENT_MAX_L + 8
    assert stream_geometry(L, L - 1, 128, 2, _VMEM_BUDGET) is None
    P = 2
    a, b = _pair(rng, P, L)
    sentinel = jnp.full((P,), 42.0, jnp.float32)
    monkeypatch.setattr(ops.ref, "dtw_band_ref",
                        lambda *a_, **kw: sentinel)
    out = np.array(ops.dtw_band_op(a, b, None))
    np.testing.assert_array_equal(out, 42.0)


# ---------------------------------------------------------------------------
# streaming geometry budget
# ---------------------------------------------------------------------------

def test_stream_geometry_fits_budget():
    budget = _VMEM_BUDGET
    for L, w in [(2048, 205), (16384, 64), (65536, 655), (65536, 4096)]:
        geom = stream_geometry(L, w, 128, 1024, budget)
        assert geom is not None, (L, w)
        tile, R = geom
        Wb = -(-(2 * w + 1) // 128) * 128
        # the kernel's window: R + Wb lanes from a 128-aligned origin,
        # read through (Wb + 128)-lane aligned loads
        Wwin = -(-(R + 127) // 128) * 128 + Wb
        per_row = (4 * Wwin + 8 * Wb) * 4
        assert tile * per_row <= budget
        assert tile % 8 == 0 and tile >= 8
        assert R >= 1


def test_stream_geometry_prefers_shared_policy():
    """When the policy block fits (and clears the streaming amortisation
    floor), streaming and the jnp reference make abandon decisions on
    identical boundaries; the floor itself is the band-width-aware
    ``stream_pref_block`` policy, not a hard-coded constant."""
    from repro.kernels.tiling import stream_pref_block

    L, w = 8192, 410
    geom = stream_geometry(L, w, 8, 8, _VMEM_BUDGET)
    assert geom is not None and geom[1] == row_block_policy(L)
    # wide band at a short length: the policy floor (320 steps at wb=205)
    # no longer binds — the shared ~8-block policy wins, where the old
    # 1024-step hard floor forced 4 oversized blocks
    geom = stream_geometry(2048, 205, 8, 8, _VMEM_BUDGET)
    assert geom is not None
    assert geom[1] == max(row_block_policy(2048), stream_pref_block(205))
    assert geom[1] < 1024
    # an explicit measured floor overrides the policy
    geom = stream_geometry(2048, 205, 8, 8, _VMEM_BUDGET, pref_block=1024)
    assert geom is not None and geom[1] == 1024


def test_stream_pref_block_policy_bounds():
    from repro.kernels.tiling import stream_pref_block

    # narrow bands (one lane group) keep the old 1024-step floor
    assert stream_pref_block(1) == 1024
    assert stream_pref_block(63) == 1024
    # wider bands amortise DMA issue with smaller blocks, floor 64
    assert stream_pref_block(205) < 1024
    assert all(stream_pref_block(wb) >= 64 for wb in (1, 205, 4096, 10**6))
    assert all(stream_pref_block(wb) % 64 == 0 for wb in (1, 77, 205, 4096))


# ---------------------------------------------------------------------------
# schedule-aware pair-tile sizing (geometry only — results invariant)
# ---------------------------------------------------------------------------

def test_dtw_band_op_tile_p_is_result_invariant(rng):
    P, L, w = 40, 64, 9
    a, b = _pair(rng, P, L)
    plain = np.array(ref.dtw_band_ref(a, b, w))
    cut = jnp.array(np.where(np.arange(P) % 3 == 0, plain * 0.5,
                             plain * 2.0).astype(np.float32))
    perm = jnp.array(rng.permutation(P))
    base = np.array(ops.dtw_band_op(a, b, w, cut))
    for tp in (8, 16, 128):
        np.testing.assert_array_equal(
            np.array(ops.dtw_band_op(a, b, w, cut, tile_p=tp)), base)
        np.testing.assert_array_equal(
            np.array(ops.dtw_band_op(a, b, w, cut, tile_p=tp, perm=perm)),
            base)
    # the reference accepts (and ignores) the same hint — one call shape
    np.testing.assert_array_equal(
        np.array(ref.dtw_band_ref(a, b, w, cut, tile_p=8)),
        np.array(ref.dtw_band_ref(a, b, w, cut)))


def test_sched_pair_tile_policy_bounds():
    for P in (8, 64, 512, 4096, 100000):
        t = sched_pair_tile(P)
        assert 8 <= t <= 128 and t % 8 == 0
    assert sched_pair_tile(512) == 32          # typical engine round
    assert sched_pair_tile(100000) == 128      # huge rounds keep full tiles
