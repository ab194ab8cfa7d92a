"""Tier-(-1) quantised sketch: admissibility, parity, masking, exactness.

The sketch store (search/index.py) buys its 32 bytes/candidate with one
invariant — outward quantisation means the dequantised segment envelope
always *contains* the true one, so

    LB_sketch <= LB_Keogh <= DTW_w

holds for every (query, candidate) pair at any window.  Everything here
pins that chain and what is built on it: kernel/reference parity, the
store-level candidate mask's exactness (bit-equal neighbours, and on the
calibration distribution never more DTW than the sketchless default
plan), and the degenerate shapes (w = 0, w = L, odd lengths, ragged
segments, zero-variance series) where rounding bugs hide.
"""

import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lower_bounds import lb_keogh_env
from repro.kernels import ref
from repro.kernels.ops import sketch_bound_op
from repro.kernels.sketch import sketch_bound_pallas
from repro.search.cascade import CascadeConfig, run_plan
from repro.search.engine import EngineConfig, brute_force, nn_search
from repro.search.index import (
    build_index,
    sketch_features,
    sketch_query_means,
    sketch_segment_sizes,
    sketch_segments,
)
from repro.search.pipeline import default_plan, get_tier
from repro.search.planner import calibration_sample, plan_cache_clear


def _walks(rng, n, L):
    return np.cumsum(
        rng.normal(size=(n, L)), axis=1
    ).astype(np.float32)


def _sketch_bound(index, q):
    s = index.sk_lo.shape[1]
    qbar = sketch_query_means(jnp.asarray(q, jnp.float32), s)
    seg = sketch_segment_sizes(index.length, s)
    return ref.sketch_bound_ref(qbar, index.sk_lo, index.sk_hi,
                                index.sk_scale, seg)


# ---------------------------------------------------------------------------
# admissibility: LB_sketch <= LB_Keogh <= DTW_w, every window, every shape
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("L", [64, 37, 8])          # even, odd/ragged, S > L
@pytest.mark.parametrize("wsel", ["0", "1", "L/4", "L"])
def test_sketch_admissible_under_keogh_and_dtw(rng, L, wsel):
    w = {"0": 0, "1": 1, "L/4": L // 4, "L": L}[wsel]
    store = _walks(rng, 24, L)
    qs = _walks(rng, 5, L)
    index = build_index(store, w)
    sb = np.array(_sketch_bound(index, qs))
    keogh = np.array(
        jnp.stack([
            jnp.stack([
                lb_keogh_env(jnp.asarray(q), index.upper[n], index.lower[n])
                for n in range(index.n)
            ])
            for q in jnp.asarray(qs)
        ])
    )
    assert np.all(sb <= keogh * (1 + 1e-5) + 1e-5), (
        f"sketch exceeds LB_Keogh at w={w}, L={L}"
    )
    d = np.array(ref.dtw_band_ref(
        jnp.repeat(jnp.asarray(qs), index.n, 0),
        jnp.tile(jnp.asarray(store), (qs.shape[0], 1)), w,
    )).reshape(qs.shape[0], index.n)
    assert np.all(sb <= d * (1 + 1e-5) + 1e-5)


def test_sketch_segments_ragged_and_short():
    # ragged: L = 37, s = 16 -> segment sizes differ by one, cover L
    segs = sketch_segments(37, 16)
    sizes = [b - a for a, b in segs]
    assert len(segs) == 16 and sum(sizes) == 37
    assert segs[0][0] == 0 and segs[-1][1] == 37
    assert all(b > a for a, b in segs)
    assert set(sizes) <= {2, 3}
    # short store: s halves (power-of-two discipline) until it fits
    assert len(sketch_segments(8, 16)) == 8
    assert len(sketch_segments(1, 16)) == 1
    np.testing.assert_array_equal(
        np.array(sketch_segment_sizes(37, 16)), np.array(sizes, np.float32)
    )


def test_sketch_outward_rounding_cellwise(rng):
    # the load-bearing invariant, asserted directly: dequantised cells
    # always contain the true segment means
    store = _walks(rng, 16, 50)
    index = build_index(store, 5)
    segs = sketch_segments(50, index.sk_lo.shape[1])
    useg = np.stack([np.mean(np.array(index.upper)[:, a:b], axis=1)
                     for a, b in segs], axis=1)
    lseg = np.stack([np.mean(np.array(index.lower)[:, a:b], axis=1)
                     for a, b in segs], axis=1)
    scale = float(np.array(index.sk_scale))
    assert np.all(np.array(index.sk_hi, np.float32) * scale >= useg - 1e-6)
    assert np.all(np.array(index.sk_lo, np.float32) * scale <= lseg + 1e-6)


def test_sketch_zero_variance_store_sanitized(rng):
    # flat series survive sanitize=True; maxabs = 0 branch keeps the
    # scale finite and the bound well-defined (zeros against any query
    # inside the envelope)
    store = np.zeros((12, 32), np.float32)
    store[6:] = _walks(rng, 6, 32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        index = build_index(store, 4, sanitize=True, normalize=True)
    sb = np.array(_sketch_bound(index, np.array(index.series)))
    assert np.all(np.isfinite(sb)) and np.all(sb >= 0)
    assert float(np.array(index.sk_scale)) > 0


def test_sketch_store_size_budget(rng):
    # acceptance bar: <= 32 bytes/candidate at the default S = 16
    store = _walks(rng, 40, 256)
    index = build_index(store, 26)
    per_cand = (index.sk_lo.nbytes + index.sk_hi.nbytes) / index.n
    assert per_cand <= 32, per_cand
    assert index.sk_lo.dtype == jnp.int8 and index.sk_hi.dtype == jnp.int8


# ---------------------------------------------------------------------------
# kernel / reference parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("Q,N,L", [(4, 40, 64), (1, 200, 37), (9, 129, 96)])
def test_sketch_kernel_matches_ref(rng, Q, N, L):
    store = _walks(rng, N, L)
    qs = _walks(rng, Q, L)
    index = build_index(store, max(1, L // 8))
    s = index.sk_lo.shape[1]
    qbar = sketch_query_means(jnp.asarray(qs), s)
    seg = sketch_segment_sizes(L, s)
    want = np.array(ref.sketch_bound_ref(
        qbar, index.sk_lo, index.sk_hi, index.sk_scale, seg))
    got = np.array(sketch_bound_op(
        qbar, index.sk_lo, index.sk_hi, index.sk_scale, seg))
    assert got.shape == (Q, N)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_sketch_kernel_tiling_is_result_invariant(rng):
    # candidate padding (N % 128 != 0) and multi-tile grids change
    # nothing but the launch geometry (up to XLA re-fusion across the
    # different grid compilations — the same 1-ulp drift the streaming
    # DTW tests document vs the jnp reference, hence rtol over bits)
    store = _walks(rng, 300, 64)
    qs = _walks(rng, 3, 64)
    index = build_index(store, 8)
    s = index.sk_lo.shape[1]
    qbar = sketch_query_means(jnp.asarray(qs), s)
    seg = sketch_segment_sizes(64, s)
    scale = jnp.asarray(index.sk_scale, jnp.float32)
    qsc = qbar / scale
    wseg = jnp.asarray(seg, jnp.float32) * scale * scale
    base = np.array(sketch_bound_pallas(
        qsc, index.sk_lo, index.sk_hi, wseg, interpret=True))
    for tc in (128, 256):
        np.testing.assert_allclose(
            np.array(sketch_bound_pallas(
                qsc, index.sk_lo, index.sk_hi, wseg, tile_c=tc,
                interpret=True)),
            base, rtol=1e-6,
        )
    # past one 128-row query tile: a zero-padded ragged last query tile
    qbar = sketch_query_means(jnp.asarray(_walks(rng, 300, 64)), s)
    np.testing.assert_allclose(
        np.array(sketch_bound_pallas(
            qbar / scale, index.sk_lo, index.sk_hi, wseg, interpret=True)),
        np.array(ref.sketch_bound_ref(
            qbar, index.sk_lo, index.sk_hi, index.sk_scale, seg)),
        rtol=1e-5, atol=1e-6,
    )


# ---------------------------------------------------------------------------
# the sketch tier and the store mask inside the cascade
# ---------------------------------------------------------------------------


def test_sketch_tier_zeros_without_features(rng):
    # an index built without a sketch must keep the tier valid (all-zero
    # bound) so cfg.use_sketch is safe on any index
    store = _walks(rng, 16, 32)
    index = build_index(store, 4, sketch=None)
    cfg = CascadeConfig(w=4, use_sketch=True)
    t = get_tier("sketch").fn(jnp.asarray(store[:3]), index, cfg)
    np.testing.assert_array_equal(np.array(t), 0.0)


@pytest.mark.parametrize("wsel", ["0", "1", "L/4", "L"])
def test_masked_search_bit_equal_and_no_extra_dtw(rng, wsel):
    """The PR's acceptance property: neighbours bit-equal to brute force
    for arbitrary queries, and on the calibration distribution (the LOO
    sample the mask and plan were derived from) per-query n_dtw never
    exceeds the sketchless default plan's."""
    N, L, k = 96, 64, 3
    w = {"0": 0, "1": 1, "L/4": L // 4, "L": L}[wsel]
    store = _walks(rng, N, L)
    cfg = EngineConfig(cascade=CascadeConfig(w=w, use_sketch=True), k=k)
    plan_cache_clear()
    index = build_index(store, w, calibrate=cfg, mask=True)
    assert index.live is not None and bool(jnp.any(index.live))

    # arbitrary out-of-sample queries: exactness only
    qs = jnp.asarray(_walks(rng, 5, L))
    res = nn_search(index, qs, cfg)
    bd, _ = brute_force(index, qs, w, k=k)
    np.testing.assert_allclose(np.sort(np.array(res.dists), 1),
                               np.sort(np.array(bd), 1),
                               rtol=1e-5, atol=1e-5)

    # calibration-sample LOO queries: exact AND never more DTW
    pick = calibration_sample(N, 8)
    qs2 = jnp.asarray(store[pick])
    ex = jnp.asarray(pick, jnp.int32)
    res2 = nn_search(index, qs2, cfg, exclude=ex)
    bd2, _ = brute_force(index, qs2, w, k=k, exclude=ex)
    np.testing.assert_allclose(np.sort(np.array(res2.dists), 1),
                               np.sort(np.array(bd2), 1),
                               rtol=1e-5, atol=1e-5)
    base_cfg = EngineConfig(cascade=CascadeConfig(w=w), k=k)
    index0 = build_index(store, w, sketch=None)
    res0 = nn_search(index0, qs2, base_cfg, exclude=ex)
    assert np.all(np.array(res2.n_dtw) <= np.array(res0.n_dtw)), (
        np.array(res2.n_dtw), np.array(res0.n_dtw))
    plan_cache_clear()


def test_masked_search_skewed_store(rng):
    """Skewed store with planted outliers: rows far from *every*
    calibration query's neighbourhood go dead (their sketch bound clears
    2x every sampled tau), the search stays exact anyway, and the
    calibration queries pay no extra DTW.  Note the mask's any-query
    semantics mean a *cluster* can never kill itself — its own rows are
    each other's LOO neighbours — so dead candidates are genuinely
    unreachable ones, not merely far-from-one-query ones."""
    L, N, w, k = 64, 128, 12, 2
    store = rng.normal(size=(N, L)).astype(np.float32)
    pick = calibration_sample(N, 8)
    # plant outliers off the calibration stride: no sampled query sits
    # near them, and every sampled query's tau stays cluster-sized
    out_rows = np.array([5, 40, 70, 100])
    assert not np.intersect1d(out_rows, pick).size
    store[out_rows] += 50.0
    cfg = EngineConfig(cascade=CascadeConfig(w=w, use_sketch=True), k=k)
    plan_cache_clear()
    index = build_index(store, w, calibrate=cfg, mask=True)
    live = np.array(index.live)
    assert not live[out_rows].any(), "planted outliers survived the mask"
    assert live.mean() > 0.5, "mask over-killed the cluster"
    qs = jnp.asarray(store[pick])
    ex = jnp.asarray(pick, jnp.int32)
    res = nn_search(index, qs, cfg, exclude=ex)
    bd, _ = brute_force(index, qs, w, k=k, exclude=ex)
    np.testing.assert_allclose(np.sort(np.array(res.dists), 1),
                               np.sort(np.array(bd), 1),
                               rtol=1e-4, atol=1e-5)
    index0 = build_index(store, w, sketch=None)
    res0 = nn_search(index0, qs, EngineConfig(
        cascade=CascadeConfig(w=w), k=k), exclude=ex)
    assert np.all(np.array(res.n_dtw) <= np.array(res0.n_dtw))
    plan_cache_clear()


def test_mask_keeps_cheap_bound_on_dead_candidates(rng):
    # a dead candidate's running bound must stay finite (kim/sketch score
    # everyone) — the mask only withholds *refinement*, never the bound
    N, L, w, k = 64, 48, 6, 2
    store = _walks(rng, N, L)
    cfg = EngineConfig(cascade=CascadeConfig(w=w, use_sketch=True), k=k)
    plan_cache_clear()
    index = build_index(store, w, calibrate=cfg, mask=True)
    if not bool(jnp.all(index.live)):
        qs = jnp.asarray(_walks(rng, 3, L))
        cres = run_plan(qs, index, cfg.cascade, k=k)
        dead = ~np.array(index.live)
        assert np.all(np.isfinite(np.array(cres.lb)[:, dead]))
    plan_cache_clear()


def test_sketch_tier_first_in_default_plan(rng):
    cfg = CascadeConfig(w=4, use_sketch=True)
    plan = default_plan(cfg)
    assert plan.tiers[0].name == "sketch"
    assert plan.tiers[0].cost == "O(S)"
    assert default_plan(CascadeConfig(w=4)).tiers[0].name != "sketch"


# ---------------------------------------------------------------------------
# LB_Improved (Lemire, arXiv:0811.3301) as an optional pairwise tier
# ---------------------------------------------------------------------------


def test_lb_improved_tier_admissible_and_pluggable(rng):
    import dataclasses

    N, L, w, k = 48, 40, 5, 2
    store = _walks(rng, N, L)
    qs = jnp.asarray(_walks(rng, 4, L))
    index = build_index(store, w)
    cfg = CascadeConfig(w=w)
    tier = get_tier("lb_improved")
    assert tier.scope == "pairwise" and tier.cost == "O(L)"
    # admissible: the two-pass bound never exceeds DTW on packed pairs
    P = 16
    qrows = jnp.repeat(qs[:1], P, axis=0)
    crows = index.series[:P]
    out = np.array(tier.fn(qrows, crows, index.upper[:P],
                           index.lower[:P], cfg))
    d = np.array(ref.dtw_band_ref(qrows, crows, w))
    assert np.all(out <= d * (1 + 1e-5) + 1e-5)
    # first-pass dominance: LB_Improved >= LB_Keogh by construction
    first = np.array(jnp.stack([
        lb_keogh_env(qrows[i], index.upper[i], index.lower[i])
        for i in range(P)
    ]))
    assert np.all(out >= first - 1e-5)
    # live masking: dead slots return the scatter-max identity
    live = jnp.arange(P) % 2 == 0
    masked = np.array(tier.fn(qrows, crows, index.upper[:P],
                              index.lower[:P], cfg, live=live))
    assert np.all(np.isneginf(masked[1::2])) and np.all(
        masked[::2] == out[::2])
    # pluggable: swapping it in for the enhanced pairwise tier stays exact
    base = default_plan(cfg)
    tiers = tuple(t if t.scope != "pairwise" else tier for t in base.tiers)
    plan = dataclasses.replace(base, tiers=tiers)
    ecfg = EngineConfig(cascade=cfg, k=k, auto_plan=False)
    res = nn_search(index, qs, ecfg, plan=plan)
    bd, _ = brute_force(index, qs, w, k=k)
    np.testing.assert_allclose(np.sort(np.array(res.dists), 1),
                               np.sort(np.array(bd), 1),
                               rtol=1e-5, atol=1e-5)
