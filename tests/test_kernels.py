"""Pallas kernel sweeps: every kernel, shapes x dtypes, vs the ref.py
pure-jnp oracles (which are themselves tested against the paper-equation
oracles).  On CPU the kernels execute in interpret mode — the same kernel
bodies that compile on TPU."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dtw as _dtw_mod  # noqa: F401 (import check)
from repro.kernels import ops, ref

SHAPES_ENV = [(1, 16, 3), (5, 33, 0), (8, 128, 12), (13, 64, 64), (3, 40, 7), (9, 256, 100)]
SHAPES_LB = [
    (3, 5, 32, 6, 4), (9, 130, 64, 20, 2), (1, 1, 16, 16, 8),
    (8, 128, 100, 10, 1), (4, 17, 48, 0, 4), (2, 3, 24, 24, 0),
]
SHAPES_DTW = [(1, 16, 4), (7, 32, 32), (130, 24, 3), (128, 64, None), (5, 48, 0)]


@pytest.mark.parametrize("n,L,w", SHAPES_ENV)
@pytest.mark.parametrize("dtype", [np.float32])
def test_envelope_kernel(rng, n, L, w, dtype):
    b = jnp.array(rng.normal(size=(n, L)).astype(dtype))
    u1, l1 = ops.envelope_op(b, w)
    u2, l2 = ref.envelope_ref(b, w)
    np.testing.assert_allclose(np.array(u1), np.array(u2), rtol=1e-5)
    np.testing.assert_allclose(np.array(l1), np.array(l2), rtol=1e-5)


@pytest.mark.parametrize("Q,C,L,w,v", SHAPES_LB)
def test_lb_keogh_kernel(rng, Q, C, L, w, v):
    q = jnp.array(rng.normal(size=(Q, L)).astype(np.float32))
    c = jnp.array(rng.normal(size=(C, L)).astype(np.float32))
    u, lo = ops.envelope_op(c, w)
    np.testing.assert_allclose(
        np.array(ops.lb_keogh_op(q, u, lo)),
        np.array(ref.lb_keogh_ref(q, u, lo)),
        rtol=1e-4, atol=1e-5,
    )


@pytest.mark.parametrize("Q,C,L,w,v", SHAPES_LB)
@pytest.mark.parametrize("bands_only", [False, True])
def test_lb_enhanced_kernel(rng, Q, C, L, w, v, bands_only):
    q = jnp.array(rng.normal(size=(Q, L)).astype(np.float32))
    c = jnp.array(rng.normal(size=(C, L)).astype(np.float32))
    u, lo = ops.envelope_op(c, w)
    got = ops.lb_enhanced_op(q, c, u, lo, w, v, bands_only=bands_only)
    want = ref.lb_enhanced_ref(q, c, u, lo, w, v, bands_only=bands_only)
    np.testing.assert_allclose(np.array(got), np.array(want), rtol=1e-4, atol=1e-5)


# pairwise survivor shape: odd L, L == 2*nb (empty bridge), tile-padding
# remainders (P=130 spills the 128 tile; P=9 pads to the 8-sublane multiple)
SHAPES_PAIRWISE = [
    (1, 16, 4, 4), (9, 33, 7, 4), (130, 47, 11, 4), (8, 5, 4, 4),
    (12, 21, 21, 8), (5, 64, 0, 4), (16, 128, 12, 0), (7, 4, 4, 4),
]


@pytest.mark.parametrize("P,L,w,v", SHAPES_PAIRWISE)
@pytest.mark.parametrize("bands_only", [False, True])
def test_lb_enhanced_pairwise_kernel(rng, P, L, w, v, bands_only):
    q = jnp.array(rng.normal(size=(P, L)).astype(np.float32))
    c = jnp.array(rng.normal(size=(P, L)).astype(np.float32))
    u, lo = ops.envelope_op(c, w)
    got = ops.lb_enhanced_pairwise_op(q, c, u, lo, w, v, bands_only=bands_only)
    want = ref.lb_enhanced_pairwise_ref(q, c, u, lo, w, v,
                                        bands_only=bands_only)
    np.testing.assert_allclose(np.array(got), np.array(want),
                               rtol=1e-4, atol=1e-5)


def test_lb_enhanced_pairwise_matches_cross_block_diagonal(rng):
    """The pairwise kernel is the diagonal of the cross-block kernel."""
    P, L, w, v = 24, 48, 10, 4
    q = jnp.array(rng.normal(size=(P, L)).astype(np.float32))
    c = jnp.array(rng.normal(size=(P, L)).astype(np.float32))
    u, lo = ops.envelope_op(c, w)
    pair = ops.lb_enhanced_pairwise_op(q, c, u, lo, w, v)
    block = ops.lb_enhanced_op(q, c, u, lo, w, v)
    np.testing.assert_allclose(np.array(pair), np.array(block).diagonal(),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("P,L,w,v", [(9, 33, 7, 4), (130, 47, 11, 4),
                                     (16, 128, 12, 0)])
@pytest.mark.parametrize("bands_only", [False, True])
def test_lb_enhanced_pairwise_live_slots(rng, P, L, w, v, bands_only):
    """Per-slot liveness: dead slots emit -inf (the compaction scatter-max
    identity), live slots are untouched, and an all-dead batch — whole
    skipped tiles — still emits the right shape of -inf."""
    q = jnp.array(rng.normal(size=(P, L)).astype(np.float32))
    c = jnp.array(rng.normal(size=(P, L)).astype(np.float32))
    u, lo = ops.envelope_op(c, w)
    live = jnp.array(rng.integers(0, 2, size=(P,)).astype(np.int32))
    got = ops.lb_enhanced_pairwise_op(q, c, u, lo, w, v, live=live,
                                      bands_only=bands_only)
    want = ref.lb_enhanced_pairwise_ref(q, c, u, lo, w, v, live=live,
                                        bands_only=bands_only)
    np.testing.assert_allclose(np.array(got), np.array(want),
                               rtol=1e-4, atol=1e-5)
    full = ops.lb_enhanced_pairwise_op(q, c, u, lo, w, v,
                                       bands_only=bands_only)
    lv = np.array(live).astype(bool)
    np.testing.assert_allclose(np.array(got)[lv], np.array(full)[lv],
                               rtol=1e-6)
    assert np.all(np.array(got)[~lv] == -np.inf)
    dead = ops.lb_enhanced_pairwise_op(q, c, u, lo, w, v,
                                       live=jnp.zeros((P,), jnp.int32),
                                       bands_only=bands_only)
    assert dead.shape == (P,) and np.all(np.array(dead) == -np.inf)


@pytest.mark.parametrize("Q,C,L,w,v", [(9, 130, 64, 20, 2), (3, 5, 32, 6, 4),
                                       (8, 128, 100, 10, 1)])
@pytest.mark.parametrize("bands_only", [False, True])
def test_lb_enhanced_cross_block_live_candidates(rng, Q, C, L, w, v,
                                                 bands_only):
    """Liveness parity for the dense cross-block tier (the pairwise
    kernel's PR 4 contract): dead candidates emit -inf down their whole
    output column (the running-max identity), live columns are bit-equal
    to the unmasked kernel, and an all-dead store — whole skipped
    candidate tiles — still emits the right shape of -inf."""
    q = jnp.array(rng.normal(size=(Q, L)).astype(np.float32))
    c = jnp.array(rng.normal(size=(C, L)).astype(np.float32))
    u, lo = ops.envelope_op(c, w)
    live = jnp.array(rng.integers(0, 2, size=(C,)).astype(np.int32))
    got = ops.lb_enhanced_op(q, c, u, lo, w, v, live=live,
                             bands_only=bands_only)
    want = ref.lb_enhanced_ref(q, c, u, lo, w, v, live=live,
                               bands_only=bands_only)
    np.testing.assert_allclose(np.array(got), np.array(want),
                               rtol=1e-4, atol=1e-5)
    full = ops.lb_enhanced_op(q, c, u, lo, w, v, bands_only=bands_only)
    lv = np.array(live).astype(bool)
    np.testing.assert_array_equal(np.array(got)[:, lv],
                                  np.array(full)[:, lv])
    assert np.all(np.array(got)[:, ~lv] == -np.inf)
    dead = ops.lb_enhanced_op(q, c, u, lo, w, v,
                              live=jnp.zeros((C,), jnp.int32),
                              bands_only=bands_only)
    assert dead.shape == (Q, C) and np.all(np.array(dead) == -np.inf)


def test_enhanced_all_pairs_live_mask(rng):
    """The dense tier's bound fn threads the candidate mask through its
    chunked kernel calls (the planner's dense limit-mask lever)."""
    from repro.search import CascadeConfig, build_index, enhanced_all_pairs
    Q, C, L, w = 5, 37, 33, 8
    q = jnp.array(rng.normal(size=(Q, L)).astype(np.float32))
    c = jnp.array(rng.normal(size=(C, L)).astype(np.float32))
    idx = build_index(c, w)
    cfg = CascadeConfig(w=w, v=4, candidate_chunk=16)
    live = jnp.array(rng.integers(0, 2, size=(C,)).astype(np.int32))
    got = np.array(enhanced_all_pairs(q, idx, cfg, live=live))
    want = np.array(enhanced_all_pairs(q, idx, cfg))
    lv = np.array(live).astype(bool)
    np.testing.assert_array_equal(got[:, lv], want[:, lv])
    assert np.all(got[:, ~lv] == -np.inf)


def test_lb_enhanced_pairwise_tile_sweep(rng):
    """VMEM tile shrink: any pair-tile size gives identical bounds."""
    from repro.kernels.lb_enhanced_pairwise import lb_enhanced_pairwise_pallas
    P, L, w, v = 60, 40, 9, 4
    q = jnp.array(rng.normal(size=(P, L)).astype(np.float32))
    c = jnp.array(rng.normal(size=(P, L)).astype(np.float32))
    u, lo = ops.envelope_op(c, w)
    a = lb_enhanced_pairwise_pallas(q, c, u, lo, w, v, tile_p=8,
                                    interpret=True)
    b = lb_enhanced_pairwise_pallas(q, c, u, lo, w, v, tile_p=128,
                                    interpret=True)
    np.testing.assert_allclose(np.array(a), np.array(b), rtol=1e-6)
    want = ref.lb_enhanced_pairwise_ref(q, c, u, lo, w, v)
    np.testing.assert_allclose(np.array(b), np.array(want), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("P,L,w", SHAPES_DTW)
def test_dtw_band_kernel(rng, P, L, w):
    a = jnp.array(rng.normal(size=(P, L)).astype(np.float32))
    b = jnp.array(rng.normal(size=(P, L)).astype(np.float32))
    np.testing.assert_allclose(
        np.array(ops.dtw_band_op(a, b, w)),
        np.array(ref.dtw_band_ref(a, b, w)),
        rtol=1e-4, atol=1e-5,
    )


def test_dtw_band_f64_interpret(rng):
    """dtype sweep: interpret mode should honour f64 inputs too."""
    import jax
    a = jnp.array(rng.normal(size=(4, 20)))
    b = jnp.array(rng.normal(size=(4, 20)))
    got = ops.dtw_band_op(a.astype(jnp.float32), b.astype(jnp.float32), 5)
    want = ref.dtw_band_ref(a.astype(jnp.float32), b.astype(jnp.float32), 5)
    np.testing.assert_allclose(np.array(got), np.array(want), rtol=1e-4)


def test_long_series_fallback(rng):
    """Series beyond the kernel VMEM budget route to the jnp reference,
    with a ``KernelFallbackWarning`` once per kernel and shape."""
    import warnings

    L = 70000   # > envelope kernel budget
    stores = [jnp.array(rng.normal(size=(n, L)).astype(np.float32))
              for n in (1, 1, 2)]
    # the "default" action shows a message once per call site until the
    # filters change, so nothing inside the block may touch them
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("default")
        outs = [ops.envelope_op(b, 10) for b in stores]
    for b, (u, lo) in zip(stores, outs):
        assert u.shape == b.shape and lo.shape == b.shape
        np.testing.assert_array_equal(np.array(u),
                                      np.array(ref.envelope_ref(b, 10)[0]))
    fell = [str(w.message) for w in caught
            if issubclass(w.category, ops.KernelFallbackWarning)]
    assert len(fell) == 2, fell
    assert "(1, 70000)" in fell[0] and "(2, 70000)" in fell[1]


def test_interpret_only_on_cpu_compiled_only_on_tpu(monkeypatch):
    """Kernels interpret on cpu, compile on tpu, and refuse any other
    backend rather than pick a silent third path."""
    import jax

    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert ops._interpret() is want
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._interpret()


# ---------------------------------------------------------------------------
# fused mamba selective scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,C,N,tc,ts", [
    (2, 16, 8, 4, 8, 8), (1, 33, 6, 4, 4, 16), (3, 64, 16, 8, 16, 16),
    (2, 8, 12, 4, 12, 8),
])
def test_mamba_scan_kernel(rng, B, S, C, N, tc, ts):
    from repro.kernels.mamba_scan import mamba_scan_pallas
    from repro.models.mamba import _chunked_selective_scan
    delta = jnp.array(np.abs(rng.normal(size=(B, S, C))).astype(np.float32))
    u = jnp.array(rng.normal(size=(B, S, C)).astype(np.float32))
    A = -jnp.array(np.abs(rng.normal(size=(C, N))).astype(np.float32))
    Bm = jnp.array(rng.normal(size=(B, S, N)).astype(np.float32))
    Cm = jnp.array(rng.normal(size=(B, S, N)).astype(np.float32))
    h0 = jnp.array(rng.normal(size=(B, C, N)).astype(np.float32))
    y1, h1 = mamba_scan_pallas(delta, u, A, Bm, Cm, h0,
                               tile_c=tc, tile_s=ts, interpret=True)
    y2, h2 = _chunked_selective_scan(delta, u, A, Bm, Cm, h0, chunk=8)
    np.testing.assert_allclose(np.array(y1), np.array(y2), rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(np.array(h1), np.array(h2), rtol=1e-3, atol=1e-4)


def test_mamba_scan_op_gradients(rng):
    """custom_vjp backward (recompute through the chunked scan) must match
    differentiating the chunked scan directly."""
    import jax
    from repro.kernels.ops import mamba_scan_op
    from repro.models.mamba import _chunked_selective_scan
    B, S, C, N = 1, 16, 4, 4
    delta = jnp.array(np.abs(rng.normal(size=(B, S, C))).astype(np.float32))
    u = jnp.array(rng.normal(size=(B, S, C)).astype(np.float32))
    A = -jnp.array(np.abs(rng.normal(size=(C, N))).astype(np.float32))
    Bm = jnp.array(rng.normal(size=(B, S, N)).astype(np.float32))
    Cm = jnp.array(rng.normal(size=(B, S, N)).astype(np.float32))
    h0 = jnp.zeros((B, C, N))

    def loss_k(d):
        y, h = mamba_scan_op(d, u, A, Bm, Cm, h0)
        return jnp.sum(y * y) + jnp.sum(h)

    def loss_r(d):
        y, h = _chunked_selective_scan(d, u, A, Bm, Cm, h0, chunk=8)
        return jnp.sum(y * y) + jnp.sum(h)

    g1 = jax.grad(loss_k)(delta)
    g2 = jax.grad(loss_r)(delta)
    np.testing.assert_allclose(np.array(g1), np.array(g2), rtol=1e-3, atol=1e-4)


def test_mamba_pallas_impl_in_model(rng):
    """ssm_impl='pallas' must reproduce the scan path end to end."""
    import jax
    from repro.configs.registry import ARCHS, reduced
    from repro.models.model import LM
    import dataclasses
    r = reduced(ARCHS["falcon-mamba-7b"])
    m1 = LM(cfg=r, mesh=None, remat=False, ssm_impl="scan")
    m2 = LM(cfg=r, mesh=None, remat=False, ssm_impl="pallas")
    params = m1.init(jax.random.PRNGKey(0))
    batch = {
        "tokens": jnp.array(rng.integers(0, r.vocab, size=(2, 16)), jnp.int32),
        "labels": jnp.array(rng.integers(0, r.vocab, size=(2, 16)), jnp.int32),
    }
    l1, _ = m1.loss_fn(params, batch)
    l2, _ = m2.loss_fn(params, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=2e-3)


def test_lb_enhanced_kernel_large_query_tile(rng):
    """SSPerf hillclimb C: tile_q=64 (one candidate-store pass per 64
    queries) must be bit-identical to the default tiling."""
    from repro.kernels.lb_enhanced import lb_enhanced_pallas
    Q, C, L, w, v = 80, 130, 96, 28, 4
    q = jnp.array(rng.normal(size=(Q, L)).astype(np.float32))
    c = jnp.array(rng.normal(size=(C, L)).astype(np.float32))
    u, lo = ops.envelope_op(c, w)
    a = lb_enhanced_pallas(q, c, u, lo, w, v, tile_q=8, interpret=True)
    b = lb_enhanced_pallas(q, c, u, lo, w, v, tile_q=64, interpret=True)
    np.testing.assert_allclose(np.array(a), np.array(b), rtol=1e-6)
    want = ref.lb_enhanced_ref(q, c, u, lo, w, v)
    np.testing.assert_allclose(np.array(b), np.array(want), rtol=1e-4, atol=1e-5)


def test_lb_keogh_kernel_large_query_tile(rng):
    from repro.kernels.lb_keogh import lb_keogh_pallas
    Q, C, L, w = 70, 100, 64, 12
    q = jnp.array(rng.normal(size=(Q, L)).astype(np.float32))
    c = jnp.array(rng.normal(size=(C, L)).astype(np.float32))
    u, lo = ops.envelope_op(c, w)
    a = lb_keogh_pallas(q, u, lo, tile_q=64, interpret=True)
    want = ref.lb_keogh_ref(q, u, lo)
    np.testing.assert_allclose(np.array(a), np.array(want), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# fused flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,Hq,Hkv,D,causal,window,cap", [
    (2, 32, 4, 2, 8, True, None, None),
    (1, 48, 6, 1, 8, True, None, None),
    (2, 33, 4, 4, 8, False, None, None),
    (1, 64, 2, 2, 8, True, 16, None),
    (1, 32, 2, 2, 8, True, None, 30.0),
])
def test_flash_attention_kernel(rng, B, S, Hq, Hkv, D, causal, window, cap):
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.models.attention import flash_attention
    q = jnp.array(rng.normal(size=(B, S, Hq, D)).astype(np.float32))
    k = jnp.array(rng.normal(size=(B, S, Hkv, D)).astype(np.float32))
    v = jnp.array(rng.normal(size=(B, S, Hkv, D)).astype(np.float32))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 score_cap=cap, tile_q=8, tile_k=8,
                                 interpret=True)
    want = flash_attention(q, k, v, pos, pos, causal=causal, window=window,
                           score_cap=cap, kv_chunk=8)
    np.testing.assert_allclose(np.array(got), np.array(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_op_gradients(rng):
    import jax
    from repro.kernels.ops import flash_attention_op
    from repro.models.attention import flash_attention
    B, S, H, D = 1, 16, 2, 8
    q = jnp.array(rng.normal(size=(B, S, H, D)).astype(np.float32))
    k = jnp.array(rng.normal(size=(B, S, H, D)).astype(np.float32))
    v = jnp.array(rng.normal(size=(B, S, H, D)).astype(np.float32))
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    g1 = jax.grad(lambda qq: jnp.sum(flash_attention_op(qq, k, v) ** 2))(q)
    g2 = jax.grad(lambda qq: jnp.sum(
        flash_attention(qq, k, v, pos, pos, kv_chunk=8) ** 2))(q)
    np.testing.assert_allclose(np.array(g1), np.array(g2), rtol=2e-3, atol=2e-3)


def test_attn_pallas_impl_in_model(rng):
    """attn_impl='pallas' must reproduce the chunked path end to end."""
    import jax
    from repro.configs.registry import ARCHS, reduced
    from repro.models.model import LM
    r = reduced(ARCHS["gemma2-2b"])   # local+global windows + softcap
    m1 = LM(cfg=r, mesh=None, remat=False, attn_impl="chunked")
    m2 = LM(cfg=r, mesh=None, remat=False, attn_impl="pallas")
    params = m1.init(jax.random.PRNGKey(0))
    batch = {
        "tokens": jnp.array(rng.integers(0, r.vocab, size=(2, 16)), jnp.int32),
        "labels": jnp.array(rng.integers(0, r.vocab, size=(2, 16)), jnp.int32),
    }
    l1, _ = m1.loss_fn(params, batch)
    l2, _ = m2.loss_fn(params, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=2e-3)
