"""Exactness guards + fault injection: every injector trips its guard.

The contract under test (search/guards.py + testing/faults.py):

  * clean data: guards are invisible — bit-equal results, zero counters;
  * every deterministic injector trips exactly the guard built for it;
  * tripped trigger-guards degrade: the batch is re-served via reference
    brute force (bounds untrusted, jnp kernels) and the result is
    bit-equal to an independent brute-force run (the ``dtw_out`` fault
    seam lives in kernels/ops.py only, so the fallback dodges injected
    kernel faults by construction);
  * non-finite faults are *contained* (counted, gated, results exact)
    without tripping the degradation ladder — except NaN verification
    values, whose +inf gate may exclude a true neighbour and therefore
    must degrade;
  * input hygiene at the build_index/nn_search boundary rejects (or,
    with ``sanitize=True``, masks and reports) NaN/Inf and zero-variance
    series before they reach z-normalisation.

CI runs this file twice: once normally and once with
``REPRO_FORCE_GUARDS=1`` so a refactor cannot silently disarm the
default-on wiring.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.search import (
    CascadeConfig,
    EngineConfig,
    GuardConfig,
    GuardReport,
    GuardWarning,
    brute_force,
    build_index,
    nn_search,
    preflight_engine,
)
from repro.search import guards as guards_mod
from repro.search.planner import PlannerConfig, calibrate_plan
from repro.testing import faults

W, K = 4, 2


def _store(n=48, length=24, n_q=6, seed=7):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, length)).astype(np.float32)
    q = rng.normal(size=(n_q, length)).astype(np.float32)
    return x, q


def _cfg(use_pallas=False, guards=None, **kw):
    return EngineConfig(
        cascade=CascadeConfig(w=W, v=4, candidate_chunk=16,
                              use_pallas=use_pallas),
        verify_chunk=8, k=K, auto_plan=False, guards=guards, **kw,
    )


def _search(idx, q, cfg):
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        res, rep = nn_search(idx, q, cfg, with_guards=True)
    gw = [x for x in w if issubclass(x.category, GuardWarning)]
    return res, rep, gw


@pytest.fixture()
def store():
    x, q = _store()
    idx = build_index(x, W)
    bd, bi = brute_force(idx, q, W, K, use_pallas=False)
    return idx, q, np.asarray(bd), np.asarray(bi)


@pytest.fixture(scope="module", autouse=True)
def _drop_jit_caches():
    # this module compiles dozens of one-off fault-injected engine
    # variants (each installed hook is a distinct trace); leaving them
    # in jax's global jit cache has crashed XLA's CPU compiler on later
    # heavy compiles in the same process (test_streaming's L=16384
    # stream grid) — clear them on the way out
    yield
    jax.clear_caches()


# ---------------------------------------------------------------------------
# clean path: guards are invisible
# ---------------------------------------------------------------------------


def test_clean_guarded_run_bit_equal_and_counters_zero(store):
    idx, q, bd, bi = store
    res_off = nn_search(idx, q, _cfg(guards=GuardConfig(enabled=False)))
    res_on, rep, gw = _search(idx, q, _cfg())
    assert np.array_equal(np.asarray(res_on.dists), np.asarray(res_off.dists))
    assert np.array_equal(np.asarray(res_on.idx), np.asarray(res_off.idx))
    assert np.array_equal(np.asarray(res_on.dists), bd)
    assert rep.ok() and rep.tripped() == ()
    for f in ("admiss_viol", "conserve_viol", "account_viol",
              "nonfinite_bounds", "nonfinite_dtw", "degraded"):
        assert float(np.asarray(getattr(rep, f))) == 0.0, f
    assert float(np.asarray(rep.admiss_checked)) > 0
    assert float(np.asarray(rep.conserve_checked)) > 0
    assert not gw


def test_clean_guarded_run_jit_clean(store):
    idx, q, bd, _ = store
    cfg = _cfg()

    @jax.jit
    def run(qq):
        res, rep = nn_search(idx, qq, cfg, with_guards=True)
        return res.dists, rep.to_vector()

    d, vec = run(jnp.asarray(q))
    assert np.array_equal(np.asarray(d), bd)
    rep = GuardReport.from_vector(vec)
    assert rep.ok()


def test_guard_report_vector_roundtrip_and_merge():
    import dataclasses

    rep = dataclasses.replace(
        GuardReport.zeros(),
        admiss_checked=jnp.float32(10.0), admiss_viol=jnp.float32(2.0),
        admiss_gap=jnp.float32(0.5), nonfinite_dtw=jnp.float32(3.0),
    )
    back = GuardReport.from_vector(rep.to_vector())
    for f in guards_mod._VEC_FIELDS:
        assert float(np.asarray(getattr(back, f))) == float(
            np.asarray(getattr(rep, f))), f
    merged = rep.merge(rep)
    assert float(np.asarray(merged.admiss_checked)) == 20.0
    assert float(np.asarray(merged.admiss_gap)) == 0.5   # max, not sum
    assert merged.tripped() == ("admiss_viol", "nonfinite_dtw")


def test_forced_guards_env(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_GUARDS", "1")
    g = guards_mod.resolve_guards(GuardConfig(enabled=False))
    assert g.enabled and g.admissibility and g.conservation
    monkeypatch.setenv("REPRO_FORCE_GUARDS", "0")
    assert not guards_mod.resolve_guards(GuardConfig(enabled=False)).enabled


# ---------------------------------------------------------------------------
# trigger guards: injector trips -> degradation restores bit-equality
# ---------------------------------------------------------------------------


def test_inadmissible_tier_trips_and_degrades(store):
    idx, q, bd, bi = store
    with faults.inadmissible_tier():
        res, rep, gw = _search(idx, q, _cfg())
    assert "admiss_viol" in rep.tripped()
    assert float(np.asarray(rep.degraded)) > 0
    assert len(gw) == 1
    assert np.array_equal(np.asarray(res.dists), bd)
    assert np.array_equal(np.asarray(res.idx), bi)


def test_corrupt_dtw_scale_trips_admissibility(store):
    # shrunk verification values fall below valid bounds; the rerun uses
    # the jnp reference kernels (no dtw_out seam) and restores exactness
    idx, q, bd, bi = store
    with faults.corrupt_dtw():
        res, rep, gw = _search(idx, q, _cfg(use_pallas=True))
    assert "admiss_viol" in rep.tripped()
    assert float(np.asarray(rep.degraded)) > 0
    assert np.array_equal(np.asarray(res.dists), bd)
    assert np.array_equal(np.asarray(res.idx), bi)


def test_corrupt_dtw_nan_trips_nonfinite_and_degrades(store):
    idx, q, bd, bi = store
    with faults.corrupt_dtw(value=np.nan):
        res, rep, gw = _search(idx, q, _cfg(use_pallas=True))
    assert "nonfinite_dtw" in rep.tripped()
    assert float(np.asarray(rep.nonfinite_dtw)) > 0
    assert float(np.asarray(rep.degraded)) > 0
    assert np.array_equal(np.asarray(res.dists), bd)
    assert np.array_equal(np.asarray(res.idx), bi)


def test_drop_compaction_candidates_trips_conservation(store):
    idx, q, bd, _ = store
    with faults.drop_compaction_candidates():
        res, rep, gw = _search(idx, q, _cfg())
    assert "conserve_viol" in rep.tripped()
    assert float(np.asarray(rep.degraded)) > 0
    assert np.array_equal(np.asarray(res.dists), bd)


def test_miscount_verifications_trips_accounting(store):
    idx, q, bd, _ = store
    with faults.miscount_verifications():
        res, rep, gw = _search(idx, q, _cfg())
    assert "account_viol" in rep.tripped()
    assert np.array_equal(np.asarray(res.dists), bd)


def test_inward_quantiser_trips_and_degrades():
    # build-time fault (like poison_envelopes): the corrupted sketch
    # store persists past the injector's scope, and the *search* against
    # it must trip the seed admissibility spot-check — the inverted
    # envelopes inflate the tier-(-1) bound above true near-neighbour
    # DTW distances — then degrade to reference brute force (which
    # never reads the sketch) bit-equally
    x, q = _store()
    with faults.inward_quantiser():
        bad = build_index(x, W)
    assert bad.sk_lo is not None
    cfg = EngineConfig(
        cascade=CascadeConfig(w=W, v=4, candidate_chunk=16,
                              use_pallas=False, use_sketch=True),
        verify_chunk=8, k=K, auto_plan=False,
    )
    bd, bi = brute_force(bad, q, W, K, use_pallas=False)
    res, rep, gw = _search(bad, q, cfg)
    assert "admiss_viol" in rep.tripped()
    assert float(np.asarray(rep.degraded)) > 0
    assert np.array_equal(np.asarray(res.dists), np.asarray(bd))
    assert np.array_equal(np.asarray(res.idx), np.asarray(bi))
    # the same store searched without the sketch tier is clean: the
    # fault lives in the sketch features alone
    res2, rep2, _ = _search(bad, q, _cfg())
    assert rep2.tripped() == ()
    assert np.array_equal(np.asarray(res2.dists), np.asarray(bd))


def test_degrade_false_reports_but_serves_raw(store, monkeypatch):
    # the env force overrides degrade=False by design — clear it so this
    # tests the config path, not the CI override
    monkeypatch.delenv("REPRO_FORCE_GUARDS", raising=False)
    idx, q, bd, _ = store
    with faults.inadmissible_tier():
        res, rep, gw = _search(
            idx, q, _cfg(guards=GuardConfig(degrade=False)))
    assert "admiss_viol" in rep.tripped()
    assert float(np.asarray(rep.degraded)) == 0.0
    assert not gw   # no rerun, no warning — caller opted to only observe


# ---------------------------------------------------------------------------
# containment guards: counted + gated, results stay exact, no trip
# ---------------------------------------------------------------------------


def test_poison_envelopes_contained(store):
    idx, q, bd, bi = store
    bad = faults.poison_envelopes(idx, rows=(0, 3, 5))
    res, rep, gw = _search(bad, q, _cfg())
    assert float(np.asarray(rep.nonfinite_bounds)) > 0
    assert rep.tripped() == ()
    assert np.array_equal(np.asarray(res.dists), bd)
    assert np.array_equal(np.asarray(res.idx), bi)


def test_nonfinite_tier_contained(store):
    idx, q, bd, _ = store
    with faults.nonfinite_tier():
        res, rep, gw = _search(idx, q, _cfg())
    assert float(np.asarray(rep.nonfinite_bounds)) > 0
    assert rep.tripped() == ()
    assert np.array_equal(np.asarray(res.dists), bd)


def test_corrupt_packed_rows_contained(store):
    idx, q, bd, _ = store
    with faults.corrupt_packed_rows():
        res, rep, gw = _search(idx, q, _cfg())
    assert float(np.asarray(rep.nonfinite_bounds)) > 0
    assert rep.tripped() == ()
    assert np.array_equal(np.asarray(res.dists), bd)


def test_gates_off_nan_bounds_would_poison(store):
    # the control experiment for the line-438 fix: with finite gates off
    # and a tier emitting NaN, the engine must NOT silently exclude the
    # poisoned candidates' true neighbours.  Gates-on is the default; we
    # only check the guarded path stays exact under the same fault above.
    idx, q, bd, _ = store
    with faults.nonfinite_tier():
        res, rep, gw = _search(idx, q, _cfg())
    assert np.array_equal(np.asarray(res.dists), bd)


# ---------------------------------------------------------------------------
# input hygiene (boundary)
# ---------------------------------------------------------------------------


def test_hygiene_build_index_rejects_nan():
    x, _ = _store()
    bad = faults.corrupt_series(x, rows=(1, 4), cols=(0, 3))
    with pytest.raises(ValueError, match="series"):
        build_index(bad, W)


def test_hygiene_build_index_sanitize_masks_and_warns():
    x, q = _store()
    bad = faults.corrupt_series(x, rows=(1,), cols=(0, 3), value=np.inf)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        idx = build_index(bad, W, sanitize=True)
    assert any(issubclass(x.category, GuardWarning) for x in w)
    assert bool(np.all(np.isfinite(np.asarray(idx.series))))
    res, rep, _ = _search(idx, q, _cfg())
    assert bool(np.all(np.isfinite(np.asarray(res.dists))))


def test_hygiene_query_rejects_and_sanitizes(store):
    idx, q, bd, _ = store
    badq = faults.corrupt_series(q, rows=(0,), cols=(2,))
    with pytest.raises(ValueError, match="query"):
        nn_search(idx, badq, _cfg())
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        res, rep = nn_search(idx, badq, _cfg(), with_guards=True,
                             sanitize=True)
    assert any(issubclass(x.category, GuardWarning) for x in w)
    assert float(np.asarray(rep.hygiene_values)) > 0
    # untouched queries still serve their exact neighbours
    assert np.array_equal(np.asarray(res.dists)[1:], bd[1:])


def test_hygiene_flat_series_under_normalize():
    x, _ = _store()
    x[2] = 1.5   # zero variance: z-norm would divide by ~0
    with pytest.raises(ValueError, match="zero-variance"):
        build_index(x, W, normalize=True)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        idx = build_index(x, W, normalize=True, sanitize=True)
    assert any(issubclass(c.category, GuardWarning) for c in w)
    assert bool(np.all(np.isfinite(np.asarray(idx.series))))


# ---------------------------------------------------------------------------
# preflight + planner fallback
# ---------------------------------------------------------------------------


def test_preflight_engine_ok_and_cached():
    guards_mod.preflight_clear()
    try:
        assert preflight_engine() is True
        assert preflight_engine() is True   # cache hit, no recompute
    finally:
        guards_mod.preflight_clear()


def test_build_index_preflight_flag():
    x, _ = _store(n=32, length=16)
    guards_mod.preflight_clear()
    try:
        build_index(x, W, preflight=True)
        assert ("engine", jax.__version__) in guards_mod._PREFLIGHT_CACHE
    finally:
        guards_mod.preflight_clear()


def test_calibrate_plan_falls_back_on_tripped_guard(store):
    idx, q, _, _ = store
    cfg = _cfg()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        with faults.inadmissible_tier():
            dec = calibrate_plan(q, idx, cfg.cascade, K,
                                 pcfg=PlannerConfig())
    assert any(issubclass(x.category, GuardWarning) for x in w)
    # measurements under a tripped guard are untrusted: nothing dropped
    assert dec.dropped == ()


# ---------------------------------------------------------------------------
# injector harness hygiene
# ---------------------------------------------------------------------------


def test_inject_rejects_nested_same_seam():
    with faults.miscount_verifications():
        with pytest.raises(RuntimeError, match="already injected"):
            with faults.miscount_verifications():
                pass
    assert "engine_count" not in guards_mod._FAULT_HOOKS


def test_seams_empty_after_faults():
    x, q = _store(n=16, length=16, n_q=2)
    idx = build_index(x, W)
    with faults.drop_compaction_candidates():
        nn_search(idx, q, _cfg())
    assert guards_mod._FAULT_HOOKS == {}


# ---------------------------------------------------------------------------
# distributed: guard transport + shard dropout (subprocess, 8 host devices)
# ---------------------------------------------------------------------------


def _run_distributed(script: str, n_devices: int = 8) -> str:
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


_DIST_PRELUDE = """
import numpy as np, jax, jax.numpy as jnp
from repro.search import (build_index, brute_force, EngineConfig, CascadeConfig,
                          make_distributed_search, shard_index, GuardReport)
from repro.testing import faults
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh((4, 2), ("data", "model"))
rng = np.random.default_rng(11)
X = rng.normal(size=(64, 32)).astype(np.float32)
q = rng.normal(size=(4, 32)).astype(np.float32)
idx = build_index(X, 8)
cfg = EngineConfig(cascade=CascadeConfig(w=8, v=4, candidate_chunk=16,
                                         use_pallas=False), verify_chunk=4, k=2)
sidx = shard_index(mesh, idx, ("data",))
step = make_distributed_search(mesh, cfg, data_axes=("data",),
                               query_axis="model", with_guards=True)
bd, _ = brute_force(idx, q, 8, k=2, use_pallas=False)
"""


def test_distributed_guard_vector_merged_clean():
    _run_distributed(_DIST_PRELUDE + """
d, i, n, gv = step(sidx.series, sidx.labels, sidx.upper, sidx.lower,
                   sidx.kim, sidx.kim_ok, jnp.asarray(q))
assert np.allclose(np.array(d), np.array(bd), rtol=1e-4)
rep = GuardReport.from_vector(gv)
assert rep.ok(), rep.summary()
# psum actually merged across 8 shards: every shard checked something
assert float(np.asarray(rep.conserve_checked)) > 0
assert float(np.asarray(rep.admiss_checked)) > 0
print("OK", rep.summary())
""")


def test_distributed_shard_dropout_trips_conservation():
    _run_distributed(_DIST_PRELUDE + """
with faults.shard_dropout(shard=0):
    d, i, n, gv = step(sidx.series, sidx.labels, sidx.upper, sidx.lower,
                       sidx.kim, sidx.kim_ok, jnp.asarray(q))
rep = GuardReport.from_vector(gv)
assert "conserve_viol" in rep.tripped(), rep.summary()
print("OK", rep.summary())
""")
