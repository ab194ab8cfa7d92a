"""The bound pass's program cache (``engine._bound_pass``).

On concrete inputs the cascade runs as one jitted program per static
key (plan, budget-resolved cascade config, k, guards, fault seams) and
argument shapes: a warm call traces and lowers nothing, a new key lowers
once, a fault injected after a warm call still reaches the guards, the
warnings and counters its tracing recorded fire on every reuse, and its
result is the eager ``run_plan``'s.  Traced inputs run the pass inline.
"""

import dataclasses
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import ops
from repro.search import (CascadeConfig, EngineConfig, GuardConfig,
                          GuardWarning, brute_force, build_index, nn_search,
                          run_plan)
from repro.search import cascade, engine
from repro.search.guards import resolve_guards
from repro.search.pipeline import default_plan
from repro.testing import faults

W = 4
BOUNDS = "repro.engine.bounds"


def _store(n=48, length=24, n_q=6, seed=13):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, length)).astype(np.float32)
    q = rng.normal(size=(n_q, length)).astype(np.float32)
    return build_index(x, W), q


def _cfg(k=2, budget=None, guards=None):
    return EngineConfig(
        cascade=CascadeConfig(w=W, v=4, candidate_chunk=8, use_pallas=True,
                              survivor_budget=budget),
        verify_chunk=8, k=k, auto_plan=False, guards=guards,
    )


def _delta(before, name, key=None):
    after = obs.snapshot()
    if key is None:
        return after[name] - before[name]
    return after[name].get(key, 0) - before[name].get(key, 0)


def _bounds_lowerings(before) -> int:
    return _delta(before, "lowerings_by_span", BOUNDS)


def _exact(idx, q, res, k):
    bd, bi = brute_force(idx, q, W, k, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(res.dists), np.asarray(bd))
    np.testing.assert_array_equal(np.asarray(res.idx), np.asarray(bi))


@pytest.fixture(autouse=True)
def _fresh_programs():
    # each test starts without the engine's programs and leaves none of
    # its own (a monkeypatched kernel limit is not part of a key)
    for program in (engine._bound_pass, engine._verify_loop):
        program.clear_cache()
    yield
    for program in (engine._bound_pass, engine._verify_loop):
        program.clear_cache()


def test_two_calls_of_one_shape_lower_the_bound_pass_once():
    idx, q = _store()
    cfg = _cfg()
    before = obs.snapshot()
    first = nn_search(idx, q, cfg)
    assert engine._bound_pass._cache_size() == 1
    warm = obs.snapshot()
    runs = [nn_search(idx, q, cfg) for _ in range(2)]
    assert _bounds_lowerings(warm) == 0
    assert _delta(warm, "lowerings") == 0
    assert engine._bound_pass._cache_size() == 1
    assert _delta(before, "bound_pass", "program") == 3
    assert _delta(before, "bound_pass", "inline") == 0
    for res in (first, *runs):
        _exact(idx, q, res, cfg.k)
        np.testing.assert_array_equal(np.asarray(res.n_dtw),
                                      np.asarray(first.n_dtw))
        np.testing.assert_array_equal(np.asarray(res.lb),
                                      np.asarray(first.lb))


def _noop_tier(t, name):
    return t


@pytest.mark.parametrize("change", ["budget", "k", "plan", "hook"])
def test_a_new_budget_k_plan_or_hook_lowers_again(change):
    idx, q = _store()
    cfg = _cfg(budget=16)
    nn_search(idx, q, cfg)
    plan = None
    if change == "budget":
        cfg = _cfg(budget=32)
    elif change == "k":
        cfg = _cfg(k=3, budget=16)
    elif change == "plan":
        base = default_plan(cfg.cascade)
        plan = dataclasses.replace(
            base, tiers=tuple(t for t in base.tiers if t.name != "kim"))

    def search():
        if change != "hook":
            return nn_search(idx, q, cfg, plan=plan)
        with faults.inject("tier_out", _noop_tier):
            return nn_search(idx, q, cfg)

    first = search()
    assert engine._bound_pass._cache_size() == 2
    before = obs.snapshot()
    again = search()
    assert _bounds_lowerings(before) == 0
    assert engine._bound_pass._cache_size() == 2
    _exact(idx, q, first, cfg.k)
    _exact(idx, q, again, cfg.k)
    np.testing.assert_array_equal(np.asarray(again.n_dtw),
                                  np.asarray(first.n_dtw))


@pytest.mark.parametrize("seam", ["tier_out", "compaction_cand",
                                  "packed_rows"])
def test_a_fault_injected_after_a_warm_call_reaches_the_guards(seam):
    idx, q = _store()
    cfg = _cfg()
    clean = nn_search(idx, q, cfg)
    inject = {"tier_out": faults.inadmissible_tier,
              "compaction_cand": faults.drop_compaction_candidates,
              "packed_rows": faults.corrupt_packed_rows}[seam]
    with inject(), warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        res, rep = nn_search(idx, q, cfg, with_guards=True)
    assert engine._bound_pass._cache_size() == 2
    degraded = any(issubclass(w.category, GuardWarning) for w in caught)
    if seam == "packed_rows":
        # contained: the finite gate counts the poisoned bounds, no trip
        assert float(np.asarray(rep.nonfinite_bounds)) > 0
        assert rep.tripped() == () and not degraded
    else:
        want = "admiss_viol" if seam == "tier_out" else "conserve_viol"
        assert want in rep.tripped()
        assert float(np.asarray(rep.degraded)) > 0 and degraded
    _exact(idx, q, res, cfg.k)
    # the clean program is still there, and serves the clean call
    before = obs.snapshot()
    again = nn_search(idx, q, cfg)
    assert _bounds_lowerings(before) == 0
    for name in ("dists", "idx", "n_dtw", "lb"):
        np.testing.assert_array_equal(np.asarray(getattr(again, name)),
                                      np.asarray(getattr(clean, name)))


def test_a_fallback_warning_and_the_grid_counts_fire_on_every_reuse(
        monkeypatch):
    # the seeds' DTW shape falls back to the jnp reference: past the
    # (lowered) residency limit, with no band state that fits VMEM
    monkeypatch.setattr(ops, "_DTW_RESIDENT_MAX_L", 16)
    monkeypatch.setattr(ops, "stream_vmem_budget", lambda: 1)
    idx, q = _store()
    # a fixed budget: the adaptive estimate's eager DTW warns on the
    # first call alone
    cfg = _cfg(budget=16)

    def search():
        before = obs.snapshot()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            res = nn_search(idx, q, cfg)
        warned = [str(w.message) for w in caught
                  if issubclass(w.category, ops.KernelFallbackWarning)
                  and os.path.samefile(w.filename, cascade.__file__)]
        grid = {key: _delta(before, "bound_grid", key)
                for key in ("resident", "l_blocked")}
        fell = _delta(before, "kernel_fallbacks", "dtw_band")
        return res, warned, grid, fell, before

    first, warned, grid, fell, _ = search()
    assert warned and grid["resident"] > 0 and fell > 0
    for _ in range(2):
        res, again, grid_again, fell_again, before = search()
        assert _bounds_lowerings(before) == 0
        assert again == warned
        assert grid_again == grid
        assert fell_again == fell
        np.testing.assert_array_equal(np.asarray(res.dists),
                                      np.asarray(first.dists))
    _exact(idx, q, first, cfg.k)


@pytest.mark.parametrize("loo", [False, True])
@pytest.mark.parametrize("stats", [False, True])
def test_the_program_returns_what_eager_run_plan_returns(loo, stats):
    idx, q = _store()
    cfg = _cfg()
    k = cfg.k
    casc = cfg.cascade
    plan = default_plan(casc)
    exclude = (np.arange(q.shape[0], dtype=np.int32) * 5) if loo else None
    qa = jnp.asarray(q)
    ex = None if exclude is None else jnp.asarray(exclude)
    g = resolve_guards(None)
    key = engine._BoundsKey(
        cascade=casc, plan=plan, k=k, dtw_fn=ops.dtw_band_op,
        collect_stats=stats, guards=g, faults=(None,) * 4,
        interpret=ops._interpret())
    got = engine._call(engine._bound_pass, key, qa, idx, ex)
    want = run_plan(qa, idx, casc, plan, k=k, dtw_fn=ops.dtw_band_op,
                    exclude=ex, collect_stats=stats, guards=g)
    np.testing.assert_array_equal(np.asarray(got.seed_idx),
                                  np.asarray(want.seed_idx))
    np.testing.assert_array_equal(np.asarray(got.seed_d),
                                  np.asarray(want.seed_d))
    np.testing.assert_allclose(np.asarray(got.lb), np.asarray(want.lb),
                               rtol=1e-6, atol=1e-6)
    assert got.guard.tripped() == want.guard.tripped() == ()
    if stats:
        assert got.stats.names == want.stats.names
        for name in ("mass", "scored", "work", "survivors"):
            np.testing.assert_allclose(
                np.asarray(getattr(got.stats, name)),
                np.asarray(getattr(want.stats, name)), rtol=1e-6)
    else:
        assert got.stats is None and want.stats is None
    res = nn_search(idx, q, cfg, exclude=exclude)
    bd, bi = brute_force(idx, q, W, k, exclude=ex, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(res.idx), np.asarray(bi))
    np.testing.assert_array_equal(np.asarray(res.dists), np.asarray(bd))


def test_a_traced_input_runs_the_pass_inline():
    idx, q = _store()
    cfg = _cfg(guards=GuardConfig(enabled=False))
    eager = nn_search(idx, q, cfg)
    before = obs.snapshot()
    step = jax.jit(lambda x: nn_search(idx, x, cfg))
    traced = step(q)
    assert _delta(before, "bound_pass", "inline") == 1
    assert _delta(before, "bound_pass", "program") == 0
    for name in ("dists", "idx", "n_dtw"):
        np.testing.assert_array_equal(np.asarray(getattr(traced, name)),
                                      np.asarray(getattr(eager, name)))
    _exact(idx, q, traced, cfg.k)
