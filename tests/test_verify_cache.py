"""The verification loop's program cache (``engine._verify_loop``).

The loop is one jitted program per static key and argument shapes: a
warm call of the same shape traces and lowers nothing, a new shape or
key lowers once, results stay bit-equal to the unpruned brute force,
fault seams key the program, and a kernel fallback warning raised while
the program was traced fires on every call that reuses it.
"""

import dataclasses
import os
import warnings

import numpy as np
import pytest

from repro import obs
from repro.kernels import ops
from repro.search import (CascadeConfig, EngineConfig, GuardConfig,
                          GuardWarning, brute_force, build_index, nn_search)
from repro.search import engine
from repro.search.pipeline import default_plan
from repro.testing import faults

W = 4
VERIFY = "repro.engine.verify"


def _store(n=48, length=24, n_q=6, seed=11):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, length)).astype(np.float32)
    q = rng.normal(size=(n_q, length)).astype(np.float32)
    return build_index(x, W), q


def _cfg(k=2, verify=8, guards=None):
    return EngineConfig(
        cascade=CascadeConfig(w=W, v=4, candidate_chunk=16, use_pallas=True),
        verify_chunk=verify, k=k, auto_plan=False, guards=guards,
    )


def _verify_lowerings(before) -> int:
    return obs.snapshot()["lowerings_by_span"].get(VERIFY, 0) - \
        before["lowerings_by_span"].get(VERIFY, 0)


def _exact(idx, q, res, k):
    bd, bi = brute_force(idx, q, W, k, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(res.dists), np.asarray(bd))
    np.testing.assert_array_equal(np.asarray(res.idx), np.asarray(bi))


@pytest.fixture(autouse=True)
def _fresh_programs():
    # each test starts without the engine's programs, and leaves none of
    # its own (a monkeypatched kernel limit is not part of a key)
    for program in (engine._bound_pass, engine._verify_loop):
        program.clear_cache()
    yield
    for program in (engine._bound_pass, engine._verify_loop):
        program.clear_cache()


def test_two_calls_of_one_shape_lower_once():
    idx, q = _store()
    cfg = _cfg()
    before = obs.snapshot()
    runs = [nn_search(idx, q, cfg) for _ in range(3)]
    assert _verify_lowerings(before) == 1
    for res in runs:
        _exact(idx, q, res, cfg.k)
        np.testing.assert_array_equal(np.asarray(res.n_dtw),
                                      np.asarray(runs[0].n_dtw))


@pytest.mark.parametrize("change", ["Q", "verify_chunk", "k", "schedule"])
def test_a_new_shape_or_key_lowers_again(change):
    idx, q = _store()
    cfg = _cfg()
    nn_search(idx, q, cfg)
    plan = None
    if change == "Q":
        q = q[:4]
    elif change == "verify_chunk":
        cfg = dataclasses.replace(cfg, verify_chunk=4)
    elif change == "k":
        cfg = dataclasses.replace(cfg, k=3)
    else:
        plan = default_plan(cfg.cascade, schedule="index")
    before = obs.snapshot()
    first = nn_search(idx, q, cfg, plan=plan)
    assert _verify_lowerings(before) == 1
    again = nn_search(idx, q, cfg, plan=plan)
    assert _verify_lowerings(before) == 1
    _exact(idx, q, first, cfg.k)
    _exact(idx, q, again, cfg.k)
    np.testing.assert_array_equal(np.asarray(again.n_dtw),
                                  np.asarray(first.n_dtw))


@pytest.mark.parametrize("seam", ["engine_count", "dtw_out"])
def test_a_fault_injected_after_a_warm_call_takes_effect(seam):
    idx, q = _store()
    cfg = _cfg(guards=GuardConfig(enabled=False))
    clean = nn_search(idx, q, cfg)
    inject = (faults.miscount_verifications(delta=1000)
              if seam == "engine_count" else faults.corrupt_dtw(scale=0.5))
    before = obs.snapshot()
    with inject:
        faulty = nn_search(idx, q, cfg)
    assert _verify_lowerings(before) == 1
    if seam == "engine_count":
        assert int(faulty.n_dtw[0]) >= int(clean.n_dtw[0]) + 1000
    else:
        assert not np.array_equal(np.asarray(faulty.dists),
                                  np.asarray(clean.dists))
    before = obs.snapshot()
    again = nn_search(idx, q, cfg)
    assert _verify_lowerings(before) == 0
    for name in ("dists", "idx", "n_dtw"):
        np.testing.assert_array_equal(np.asarray(getattr(again, name)),
                                      np.asarray(getattr(clean, name)))
    _exact(idx, q, again, cfg.k)


def test_a_trip_in_the_loop_still_degrades_on_a_warm_program():
    idx, q = _store()
    cfg = _cfg()
    nn_search(idx, q, cfg)
    with faults.miscount_verifications(), \
            warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        res, rep = nn_search(idx, q, cfg, with_guards=True)
    assert "account_viol" in rep.tripped()
    assert any(issubclass(w.category, GuardWarning) for w in caught)
    _exact(idx, q, res, cfg.k)


def test_a_fallback_warning_fires_on_every_reuse(monkeypatch):
    # the loop's DTW shape falls back to the jnp reference: past the
    # (lowered) residency limit, with no band state that fits VMEM
    monkeypatch.setattr(ops, "_DTW_RESIDENT_MAX_L", 16)
    monkeypatch.setattr(ops, "stream_vmem_budget", lambda: 1)
    idx, q = _store()
    cfg = _cfg()

    def loop_fallbacks():
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            res = nn_search(idx, q, cfg)
        return res, [w for w in caught
                     if issubclass(w.category, ops.KernelFallbackWarning)
                     and os.path.samefile(w.filename, engine.__file__)]

    first, warned = loop_fallbacks()
    assert warned
    for _ in range(2):
        before = obs.snapshot()
        res, again = loop_fallbacks()
        assert _verify_lowerings(before) == 0
        assert [str(w.message) for w in again] == \
            [str(w.message) for w in warned]
        np.testing.assert_array_equal(np.asarray(res.dists),
                                      np.asarray(first.dists))
    _exact(idx, q, first, cfg.k)
