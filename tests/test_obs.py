"""Host spans and counters of the search path (``repro.obs``)."""

import math
import os
import subprocess
import sys
import warnings

import jax
import numpy as np
import pytest

from repro import obs
from repro.data import make_dataset
from repro.search import (CascadeConfig, EngineConfig, GuardWarning,
                          build_index, nn_search)
from repro.search import engine
from repro.testing import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _setup(k=1, verify=4, w=6, L=32):
    ds = make_dataset(n_classes=3, n_train_per_class=16,
                      n_test_per_class=4, length=L, seed=3)
    idx = build_index(ds.x_train, w, ds.y_train)
    cfg = EngineConfig(cascade=CascadeConfig(w=w, v=4, candidate_chunk=16),
                       verify_chunk=verify, k=k)
    return ds, idx, cfg


def _delta(before, after, key):
    return after[key] - before[key]


def _open():
    return [frame[0] for frame in obs._stack()]


def test_spans_nest_and_unwind_on_exceptions():
    with obs.span("outer"):
        with obs.span("inner"):
            assert _open() == ["repro.outer", "repro.inner"]
        with pytest.raises(RuntimeError):
            with obs.span("failing"):
                assert _open()[-1] == "repro.failing"
                raise RuntimeError("boom")
        assert _open() == ["repro.outer"]

    def fails():
        with obs.span("function"):
            assert _open() == ["repro.function"]
            raise ValueError("boom")

    with pytest.raises(ValueError):
        fails()
    assert _open() == []


def test_calls_count_outermost_spans_only():
    before = obs.snapshot()
    with obs.span("a"):
        with obs.span("b"):
            pass
    with obs.span("c"):
        pass
    assert _delta(before, obs.snapshot(), "calls") == 2


def test_a_fresh_search_lowers_in_verify_and_a_warm_one_lowers_nothing():
    ds, idx, cfg = _setup()
    engine._verify_loop.clear_cache()     # fresh: no loop program yet
    s0 = obs.snapshot()
    nn_search(idx, ds.x_test, cfg)
    s1 = obs.snapshot()
    assert s1["lowerings_by_span"].get("repro.engine.verify", 0) == \
        s0["lowerings_by_span"].get("repro.engine.verify", 0) + 1
    assert s1["lowering_s"] > s0["lowering_s"]
    # warm: every eager op and the loop's program are cached
    nn_search(idx, ds.x_test, cfg)
    s2 = obs.snapshot()
    assert s2["lowerings_by_span"] == s1["lowerings_by_span"]
    assert _delta(s1, s2, "lowerings") == 0
    assert _delta(s1, s2, "lowering_s") == 0


def test_no_lowering_is_counted_outside_a_span():
    before = obs.snapshot()
    jax.jit(lambda x: x * 3.0 + 1.0)(np.arange(7.0)).block_until_ready()
    after = obs.snapshot()
    assert _delta(before, after, "lowerings") == 0
    assert _delta(before, after, "lowering_s") == 0


def test_rounds_are_zero_when_every_query_certifies_at_its_seeds():
    ds, idx, cfg = _setup()
    before = obs.snapshot()
    res = nn_search(idx, ds.x_train[:6], cfg)     # exact store rows
    assert np.all(np.asarray(res.dists)[:, 0] == 0)
    assert _delta(before, obs.snapshot(), "verify_rounds") == 0


@pytest.mark.parametrize("k,verify", [(1, 4), (3, 2), (2, 8)])
def test_rounds_lie_between_the_work_done_and_the_loop_cap(k, verify):
    ds, idx, cfg = _setup(k=k, verify=verify)
    q = ds.x_test
    Q, N = q.shape[0], idx.n
    before = obs.snapshot()
    res = nn_search(idx, q, cfg)
    rounds = _delta(before, obs.snapshot(), "verify_rounds")
    P = Q * min(verify, N)
    work = int(np.sum(np.asarray(res.n_dtw))) - Q * k
    assert work > 0
    assert math.ceil(work / P) <= rounds <= -(-Q * N // P) + 2


def test_round_counters_add_up_on_the_device():
    # the device scalars wait in a queue and are added up on the host
    before = obs.snapshot()
    for _ in range(70):
        obs.count_rounds(jax.numpy.int32(2))
    assert all(isinstance(r, jax.Array) for r, _ in obs._rounds)
    assert _delta(before, obs.snapshot(), "verify_rounds") == 140
    assert not obs._rounds
    # a later call adds up what is ready without waiting on the rest
    obs.count_rounds(jax.numpy.int32(3))
    obs.count_rounds(jax.numpy.int32(4))
    with obs.span("next_call"):
        pass
    assert _delta(before, obs.snapshot(), "verify_rounds") == 147


def test_rounds_count_searches_of_indexes_on_two_devices():
    # one process serving a replica of the store on each of two devices
    script = """
import jax, numpy as np
from repro import obs
from repro.data import make_dataset
from repro.search import CascadeConfig, EngineConfig, build_index, nn_search
ds = make_dataset(n_classes=3, n_train_per_class=16, n_test_per_class=4,
                  length=32, seed=3)
idx = build_index(ds.x_train, 6, ds.y_train)
cfg = EngineConfig(cascade=CascadeConfig(w=6, v=4, candidate_chunk=16,
                                         use_pallas=False),
                   verify_chunk=4, k=1, auto_plan=False)
devs = jax.devices()[:2]
assert len(devs) == 2
s0 = obs.snapshot()["verify_rounds"]
nn_search(idx, ds.x_test, cfg)
one = obs.snapshot()["verify_rounds"] - s0
assert one > 0
res = [nn_search(jax.device_put(idx, d), jax.device_put(ds.x_test, d), cfg)
       for d in (devs[0], devs[1], devs[0], devs[1])]
assert [r.dists.devices() for r in res] == [{d} for d in devs * 2]
assert obs.snapshot()["verify_rounds"] - s0 == 5 * one
print("OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "OK"


def test_a_guard_warning_points_at_the_caller():
    rng = np.random.default_rng(7)
    x = rng.normal(size=(48, 24)).astype(np.float32)
    q = rng.normal(size=(6, 24)).astype(np.float32)
    idx = build_index(x, 4)
    cfg = EngineConfig(cascade=CascadeConfig(w=4, v=4, candidate_chunk=16,
                                             use_pallas=False),
                       verify_chunk=8, k=2, auto_plan=False)
    with faults.inadmissible_tier(), \
            warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        nn_search(idx, q, cfg)
    trips = [w for w in caught if issubclass(w.category, GuardWarning)]
    assert len(trips) == 1
    assert trips[0].filename == __file__


def test_round_counters_skip_traced_values():
    before = obs.snapshot()
    jax.jit(lambda r: (obs.count_rounds(r), r)[1])(jax.numpy.int32(5))
    assert _delta(before, obs.snapshot(), "verify_rounds") == 0


def test_results_bit_equal_with_and_without_a_profiler_trace(tmp_path):
    ds, idx, cfg = _setup(k=2)
    plain = nn_search(idx, ds.x_test, cfg)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = nn_search(idx, ds.x_test, cfg)
        jax.block_until_ready(traced.dists)
    finally:
        jax.profiler.stop_trace()
    np.testing.assert_array_equal(np.asarray(plain.idx),
                                  np.asarray(traced.idx))
    np.testing.assert_array_equal(np.asarray(plain.dists),
                                  np.asarray(traced.dists))
    np.testing.assert_array_equal(np.asarray(plain.n_dtw),
                                  np.asarray(traced.n_dtw))


def test_trace_counters_cover_the_calls_made_while_tracing(tmp_path):
    ds, idx, cfg = _setup()
    nn_search(idx, ds.x_test, cfg)                 # warm, untraced
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            res = nn_search(idx, ds.x_test, cfg)
        jax.block_until_ready(res.dists)
    finally:
        jax.profiler.stop_trace()
    trace = obs.snapshot()["trace"]
    assert trace["calls"] == 2
    assert trace["lowerings"] == 0
    assert trace["lowerings_by_span"] == {}
    spans = trace["span_s"]
    for name in ("nn_search", "nn_search.hygiene", "engine.bounds",
                 "engine.order", "engine.verify", "nn_search.guards"):
        assert spans["repro." + name] > 0
    assert spans["repro.nn_search"] >= spans["repro.engine.bounds"]
    # an untraced call leaves the last trace's counters as they were
    nn_search(idx, ds.x_test, cfg)
    assert obs.snapshot()["trace"]["calls"] == 2
