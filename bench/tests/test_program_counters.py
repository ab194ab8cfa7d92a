"""The readers of the program's own counters and spans (``repro.obs``),
on hand-built runs."""

import sys

import numpy as np
import pytest

from bench import harness, trace_reduce

SUMMARY = trace_reduce.Summary(window_s=2.0, busy_s=1.0, kernel_s={},
                               other_s=0.0, device_ops=[], idle_gaps=[],
                               n_devices=1)
TRACED = {"calls": 4, "lowerings": 4, "lowering_s": 0.8,
          "lowerings_by_span": {"repro.engine.verify": 4},
          "lowering_s_by_span": {"repro.engine.verify": 0.8},
          "verify_rounds": 5000,
          "span_s": {"repro.nn_search": 4.0,
                     "repro.nn_search.guards": 0.2,
                     "repro.engine.bounds": 1.2}}
READERS = {"lowerings_per_request.online": 1.0,
           "lowering_ms_per_request.online": 200.0,
           "lowering_ms_per_request.batch": 200.0,
           "guard_sync_ms_per_request.online": 50.0,
           "cascade_host_ms_per_request.batch": 300.0,
           "verify_rounds_per_request.batch": 1250.0}


def _run(trace=SUMMARY, n=4):
    reqs = [harness.Request(i=k, t0=float(k), t1=k + 0.5, n=32,
                            idx=np.zeros((32, 1)), dists=np.zeros((32, 1)))
            for k in range(n)]
    return harness.Run(cell="c", config={"n_series": 10}, traffic={},
                       setup_s=1.0, requests=reqs, trace=trace)


@pytest.fixture
def counted(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "snapshot", lambda: {"trace": TRACED})


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_on_a_hand_built_run(counted, metric):
    read = harness.Catalog().reader(metric)
    assert read(_run()) == pytest.approx(READERS[metric])
    assert read(_run(trace=None)) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_finds_nothing_in_a_program_without_obs(monkeypatch, metric):
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr("repro.obs", raising=False)
    assert harness.Catalog().reader(metric)(_run()) is None


def test_span_readers_find_nothing_for_a_span_not_entered(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "snapshot",
                        lambda: {"trace": dict(TRACED, span_s={})})
    for metric in ("guard_sync_ms_per_request.online",
                   "cascade_host_ms_per_request.batch"):
        assert harness.Catalog().reader(metric)(_run()) is None


def test_readers_read_a_real_traced_window(tmp_path):
    import jax

    from repro.data import make_dataset
    from repro.search import (CascadeConfig, EngineConfig, build_index,
                              nn_search)

    ds = make_dataset(n_classes=3, n_train_per_class=12, n_test_per_class=4,
                      length=24, seed=1)
    idx = build_index(ds.x_train, 4, ds.y_train)
    cfg = EngineConfig(cascade=CascadeConfig(w=4, v=4, candidate_chunk=16),
                       verify_chunk=4)
    nn_search(idx, ds.x_test, cfg)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            jax.block_until_ready(nn_search(idx, ds.x_test, cfg).dists)
    finally:
        jax.profiler.stop_trace()
    run = _run(n=3)
    got = {m: harness.Catalog().reader(m)(run) for m in READERS}
    assert got["lowerings_per_request.online"] == 1.0
    assert got["lowering_ms_per_request.batch"] > 0
    assert got["verify_rounds_per_request.batch"] >= 1
    assert 0 < got["guard_sync_ms_per_request.online"]
    assert 0 < got["cascade_host_ms_per_request.batch"]
