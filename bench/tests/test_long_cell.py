"""A CPU stand-in for ``uea_eigenworms.long_1q``: full-window DTW over
series longer than the bound kernels' L block and the DTW residency
crossover, both lowered so that L = 600 crosses them.  The cell runs end
to end through the same store, serve and query modules, comes out not
correct when the timed path is broken, and the control fails it; the
``.long`` metric readers read what the program counts."""

import json

import numpy as np
import pytest

from bench import harness
from bench.tests import tiny

SEED = 2**33 + 4242
CELL = "tiny_long.tiny_long_1q"
CONFIG = {"store": "ucr_synthetic", "serve": "nn_search",
          "n_series": 40, "n_test": 12, "n_classes": 5, "length": 600,
          "w": 600, "v": 4, "k": 1, "sketch_segments": 8,
          "verify_chunk": 8, "candidate_chunk": 128, "warp": 0.5,
          "noise": 0.15, "data_seed": 7, "precision": "float32",
          "chips": 1}
MIX = {"loop": "closed", "batch": 1, "queries": "test_split",
       "warmup": 1, "check": 3}
REAL = "uea_eigenworms.long_1q"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    root = tmp_path_factory.mktemp("tiny_long")
    spec, catalog = tiny.make(root)
    (root / "configs" / "tiny_long.json").write_text(json.dumps(CONFIG))
    (root / "traffic" / "tiny_long_1q.json").write_text(json.dumps(MIX))
    (root / "limits" / f"{CELL}.json").write_text(
        json.dumps(tiny.LIMITS))
    real = harness.load_spec()
    spec["workloads"] = [{"name": CELL, "config": "tiny_long",
                          "traffic": "tiny_long_1q", "chips": 1,
                          "why": "test"}]
    for group in ("end_to_end", "per_layer"):
        spec[group] = [dict(m, workloads=[CELL]) for m in real[group]
                       if REAL in m.get("workloads", [REAL])]
    return spec, catalog


@pytest.fixture
def long_paths(monkeypatch):
    """The L-blocked bound kernels and the streaming DTW grid at L=600:
    L blocks of 256 columns, the residency crossover at 256."""
    from repro.kernels import ops, tiling
    from repro.kernels.lb_enhanced import lb_enhanced_pallas
    from repro.kernels.lb_enhanced_pairwise import (
        lb_enhanced_pairwise_pallas)
    from repro.search import engine

    def clear():
        for fn in (lb_enhanced_pallas, lb_enhanced_pairwise_pallas,
                   engine._verify_loop):
            fn.clear_cache()

    clear()
    monkeypatch.setattr(tiling, "LB_BLOCK_L", 256)
    monkeypatch.setattr(ops, "_DTW_RESIDENT_MAX_L", 256)
    yield
    clear()


def _run(bench, **kw):
    spec, catalog = bench
    return harness.run_cell(spec, CELL, SEED, 1.0, False, catalog=catalog,
                            **kw)


def test_long_cell_runs_end_to_end(bench, long_paths):
    from repro import obs

    before = obs.snapshot()
    out = _run(bench)
    after = obs.snapshot()
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    spec, _ = bench
    want = {m["name"] for m in harness.cell_metrics(spec, CELL, False)}
    assert set(out["metrics"]) == want == {"setup_s", "latency_p50_ms",
                                           "latency_p95_ms"}
    for name in ("bound_grid", "verify_grid"):
        grown = {k for k, v in after[name].items()
                 if v > before[name].get(k, 0)}
        assert grown >= {"l_blocked" if name == "bound_grid"
                         else "stream"}
    assert after["kernel_fallbacks"] == before["kernel_fallbacks"]


def test_long_cell_sees_a_broken_dtw(bench, long_paths):
    """Every verified DTW distance 0.1% high, inside the kernel's op."""
    from repro.testing import faults

    with faults.inject("dtw_out", lambda d: d * 1.001):
        out = _run(bench)
    assert out["correct"] is False
    gap = out["checks"]["answer_gap"]
    assert gap["value"] > gap["limit"]


def test_long_cell_control_is_not_correct(bench, long_paths):
    out = _run(bench, control=True)
    assert out["correct"] is True
    assert out["control"]["correct"] is False


def test_every_seed_sends_the_whole_test_split(bench):
    """The cell sends the held-out series of the test split, each once in
    ``n_test`` requests, in an order drawn from the run's seed."""
    _, catalog = bench
    cfg = catalog.config("tiny_long")
    data = catalog.module("stores", cfg["store"]).make(cfg)
    make = catalog.module("queries", MIX["queries"]).make

    def sent(seed):
        src = make(MIX, cfg, data, seed)
        return np.concatenate([src.batch(i) for i in range(cfg["n_test"])])

    a, b = sent(SEED), sent(SEED + 2**32)
    assert a.shape == (cfg["n_test"], 600)
    assert not np.array_equal(a, b)
    key = lambda x: x[np.lexsort(x.T[::-1])]  # noqa: E731
    np.testing.assert_array_equal(key(a), key(np.asarray(data.test)))
    np.testing.assert_array_equal(key(a), key(b))


# ---------------------------------------------------------------------------
# the .long readers on hand-built runs
# ---------------------------------------------------------------------------

def _hand_run(trace, n=4, n_dtw=32):
    from bench import trace_reduce

    summary = None if trace is None else trace_reduce.Summary(
        window_s=2.0, busy_s=1.5, kernel_s=trace, other_s=0.0,
        device_ops=[], idle_gaps=[], n_devices=1)
    reqs = [harness.Request(i=k, t0=float(k), t1=k + 0.5, n=1,
                            idx=np.zeros((1, 1)), dists=np.zeros((1, 1)),
                            n_dtw=np.array([n_dtw]))
            for k in range(n)]
    cfg = {"n_series": 128, "length": 17984, "w": 17984, "v": 4}
    return harness.Run(cell=REAL, config=cfg, traffic={}, setup_s=1.0,
                       requests=reqs, trace=summary)


def test_long_readers_on_a_hand_built_run(monkeypatch):
    from repro import obs

    catalog = harness.Catalog()
    kernels = {"dtw_band_pallas": 2.0, "lb_enhanced_pallas": 0.001,
               "lb_enhanced_pairwise_pallas": 0.0002}
    run = _hand_run(kernels)
    monkeypatch.setattr(obs, "snapshot", lambda: {"trace": {
        "kernel_fallbacks": {}, "bound_grid": {}, "verify_grid": {}}})
    got = {m: catalog.reader(m)(run) for m in (
        "device_idle_frac.online", "verified_frac.long",
        "dtw_kernel_ms_per_query.long", "dtw_gcells_per_s.long",
        "bound_kernels_ms_per_query.long",
        "kernel_fallbacks_per_request.long")}
    assert got["device_idle_frac.online"] == pytest.approx(0.25)
    assert got["verified_frac.long"] == pytest.approx(0.25)
    assert got["dtw_kernel_ms_per_query.long"] == pytest.approx(500.0)
    assert got["dtw_gcells_per_s.long"] == pytest.approx(
        4 * 32 * 17984**2 / 2.0 / 1e9)
    assert got["bound_kernels_ms_per_query.long"] == pytest.approx(0.3)
    assert got["kernel_fallbacks_per_request.long"] == 0.0
    monkeypatch.setattr(obs, "snapshot", lambda: {"trace": {
        "kernel_fallbacks": {"dtw_band": 6, "envelope": 2}}})
    assert catalog.reader("kernel_fallbacks_per_request.long")(run) == 2.0


def test_long_readers_find_nothing_without_a_trace_or_kernel(monkeypatch):
    """A run without the trace reads nothing, and so does a program
    whose trace lacks the kernels or whose ``repro.obs`` lacks the
    fallback counter (the parent commit's)."""
    from repro import obs

    catalog = harness.Catalog()
    monkeypatch.setattr(obs, "snapshot", lambda: {"trace": {"calls": 4}})
    for metric in ("device_idle_frac.online", "dtw_kernel_ms_per_query.long",
                   "dtw_gcells_per_s.long", "bound_kernels_ms_per_query.long",
                   "kernel_fallbacks_per_request.long"):
        read = catalog.reader(metric)
        assert read(_hand_run(None)) is None
        if metric != "device_idle_frac.online":
            assert read(_hand_run({})) is None
