"""Every cell kind end to end on the CPU at tiny sizes: set-up, the
closed loop, the metric arithmetic and the check against the reference."""

import json
import math
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import harness, metrics_util
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**33 + 12345          # past 32 bits, as the driver's seeds are


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_cell_runs_end_to_end(bench, cell):
    spec, catalog = bench
    out = harness.run_cell(spec, cell, SEED, 1.0, False, catalog=catalog)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in harness.cell_metrics(spec, cell, False)}
    assert set(out["metrics"]) == want and "setup_s" in want
    for m in out["metrics"].values():
        assert math.isfinite(m["value"]) and m["value"] > 0
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"answer_gap"}
    assert out["window"]["checked"] > 0


@pytest.mark.parametrize("mix", ["tiny_walks", "tiny_dups"])
def test_same_seed_same_inputs(bench, mix):
    """The store and the query pool are one draw from the deployment's
    ``data_seed``; the run's seed orders the queries, so each seed sends
    the same queries in another order."""
    _, catalog = bench
    cfg, m = catalog.config("tiny_rw"), catalog.traffic(mix)
    data = catalog.module("stores", cfg["store"]).make(cfg)
    again = catalog.module("stores", cfg["store"]).make(cfg)
    assert np.array_equal(np.asarray(data.store), np.asarray(again.store))
    make = catalog.module("queries", m["queries"]).make

    def sent(seed):
        src = make(m, cfg, data, seed)
        return np.concatenate([np.asarray(src.batch(i))
                               for i in range(m["pool"])])

    a, b, c = sent(SEED), sent(SEED), sent(SEED + 2**32)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    key = lambda x: x[np.lexsort(x.T[::-1])]  # noqa: E731
    np.testing.assert_array_equal(key(a), key(c))


def test_unknown_names_are_refused(bench):
    _, catalog = bench
    with pytest.raises(KeyError):
        catalog.config("no_such_config")
    with pytest.raises(KeyError):
        catalog.reader("no_such_metric")
    with pytest.raises(KeyError):
        catalog.module("loops", "no_such_loop")


def test_metric_arithmetic():
    for length, w in [(7, 0), (7, 2), (7, 7), (12, 3)]:
        i, j = np.indices((length, length))
        assert metrics_util.band_cells(length, w) == int(
            np.sum(np.abs(i - j) <= w))
    reqs = [harness.Request(i=k, t0=0.0, t1=(k + 1) / 1e3, n=2,
                            idx=np.zeros((2, 1)), dists=np.zeros((2, 1)))
            for k in range(20)]
    reqs[3].failed = reqs[5].failed = True
    run = harness.Run(cell="c", config={"n_series": 10}, traffic={},
                      setup_s=1.0, requests=reqs)
    assert metrics_util.latency_percentile_ms(run, 50) == pytest.approx(12)
    assert metrics_util.latency_percentile_ms(run, 95) == math.inf
    qps = harness.Catalog().reader("queries_per_s")(run)
    assert qps == pytest.approx(36 / 0.020)


def test_benchmark_json_names_files_that_exist():
    spec = harness.load_spec()
    catalog = harness.Catalog()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    cells = {w["name"] for w in spec["workloads"]}
    for w in spec["workloads"]:
        assert name.match(w["name"])
        cfg, mix = catalog.config(w["config"]), catalog.traffic(w["traffic"])
        catalog.module("stores", cfg["store"])
        catalog.module("serve", cfg["serve"])
        catalog.module("queries", mix["queries"])
        catalog.module("loops", mix["loop"])
        assert set(catalog.limits(w["name"])) == {"answer_gap"}
        e2e = [m["name"] for m in harness.cell_metrics(spec, w["name"],
                                                       False)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.cell_metrics(spec, w["name"], True)
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert name.match(m["name"])
        catalog.reader(m["name"])
        assert set(m.get("workloads", cells)) <= cells


def test_run_refuses_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper_chip.rw_q32",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_refuses_in_a_checkout_without_the_program(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper_chip.rw_q32",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
