"""The reduction from a profiler trace to the per-layer numbers, on a
small trace recorded on a TPU v5e (two ``nn_search`` calls of 4 queries
over 4096 random walks of 64, w=19, each inside ``bench.request``:
``data/nn_search_v5e.xplane.pb.gz``) and on hand-made intervals."""

import gzip
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import trace_reduce

TRACE = Path(__file__).resolve().parent / "data" / "nn_search_v5e.xplane.pb.gz"


def test_union_and_clip():
    merged = trace_reduce._union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert merged == [[0, 3], [5, 9]]
    assert trace_reduce._length(merged) == 7
    assert trace_reduce._clip([(0, 4), (6, 10), (11, 12)], 2, 8) == [
        (2, 4), (6, 8)]


def test_idle_gaps_are_named_by_the_innermost_open_host_event():
    hosts = [("bench.request", 0, 100), ("bench.nn_search", 0, 60),
             ("dispatch", 10, 20), ("bench.fetch", 60, 100)]
    got = trace_reduce._host_labels(hosts, [15, 40, 80, 150])
    assert got == ["bench.nn_search / dispatch", "bench.nn_search",
                   "bench.fetch", "<no host event>"]


def test_op_and_kernel_names():
    kernel = ('%dtw_band_pallas.3 = f32[256,1]{1,0} custom-call(f32[8] %a), '
              'custom_call_target="tpu_custom_call"')
    consumer = ("%slice.0 = f32[4,8]{1,0} "
                "slice(f32[8,8]{1,0} %dtw_band_pallas.3)")
    assert trace_reduce.kernel_name(kernel) == "dtw_band_pallas"
    assert trace_reduce.kernel_name(consumer) is None
    assert trace_reduce.op_name("%fusion.12.clone = f32[] fusion()") == (
        "fusion")
    assert trace_reduce.op_name("%copy-done = f32[4]") == "copy-done"


def test_self_times_take_nested_ops_off_their_parent():
    got = trace_reduce._self_times([(0, 10, "while"), (1, 3, "k"),
                                    (4, 8, "k"), (12, 14, "sort")])
    assert got == {"while": 4, "k": 6, "sort": 2}


@pytest.fixture(scope="module")
def summary():
    xspace = gzip.decompress(TRACE.read_bytes())
    return trace_reduce.reduce(ProfileData.from_serialized_xspace(xspace))


def test_recorded_trace_reduces(summary):
    assert summary.n_devices == 1
    assert 0 < summary.busy_s <= summary.window_s
    assert 0 <= summary.other_s <= summary.busy_s
    assert sum(summary.kernel_s.values()) <= summary.busy_s * 1.000001
    assert "dtw_band_pallas" in summary.kernel_s
    bd = summary.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in bd["device_ops"])
    assert set(summary.kernel_s) == {
        "sketch_bound_pallas", "lb_enhanced_pallas",
        "lb_enhanced_pairwise_pallas", "dtw_band_pallas"}
    assert any(n.startswith("bench.nn_search") for n, _ in bd["idle_gaps"])
