"""Crediting device programs and idle gaps to the program's ``repro.``
host spans: on hand-made events, and on the trace recorded on a TPU v5e
(``data/nn_search_v5e.xplane.pb.gz``, taken before the program had
spans)."""

from pathlib import Path

import pytest

from bench import span_reduce, trace_reduce

TRACE = Path(__file__).resolve().parent / "data" / "nn_search_v5e.xplane.pb.gz"

HOSTS = [("bench.request", 0, 100), ("bench.nn_search", 0, 80),
         ("repro.nn_search", 5, 75), ("repro.engine.bounds", 10, 30),
         ("repro.cascade.kim", 12, 20), ("repro.engine.verify", 40, 60),
         ("lower_sharding_computation", 41, 50),
         ("trace_to_jaxpr_dynamic", 43, 45), ("bench.fetch", 80, 100)]
# program 1 launched in the Kim tier, 2 later in the bound pass, 3 in the
# loop, 4 while the answers were fetched
DISPATCH = {1: 15, 2: 25, 3: 55, 4: 90}
MODULES = [(16, 18, 1), (26, 35, 2), (56, 70, 3), (91, 95, 4)]


@pytest.fixture(scope="module")
def summary():
    ops = [(s, e) for s, e, _ in MODULES]
    return span_reduce.attribute(HOSTS, DISPATCH, [(MODULES, ops)], 0, 100)


def test_programs_are_credited_to_every_span_open_at_their_dispatch(
        summary):
    got = {k: round(v * 1e9) for k, v in summary.span_device_s.items()}
    assert got == {"repro.nn_search": 2 + 9 + 14,
                   "repro.engine.bounds": 2 + 9,
                   "repro.cascade.kim": 2,
                   "repro.engine.verify": 14}


def test_host_time_of_each_span_inside_the_window(summary):
    got = {k: round(v * 1e9) for k, v in summary.span_host_s.items()}
    assert got == {"repro.nn_search": 70, "repro.engine.bounds": 20,
                   "repro.cascade.kim": 8, "repro.engine.verify": 20}
    clipped = span_reduce.attribute(HOSTS, DISPATCH, [([], [])], 15, 100)
    assert round(clipped.span_host_s["repro.cascade.kim"] * 1e9) == 5


def test_attributed_share_of_busy_time(summary):
    assert summary.busy_s == pytest.approx(29e-9)
    assert summary.attributed_frac == pytest.approx(25 / 29)


def test_idle_gaps_name_the_innermost_repro_span(summary):
    got = {n: round(s * 1e9) for n, s in summary.idle_gaps}
    assert got == {
        "bench.nn_search / repro.nn_search": 16,
        "bench.nn_search / repro.engine.bounds": 8,
        "bench.nn_search / repro.engine.verify / "
        "lower_sharding_computation": 21,
        "bench.fetch": 21 + 5,
    }


def test_lowering_host_events_by_innermost_span(summary):
    got = {k: round(v * 1e9) for k, v in summary.lowering_host_s.items()}
    assert got == {"repro.engine.verify": 9}


@pytest.mark.parametrize("t,want", [
    (15, "bench.nn_search / repro.cascade.kim"),
    (42, "bench.nn_search / repro.engine.verify / "
         "lower_sharding_computation"),
    (44, "bench.nn_search / repro.engine.verify / trace_to_jaxpr_dynamic"),
    (85, "bench.fetch"),
    (150, "<no host event>"),
])
def test_three_part_labels(t, want):
    assert span_reduce.labels(HOSTS, [t]) == [want]


def test_labels_without_repro_spans_are_trace_reduce_labels():
    hosts = [("bench.request", 0, 100), ("bench.nn_search", 0, 60),
             ("dispatch", 10, 20), ("bench.fetch", 60, 100)]
    times = [15, 40, 80, 150]
    assert span_reduce.labels(hosts, times) == \
        trace_reduce._host_labels(hosts, times)


@pytest.fixture(scope="module")
def recorded():
    return span_reduce.load(str(TRACE))


def test_recorded_trace_gaps_keep_their_labels(recorded):
    spans = span_reduce.reduce(recorded)
    old = trace_reduce.reduce(recorded)
    assert spans.idle_gaps == old.idle_gaps
    assert spans.busy_s == pytest.approx(old.busy_s)
    assert spans.span_device_s == {} and spans.attributed_frac == 0.0


def test_every_recorded_program_has_a_dispatch_time(recorded):
    dispatch = span_reduce._dispatch_times(recorded)
    (modules, _), = span_reduce._planes(recorded)
    assert {rid for _, _, rid in modules} <= set(dispatch)
    # run 1657's enqueue waited on a transfer and ran on a worker thread
    # after the call that dispatched it had returned: it is timed at the
    # dispatch on the calling thread, which the flow id names
    assert dispatch[1655] == 43301229
    assert dispatch[1657] == 44422280


def test_command_line(recorded, capsys):
    import json

    assert span_reduce.main([str(TRACE)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["n_devices"] == 1 and out["attributed_frac"] == 0.0
    assert span_reduce.main([]) == 2
