"""With the timed path broken underneath, a run comes out not correct;
and the control (the reference in bfloat16 in the program's place) comes
out not correct too.  The harness's look for a chip is in ``run.py``, so
calling ``run_cell`` skips it."""

import numpy as np
import pytest

from bench import harness
from bench.tests import tiny

SEED = 2**32 + 77


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("tiny"))


def _run(bench, cell, **kw):
    spec, catalog = bench
    return harness.run_cell(spec, cell, SEED, 1.0, False, catalog=catalog,
                            **kw)


def _served_differently(change):
    """A serve that alters what ``nn_search`` produced."""
    def wrapper(serve):
        def broken(q):
            return change(serve(q), q, serve)
        return broken
    return wrapper


@pytest.mark.parametrize("cell", ["tiny_rw.tiny_walks",
                                  "tiny_rw_k3.tiny_walks",
                                  "tiny_ucr.tiny_online"])
def test_answer_altered_where_produced(bench, cell):
    """Every verified DTW distance 0.1% high, inside the kernel's op."""
    from repro.testing import faults

    with faults.inject("dtw_out", lambda d: d * 1.001):
        out = _run(bench, cell)
    assert out["correct"] is False
    gap = out["checks"]["answer_gap"]
    assert gap["value"] > gap["limit"]


def test_answer_altered_after_the_search(bench):
    import dataclasses

    def shift(res, q, serve):
        return dataclasses.replace(res, idx=(res.idx + 1) % 256)

    out = _run(bench, "tiny_rw.tiny_walks",
               serve_wrapper=_served_differently(shift))
    assert out["correct"] is False
    assert out["failed"] > 0


@pytest.mark.parametrize("change", ["swap", "repeat"])
def test_later_neighbours_altered(bench, change):
    """At k = 3 the nearest answer is right and a later rank is not."""
    import dataclasses

    def alter(res, q, serve):
        idx = np.asarray(res.idx).copy()
        if change == "swap":
            idx[:, [1, 2]] = idx[:, [2, 1]]
        else:
            idx[:, 2] = idx[:, 1]
        return dataclasses.replace(res, idx=idx)

    out = _run(bench, "tiny_rw_k3.tiny_walks",
               serve_wrapper=_served_differently(alter))
    assert out["correct"] is False

def test_half_of_the_batch_left_out(bench):
    """Only the first half of each batch is searched; its answers stand
    in for the rest."""
    import dataclasses

    def halve(res, q, serve):
        h = q.shape[0] // 2
        first = serve(q[:h])
        pick = np.arange(q.shape[0]) % h
        return dataclasses.replace(res, idx=first.idx[pick],
                                   dists=first.dists[pick])

    out = _run(bench, "tiny_rw.tiny_walks",
               serve_wrapper=_served_differently(halve))
    assert out["correct"] is False


@pytest.mark.parametrize("seed", range(20))
def test_checks_cover_both_halves_of_a_cell_sized_batch(seed):
    """At the batch cells' size (batches of 32, 4 answers checked, a
    window of 7 batches) every draw checks rows in both halves of a
    batch and at both of its ends, so a fault that drops either half of
    every batch is always among the checked answers."""
    rng = np.random.default_rng(seed)
    reqs = [harness.Request(i=i, t0=0.0, t1=1.0, n=32,
                            idx=np.zeros((32, 1)), dists=np.zeros((32, 1)),
                            n_dtw=rng.integers(1, 100, 32))
            for i in range(7)]
    picks = harness.pick_checks(reqs, {"check": 4}, 2**33 + seed)
    rows = {j for _, j in picks}
    assert len(set(picks)) == 4
    assert {0, 31} <= rows and any(j < 16 for j in rows) \
        and any(j >= 16 for j in rows)


def test_degraded_requests_count_as_failed(bench):
    """A NaN from the DTW kernel trips the guards: the engine serves the
    batch by brute force (right answers, but not the path under test)."""
    from repro.testing import faults

    with faults.corrupt_dtw(scale=None, value=float("nan")):
        out = _run(bench, "tiny_rw.tiny_walks")
    assert out["failed"] == out["attempted"] > 0


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_control_is_not_correct(bench, cell):
    out = _run(bench, cell, control=True)
    assert out["correct"] is True
    assert out["control"]["correct"] is False
