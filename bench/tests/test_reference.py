"""The plain reference equals the program's unpruned jnp brute force, and
stands apart from the program."""

import ast
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference

BENCH = Path(__file__).resolve().parents[1]


def _walks(rng, n, length):
    x = np.cumsum(rng.normal(size=(n, length)), axis=1)
    x = (x - x.mean(1, keepdims=True)) / x.std(1, keepdims=True)
    return x.astype(np.float32)


@pytest.mark.parametrize("length", [17, 24])
@pytest.mark.parametrize("wfrac", ["0", "1", "L/4", "L"])
def test_reference_equals_repro_brute_force(length, wfrac):
    from repro.search import brute_force, build_index

    w = {"0": 0, "1": 1, "L/4": length // 4, "L": length}[wfrac]
    rng = np.random.default_rng(length * 100 + w)
    store, queries = _walks(rng, 37, length), _walks(rng, 5, length)
    index = build_index(jnp.asarray(store), w, sketch=None)
    want_d, want_i = brute_force(index, queries, w, k=1, use_pallas=False)
    got_d, got_i = reference.nearest(queries, jnp.asarray(store), w,
                                     max_pairs=48)
    np.testing.assert_array_equal(got_i, np.asarray(want_i)[:, :1])
    np.testing.assert_allclose(got_d, np.asarray(want_d)[:, :1], rtol=1e-6)
    at = reference.distances_to(queries, jnp.asarray(store), got_i, w)
    np.testing.assert_array_equal(at, got_d)


@pytest.mark.parametrize("max_pairs", [48, 4096])
def test_reference_k_nearest_equal_repro_brute_force(max_pairs):
    from repro.search import brute_force, build_index

    rng = np.random.default_rng(41)
    store, queries = _walks(rng, 53, 20), _walks(rng, 6, 20)
    index = build_index(jnp.asarray(store), 5, sketch=None)
    want_d, want_i = brute_force(index, queries, 5, k=4, use_pallas=False)
    got_d, got_i = reference.nearest(queries, jnp.asarray(store), 5, k=4,
                                     max_pairs=max_pairs)
    np.testing.assert_array_equal(got_i, np.asarray(want_i))
    np.testing.assert_allclose(got_d, np.asarray(want_d), rtol=1e-6)
    at = reference.distances_to(queries, jnp.asarray(store), got_i, 5)
    np.testing.assert_array_equal(at, got_d)


def test_distances_to_marks_a_missing_answer():
    rng = np.random.default_rng(3)
    store, q = _walks(rng, 8, 16), _walks(rng, 2, 16)
    d = reference.distances_to(q, jnp.asarray(store), np.array([3, -1]), 4)
    assert np.isfinite(d[0]) and d[1] == np.inf


@pytest.mark.parametrize("name", ["reference.py", "check.py"])
def test_reference_imports_nothing_of_the_program(name):
    tree = ast.parse((BENCH / name).read_text())
    mods = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            mods |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            mods.add(node.module or "")
    assert not any(m == "repro" or m.startswith("repro.") for m in mods)
