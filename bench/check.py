"""The comparison that decides ``correct``.

Each checked answer is one query's k served neighbour ids and distances,
ascending.  The plain reference (``reference.py``, float32) scans the
whole store for that query.  Each rank j of an answer has two gaps,
relative to the reference's j-th nearest distance:

  dist_gap  |served distance j - reference distance j|
  id_gap    |reference DTW to served id j - reference distance j|

and the number compared, ``answer_gap``, is the largest of these over
every rank, worst over the checked answers: the served ids must be the
k nearest neighbours and the served distances theirs.  An id served
twice in one answer reads +inf.  (The bfloat16 control keeps the
nearest id on some random-walk seeds while missing its distance, so
``id_gap`` alone separates nothing there; together they separate in
every cell.)  An answer that never came (a negative id, a non-finite
distance) reads +inf.  The limit is in ``limits/<cell>.json``.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from bench import reference

NUMBERS = ("answer_gap",)


def gaps(served_d, served_i, ref_d, ref_d_at_served):
    """Per-answer ``(dist_gap, id_gap)`` arrays, worst over the ranks;
    every argument is ``(S, k)``."""
    served_d = np.asarray(served_d, np.float64)
    served_i = np.asarray(served_i)
    ref_d = np.asarray(ref_d, np.float64)
    den = np.maximum(np.abs(ref_d), 1e-30)
    ordered = np.sort(served_i, axis=1)
    distinct = np.all(ordered[:, 1:] != ordered[:, :-1], axis=1)
    came = (np.isfinite(served_d) & (served_i >= 0)
            & distinct[:, None])
    dist = np.where(came, np.abs(served_d - ref_d) / den, np.inf)
    at = np.asarray(ref_d_at_served, np.float64)
    ids = np.where(came, np.abs(at - ref_d) / den, np.inf)
    return dist.max(axis=1), ids.max(axis=1)


def compare_with_reference(queries, store, w: int, served_d, served_i, *,
                           max_pairs: int = 65536):
    """The numbers compared, and each answer's ``answer_gap``; also the
    worst ``dist_gap`` and ``id_gap``, which are shown but not compared.
    ``served_d`` and ``served_i`` are ``(S, k)``."""
    served_i = np.asarray(served_i)
    ref_d, _ = reference.nearest(queries, store, w, k=served_i.shape[1],
                                 max_pairs=max_pairs)
    at = reference.distances_to(queries, store, served_i, w)
    dist, ids = gaps(served_d, served_i, ref_d, at)
    per_answer = np.maximum(dist, ids)
    numbers = {"answer_gap": float(np.max(per_answer))}
    parts = {"dist_gap": float(np.max(dist)), "id_gap": float(np.max(ids))}
    return numbers, per_answer, parts


def control_answers(queries, store, w: int, *, k: int = 1,
                    max_pairs: int = 65536):
    """The control: the reference in the program's place, one precision
    step down (bfloat16 for the float32 that the deployments state)."""
    return reference.nearest(queries, store, w, k=k, dtype=jnp.bfloat16,
                             max_pairs=max_pairs)


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each number beside its limit."""
    checks = {}
    correct = True
    for name in NUMBERS:
        value, limit = numbers[name], float(limits[name])
        checks[name] = {"value": value if math.isfinite(value) else None,
                        "limit": limit}
        correct = correct and math.isfinite(value) and value <= limit
    return correct, checks
