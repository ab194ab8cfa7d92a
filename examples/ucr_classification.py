"""End-to-end driver: NN-DTW time-series classification with the
LB_ENHANCED cascade (the paper's headline application, SS IV-B).

Builds a UCR-like dataset, indexes the training set *with store-level
plan calibration* (the planner prices every tier on a sample of the
store and commits the optimised verification plan — search/planner.py),
classifies the test set with the committed plan + exact verification,
and reports accuracy, the paper's Fig.-style per-tier pruning-power
table, and timing vs the unpruned brute force.

Run: PYTHONPATH=src python examples/ucr_classification.py [--window 0.2]

The Pallas kernels serve both the cascade and the brute force: compiled
on a TPU, interpreted (slowly, for exactness only) on a CPU.  Exactness
is checked against the jnp reference DTW, not against the brute force.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import make_dataset
from repro.kernels.ref import dtw_band_ref
from repro.launch.cache import enable_compile_cache
from repro.search import (
    CascadeConfig,
    EngineConfig,
    brute_force,
    build_index,
    default_plan,
    nn_search,
)
from repro.search import planner as plr


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--window", type=float, default=0.2)
    ap.add_argument("--v", type=int, default=4)
    ap.add_argument("--length", type=int, default=64)
    ap.add_argument("--per-class", type=int, default=200,
                    help="the paper's regime is large N — pruning pays "
                         "off as the store grows")
    ap.add_argument("--n-test", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()

    ds = make_dataset(
        n_classes=5, n_train_per_class=args.per_class,
        n_test_per_class=args.n_test, length=args.length, seed=7,
    )
    w = max(1, int(args.window * ds.length))
    print(f"dataset: {ds.x_train.shape[0]} train / {ds.x_test.shape[0]} test, "
          f"L={ds.length}, W={w}, V={args.v}")

    cfg = EngineConfig(
        cascade=CascadeConfig(w=w, v=args.v),
        verify_chunk=64, k=1, auto_plan=True,
    )
    # store-level calibration: the planner prices the default plan on a
    # sample of the store itself and commits the optimised plan, so the
    # first real query batch below starts warm
    idx = build_index(ds.x_train, w, ds.y_train, calibrate=cfg)
    decision = plr.lookup_plan(idx, cfg.cascade, cfg.k,
                               default_plan(cfg.cascade))
    print(f"committed plan    : {decision.summary()}")

    # search the test set under the committed plan, with the pruning report
    res, stats = nn_search(idx, ds.x_test, cfg, with_stats=True)
    votes = idx.labels[res.idx]                                    # (Q, k)
    pred = np.array(votes[:, 0])

    # jit + warm up both paths (the committed plan pinned explicitly —
    # calibration is host-side, so a traced search runs the plan it is
    # given); report steady-state step time
    cascade_fn = jax.jit(
        lambda qq: nn_search(idx, qq, cfg, plan=decision.plan).dists
    )
    brute_fn = jax.jit(
        lambda qq: brute_force(idx, qq, w, k=1)[0]
    )
    qj = jnp.asarray(ds.x_test)
    jax.block_until_ready(cascade_fn(qj))
    jax.block_until_ready(brute_fn(qj))

    t0 = time.perf_counter()
    jax.block_until_ready(cascade_fn(qj))
    t_cascade = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(brute_fn(qj))
    t_brute = time.perf_counter() - t0

    acc = float(np.mean(pred == ds.y_test))
    prune = float(np.mean(np.array(res.pruning_power())))
    # checked against the jnp reference DTW over every pair, which shares
    # no kernel with the cascade or the timed brute force
    Q, N = qj.shape[0], idx.n
    ref_d = dtw_band_ref(jnp.repeat(qj, N, axis=0),
                         jnp.tile(idx.series, (Q, 1)), w).reshape(Q, N)
    assert np.allclose(np.array(res.dists[:, 0]),
                       np.min(np.array(ref_d), axis=1), rtol=1e-4), \
        "cascade changed the NN result!"

    print()
    print(stats.table())       # the paper's pruning-power readout, per tier
    print()
    print(f"accuracy          : {acc:.1%}")
    print(f"pruning power     : {prune:.1%} of DTW computations skipped")
    print(f"mean DTW verified : {float(np.mean(np.asarray(res.n_dtw))):.1f} "
          f"of {idx.n} candidates")
    print(f"cascade time      : {t_cascade:.2f}s   brute force: {t_brute:.2f}s "
          f"({t_brute / t_cascade:.1f}x speedup, identical results)")
    # the default-on exactness guards (search/guards.py): admissibility /
    # conservation / accounting counters for this search, plus whether
    # the degradation ladder had to serve a brute-force fallback
    if stats.guards is not None:
        verdict = "tripped: " + ", ".join(stats.guards.tripped()) \
            if stats.guards.tripped() else "all clear"
        print(f"exactness guards  : {verdict}"
              + ("   [DEGRADED]" if stats.degraded else ""))
        print(f"                    {stats.guards.summary()}")


if __name__ == "__main__":
    main()
