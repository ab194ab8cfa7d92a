"""``serve: nn_search``: the single-device search as users call it.

Build: ``build_index(store, w, labels, calibrate=cfg, sketch=S)`` with the
plan calibrated and committed at build.  Each request:
``nn_search(index, q, cfg, with_guards=True)``, eager, guards on.
"""

import jax
import jax.numpy as jnp

from bench.record import Answer


def engine_config(cfg: dict):
    from repro.search import CascadeConfig, EngineConfig

    return EngineConfig(
        cascade=CascadeConfig(
            w=cfg["w"], v=cfg["v"], use_pallas=True,
            use_sketch=cfg["sketch_segments"] is not None,
            candidate_chunk=cfg["candidate_chunk"]),
        verify_chunk=cfg["verify_chunk"], k=cfg["k"], auto_plan=True,
    )


def make(cfg: dict, data):
    """Build the index; return ``serve(q) -> Answer``."""
    from repro.search import build_index, nn_search

    ecfg = engine_config(cfg)
    labels = None if data.labels is None else jnp.asarray(data.labels)
    index = build_index(data.store, cfg["w"], labels, calibrate=ecfg,
                        sketch=cfg["sketch_segments"])
    jax.block_until_ready(index.series)

    def serve(q) -> Answer:
        res, guard = nn_search(index, q, ecfg, with_guards=True)
        return Answer(idx=res.idx, dists=res.dists, n_dtw=res.n_dtw,
                      degraded=guard.degraded)

    return serve
