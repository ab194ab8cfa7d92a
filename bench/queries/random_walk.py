"""``queries: random_walk``: z-normalised random walks, independent of
the store (the paper's protocol).  The pool, ``pool`` batches of them,
is one draw from the deployment's ``data_seed``."""

import jax

from bench import generators as gen


def make(mix: dict, cfg: dict, data: gen.Data, seed: int):
    pool = gen.random_walks(gen.seed_key(cfg["data_seed"], "queries"),
                            mix["pool"] * mix["batch"], cfg["length"])
    return gen.PoolSource(jax.block_until_ready(pool), mix["batch"], seed)
