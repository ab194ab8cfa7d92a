"""``queries: store_noise``: store rows picked uniformly, plus Gaussian
noise of ``noise`` (sigma), re-z-normalised (query by example: each query
has a close true neighbour).  The pool, ``pool`` batches of them, is one
draw from the deployment's ``data_seed``."""

import jax
import jax.numpy as jnp

from bench import generators as gen


def make(mix: dict, cfg: dict, data: gen.Data, seed: int):
    n = mix["pool"] * mix["batch"]
    pool = gen.near_dups(gen.seed_key(cfg["data_seed"], "queries"),
                         data.store, jnp.float32(mix["noise"]),
                         jax.ShapeDtypeStruct((n, cfg["length"]),
                                              jnp.float32))
    return gen.PoolSource(jax.block_until_ready(pool), mix["batch"], seed)
