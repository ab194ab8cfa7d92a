"""``store: random_walk``: ``n_series`` z-normalised random walks of
``length``, generated on the device, one draw from the deployment's
``data_seed``."""

import jax

from bench import generators as gen


def make(cfg: dict) -> gen.Data:
    store = gen.random_walks(gen.seed_key(cfg["data_seed"], "store"),
                             cfg["n_series"], cfg["length"])
    return gen.Data(store=jax.block_until_ready(store), labels=None,
                    test=None)
