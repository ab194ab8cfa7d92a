"""``store: ucr_synthetic``: warped-prototype classes at a UCR dataset's
shape, one draw from the deployment's ``data_seed``; the train split is
the store, the test split what the traffic sends."""

import jax
import jax.numpy as jnp

from bench import generators as gen


def make(cfg: dict) -> gen.Data:
    sp = gen.ucr_split(gen.seed_rng(cfg["data_seed"], "store"),
                       n_classes=cfg["n_classes"],
                       n_train=cfg["n_series"], n_test=cfg["n_test"],
                       length=cfg["length"], warp=cfg["warp"],
                       noise=cfg["noise"])
    store = jax.block_until_ready(jnp.asarray(sp.x_train))
    return gen.Data(store=store, labels=sp.y_train, test=sp.x_test)
