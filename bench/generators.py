"""Data and query generators: the data from a deployment's ``data_seed``,
the order of the traffic from the run's ``--seed``.

The random walks and the UCR-like classes are copies of the program's own
generators (``chip_smoke.random_walks`` and ``repro.data.synthetic``), kept
here so that a change to the program cannot change the yardstick.

Each deployment (``configs/<name>.json``) names a ``store`` kind and each
traffic mix (``traffic/<name>.json``) a ``queries`` kind: the modules
``stores/<kind>.py`` and ``queries/<kind>.py``, which build on this file.
"""

from __future__ import annotations

import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, salt: str):
    """A JAX key from all 64 bits of ``seed`` and a purpose ``salt``
    (``PRNGKey`` alone keeps only the low 32 bits)."""
    s = int(seed) % 2**64
    key = jax.random.PRNGKey(s & 0xFFFFFFFF)
    key = jax.random.fold_in(key, s >> 32)
    return jax.random.fold_in(key, zlib.crc32(salt.encode()))


def seed_rng(seed: int, salt: str) -> np.random.Generator:
    """A numpy generator from ``seed`` and a purpose ``salt``."""
    return np.random.default_rng([int(seed) % 2**64,
                                  zlib.crc32(salt.encode())])


def _znorm_dev(x):
    x = x - jnp.mean(x, axis=1, keepdims=True)
    return x / jnp.std(x, axis=1, keepdims=True)


@jax.jit
def _walks(key, template):
    x = jnp.cumsum(jax.random.normal(key, template.shape, jnp.float32), axis=1)
    return _znorm_dev(x)


def random_walks(key, n: int, length: int):
    """``(n, length)`` z-normalised random walks, generated on device."""
    return _walks(key, jax.ShapeDtypeStruct((n, length), jnp.float32))


# ---------------------------------------------------------------------------
# UCR-like classes (copy of repro.data.synthetic, with exact split sizes)
# ---------------------------------------------------------------------------

def _smooth(x: np.ndarray, k: int) -> np.ndarray:
    return np.convolve(x, np.ones(k) / k, mode="same")


def _znorm(x: np.ndarray) -> np.ndarray:
    return (x - x.mean(-1, keepdims=True)) / (x.std(-1, keepdims=True) + 1e-8)


def _prototype(rng, L: int) -> np.ndarray:
    walk = np.cumsum(rng.normal(size=L + 16))
    return _znorm(_smooth(walk, 9)[8:8 + L])


def _warp(rng, proto: np.ndarray, strength: float) -> np.ndarray:
    """Random monotone time warp: resample through a jittered knot map."""
    L = len(proto)
    n_knots = 6
    knots_x = np.linspace(0, 1, n_knots)
    knots_y = knots_x + rng.normal(scale=strength / n_knots, size=n_knots)
    knots_y[0], knots_y[-1] = 0.0, 1.0
    knots_y = np.maximum.accumulate(knots_y)
    knots_y /= max(knots_y[-1], 1e-9)
    t = np.interp(np.linspace(0, 1, L), knots_x, knots_y)
    return np.interp(t * (L - 1), np.arange(L), proto)


@dataclasses.dataclass(frozen=True)
class Split:
    x_train: np.ndarray  # (N, L) float32, z-normalised
    y_train: np.ndarray  # (N,) int32
    x_test: np.ndarray   # (T, L)
    y_test: np.ndarray   # (T,)


def _per_class(total: int, n_classes: int) -> list[int]:
    base, extra = divmod(total, n_classes)
    return [base + (c < extra) for c in range(n_classes)]


def ucr_split(rng, *, n_classes: int, n_train: int, n_test: int,
              length: int, warp: float, noise: float) -> Split:
    """Warped-prototype classes: each instance is a time-warped copy of
    its class prototype with amplitude jitter and noise, z-normalised
    (the UCR convention)."""
    protos = [_prototype(rng, length) for _ in range(n_classes)]

    def sample(c: int) -> np.ndarray:
        x = _warp(rng, protos[c], warp)
        x = x * (1.0 + rng.normal(scale=0.1))
        x = x + rng.normal(scale=noise, size=length)
        return _znorm(x)

    xs_tr, ys_tr, xs_te, ys_te = [], [], [], []
    for c, (ntr, nte) in enumerate(zip(_per_class(n_train, n_classes),
                                       _per_class(n_test, n_classes))):
        xs_tr += [sample(c) for _ in range(ntr)]
        ys_tr += [c] * ntr
        xs_te += [sample(c) for _ in range(nte)]
        ys_te += [c] * nte
    perm = rng.permutation(len(xs_tr))
    return Split(
        x_train=np.asarray(xs_tr, np.float32)[perm],
        y_train=np.asarray(ys_tr, np.int32)[perm],
        x_test=np.asarray(xs_te, np.float32),
        y_test=np.asarray(ys_te, np.int32),
    )


# ---------------------------------------------------------------------------
# what the store and query modules share
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Data:
    store: jax.Array              # (N, L) float32 on device
    labels: np.ndarray | None
    test: np.ndarray | None       # (T, L) host queries of a held-out split


@jax.jit
def near_dups(key, store, sigma, template):
    """``template.shape[0]`` store rows picked uniformly, plus Gaussian
    noise of ``sigma``, re-z-normalised."""
    B, L = template.shape
    kr, kn = jax.random.split(key)
    rows = jax.random.randint(kr, (B,), 0, store.shape[0])
    noise = sigma * jax.random.normal(kn, (B, L), jnp.float32)
    return _znorm_dev(store[rows] + noise)


class PoolSource:
    """Requests of ``batch`` queries taken in turn from a fixed ``pool``
    of queries, in an order drawn from the run's seed: every seed sends
    the same queries, in another order.  ``batch(i)`` is a function of
    ``(seed, i)``; a device pool gives device batches, a host pool host
    batches (a client that sends them from the host)."""

    def __init__(self, pool, batch: int, seed: int):
        self.pool = pool
        self.b = batch
        self.order = seed_rng(seed, "queries").permutation(len(pool))

    def batch(self, i: int):
        n = len(self.order)
        rows = self.order[(i * self.b + np.arange(self.b)) % n]
        if isinstance(self.pool, np.ndarray):
            return self.pool[rows]
        return self.pool[jnp.asarray(rows, jnp.int32)]
