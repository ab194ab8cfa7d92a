"""``queries: test_split``: the held-out series of the deployment's test
split, as host arrays (a client sends them from the host)."""

from bench import generators as gen


def make(mix: dict, cfg: dict, data: gen.Data, seed: int):
    if data.test is None:
        raise ValueError("test_split queries need a store with a test split")
    return gen.PoolSource(data.test, mix["batch"], seed)
