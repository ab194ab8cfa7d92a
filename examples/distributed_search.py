"""Distributed NN-DTW search over a (data, model) mesh.

The mesh is built from the devices present: every device holds a slice
of the candidate store ('data'), and the queries stay whole ('model' has
size 1) — one four-chip TPU host gives a data=4 mesh.  The candidate
store is sharded over 'data', queries over 'model'; each
device runs the local tier pipeline — with the *global survivor budget*
(the default: per-shard compaction limits allocated in proportion to
all-gathered tier-0/1 survivor mass, see search/distributed.py) — and the
per-query top-k merges with one all_gather.

Run: PYTHONPATH=src python examples/distributed_search.py
On a CPU host, XLA_FLAGS=--xla_force_host_platform_device_count=4 gives
four emulated devices (the kernels are interpreted there).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import make_dataset
from repro.kernels.ref import dtw_band_ref
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.search import (
    CascadeConfig,
    EngineConfig,
    build_index,
    make_distributed_search,
    shard_index,
)


def main() -> None:
    enable_compile_cache()
    mesh = make_host_mesh((len(jax.devices()), 1), ("data", "model"))
    print(f"mesh: {dict(mesh.shape)} over {mesh.size} devices")

    ds = make_dataset(n_classes=4, n_train_per_class=64, n_test_per_class=8,
                      length=128, seed=13)
    w = int(0.2 * ds.length)
    idx = build_index(ds.x_train, w, ds.y_train)
    cfg = EngineConfig(
        cascade=CascadeConfig(w=w, v=4),
        verify_chunk=16, k=3,
    )
    sidx = shard_index(mesh, idx, ("data",))
    step = make_distributed_search(mesh, cfg, data_axes=("data",),
                                   query_axis="model")

    q = jnp.asarray(ds.x_test)
    t0 = time.perf_counter()
    d, i, n_dtw = step(sidx.series, sidx.labels, sidx.upper, sidx.lower,
                       sidx.kim, sidx.kim_ok, q)
    jax.block_until_ready(d)
    dt = time.perf_counter() - t0

    # the jnp reference DTW over every pair: no kernel shared with the step
    Q, N = q.shape[0], idx.n
    ref_d = dtw_band_ref(jnp.repeat(q, N, axis=0),
                         jnp.tile(idx.series, (Q, 1)), w).reshape(Q, N)
    bd = np.sort(np.array(ref_d), axis=1)[:, :3]
    exact = np.allclose(np.array(d), bd, rtol=1e-4)
    print(f"3-NN over {idx.n} candidates x {q.shape[0]} queries: {dt:.2f}s")
    print(f"exact vs the jnp reference DTW over every pair: {exact}")
    print(f"mean DTW verified per query (all shards): "
          f"{float(np.mean(np.asarray(n_dtw))):.1f} / {idx.n}")
    votes = np.array(idx.labels)[np.array(i)]
    pred = np.apply_along_axis(lambda r: np.bincount(r).argmax(), 1, votes)
    print(f"accuracy: {float(np.mean(pred == ds.y_test)):.1%}")


if __name__ == "__main__":
    main()
