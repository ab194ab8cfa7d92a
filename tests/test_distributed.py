"""Distributed tests that need >1 device: run in subprocesses with
XLA_FLAGS host-device counts (the main pytest process must keep the real
single-device view for everything else)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=600,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_distributed_search_exact():
    _run("""
import numpy as np, jax, jax.numpy as jnp
from repro.data import make_dataset
from repro.search import (build_index, brute_force, EngineConfig, CascadeConfig,
                          make_distributed_search, shard_index)
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh((4, 2), ("data", "model"))
ds = make_dataset(n_classes=3, n_train_per_class=32, n_test_per_class=8,
                  length=64, seed=5)
idx = build_index(ds.x_train, 12, ds.y_train)
cfg = EngineConfig(cascade=CascadeConfig(w=12, v=4, candidate_chunk=32,
                                         use_pallas=False), verify_chunk=8, k=2)
sidx = shard_index(mesh, idx, ("data",))
step = make_distributed_search(mesh, cfg, data_axes=("data",), query_axis="model")
d, i, ndtw = step(sidx.series, sidx.labels, sidx.upper, sidx.lower,
                  sidx.kim, sidx.kim_ok, jnp.asarray(ds.x_test))
bd, _ = brute_force(idx, ds.x_test, 12, k=2, use_pallas=False)
assert np.allclose(np.array(d), np.array(bd), rtol=1e-4), "distributed != brute force"
print("OK")
""")


def test_distributed_global_budget_skewed_shards():
    """The global survivor budget: all shards exact on a store whose
    near-neighbour mass lives entirely in shard 0, and the allocation
    actually skews (shard 0 gets more than the uniform share, the far
    shards drop toward the floor)."""
    _run("""
import numpy as np, jax, jax.numpy as jnp
from jax import lax
from repro.data import make_dataset
from repro.search import (build_index, brute_force, EngineConfig, CascadeConfig,
                          make_distributed_search, shard_index)
from repro.search.distributed import global_budget_limit_fn
from repro.distributed.sharding import shard_map_compat
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh((4, 2), ("data", "model"))
rng = np.random.default_rng(7)
Q, L, N, w, k = 8, 64, 128, 12, 2
queries = rng.normal(size=(Q, L)).astype(np.float32)
near = np.repeat(queries, 4, axis=0) + 0.05 * rng.normal(size=(Q*4, L)).astype(np.float32)
far = 5.0 + rng.normal(size=(N - Q*4, L)).astype(np.float32)
series = np.concatenate([near, far], axis=0).astype(np.float32)
idx = build_index(series, w)
cfg = EngineConfig(cascade=CascadeConfig(w=w, v=4, candidate_chunk=32,
                                         use_pallas=False, survivor_budget=8),
                   verify_chunk=8, k=k)
sidx = shard_index(mesh, idx, ("data",))
step = make_distributed_search(mesh, cfg, data_axes=("data",),
                               query_axis="model", global_budget=True)
d, i, ndtw = step(sidx.series, sidx.labels, sidx.upper, sidx.lower,
                  sidx.kim, sidx.kim_ok, jnp.asarray(queries))
bd, _ = brute_force(idx, queries, w, k=k, use_pallas=False)
assert np.allclose(np.array(d), np.array(bd), rtol=1e-4), "global budget != brute force"
# probe the allocation itself: shard 0 (all the near mass) must be granted
# more packed-refine slots than the far shards
limit_fn = global_budget_limit_fn(("data",))
def probe(series, q):
    # squared-Euclidean distance as a stand-in cheap tier: the probe only
    # exercises the allocation mechanics, which need some per-pair proxy
    lb01 = jnp.sum((q[:, None, :] - series[None, :, :]) ** 2, axis=-1)
    return limit_fn(lb01, 8, k)[None]
probe_fn = shard_map_compat(probe, mesh=mesh,
                            in_specs=(P(("data",), None), P(None, None)),
                            out_specs=P(("data",), None))
limits = np.array(probe_fn(sidx.series, jnp.asarray(queries)))   # (4, Q)
assert limits[0].mean() > 8, f"skewed shard not over-allocated: {limits}"
assert limits[1:].mean() < 8, f"far shards not under-allocated: {limits}"
print("OK", limits.mean(axis=1))
""")


def test_distributed_calibrated_plan_exact_and_committed():
    """Distributed calibrate-then-commit: the shard-local TierStats are
    psum/pmax-merged over the mesh, the host derives one global plan, and
    the committed step stays exact vs single-device brute force on a
    skewed store — with the planner's refine limit composed into the
    global-budget allocation."""
    _run("""
import numpy as np, jax, jax.numpy as jnp
from repro.data import make_dataset
from repro.search import (build_index, brute_force, EngineConfig, CascadeConfig,
                          make_distributed_search, shard_index,
                          calibrate_distributed_plan)
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh((4, 2), ("data", "model"))
rng = np.random.default_rng(7)
Q, L, N, w, k = 8, 64, 128, 12, 2
queries = rng.normal(size=(Q, L)).astype(np.float32)
near = np.repeat(queries, 4, axis=0) + 0.05 * rng.normal(size=(Q*4, L)).astype(np.float32)
far = 5.0 + rng.normal(size=(N - Q*4, L)).astype(np.float32)
series = np.concatenate([near, far], axis=0).astype(np.float32)
idx = build_index(series, w)
cfg = EngineConfig(cascade=CascadeConfig(w=w, v=4, candidate_chunk=32,
                                         use_pallas=False, survivor_budget=8),
                   verify_chunk=8, k=k)
sidx = shard_index(mesh, idx, ("data",))
qj = jnp.asarray(queries)
dec = calibrate_distributed_plan(
    mesh, cfg, sidx.series, sidx.labels, sidx.upper, sidx.lower,
    sidx.kim, sidx.kim_ok, qj, data_axes=("data",), query_axis="model")
# the calibrated compaction still carries the global-budget policy
assert dec.plan.compaction.limit_fn is not None, "lost the global budget"
step = make_distributed_search(mesh, cfg, data_axes=("data",),
                               query_axis="model", plan=dec.plan)
d, i, ndtw = step(sidx.series, sidx.labels, sidx.upper, sidx.lower,
                  sidx.kim, sidx.kim_ok, qj)
bd, _ = brute_force(idx, queries, w, k=k, use_pallas=False)
assert np.allclose(np.array(d), np.array(bd), rtol=1e-4), "calibrated plan != brute force"
# the default-plan step on the same store: the committed plan may not
# verify more (conservative profile: only measured-idle work was cut)
step0 = make_distributed_search(mesh, cfg, data_axes=("data",),
                                query_axis="model")
d0, i0, ndtw0 = step0(sidx.series, sidx.labels, sidx.upper, sidx.lower,
                      sidx.kim, sidx.kim_ok, qj)
assert np.all(np.array(ndtw) <= np.array(ndtw0)), (np.array(ndtw), np.array(ndtw0))
print("OK", dec.summary())
""")


def test_distributed_sketch_masked_step_exact():
    """The sketch store crosses the mesh: sk_lo/sk_hi row-shard, sk_scale
    replicates, the store mask vec-shards — and the committed sketch step
    is exact vs single-device brute force while verifying no more than
    the sketchless step on the calibration queries (each shard masks only
    its own rows, so the top-k merge semantics are untouched)."""
    _run("""
import numpy as np, jax, jax.numpy as jnp
from repro.search import (build_index, brute_force, EngineConfig, CascadeConfig,
                          make_distributed_search, shard_index)
from repro.search.planner import calibration_sample
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh((4, 2), ("data", "model"))
rng = np.random.default_rng(7)
L, N, w, k = 64, 128, 12, 2
series = np.cumsum(rng.normal(size=(N, L)), axis=1).astype(np.float32)
cfg = EngineConfig(cascade=CascadeConfig(w=w, v=4, candidate_chunk=32,
                                         use_pallas=False, use_sketch=True),
                   verify_chunk=8, k=k)
idx = build_index(series, w, calibrate=cfg, mask=True)
assert idx.sk_lo is not None and idx.live is not None
sidx = shard_index(mesh, idx, ("data",))
assert sidx.sk_lo is not None and sidx.live is not None
pick = calibration_sample(N, 8)
qj = jnp.asarray(series[pick])
step = make_distributed_search(mesh, cfg, data_axes=("data",),
                               query_axis="model", with_sketch=True)
d, i, ndtw = step(sidx.series, sidx.labels, sidx.upper, sidx.lower,
                  sidx.kim, sidx.kim_ok, qj,
                  sidx.sk_lo, sidx.sk_hi, sidx.sk_scale, sidx.live)
bd, _ = brute_force(idx, series[pick], w, k=k, use_pallas=False)
assert np.allclose(np.array(d), np.array(bd), rtol=1e-4), "sketch step != brute force"
# sketchless baseline on the unchanged 7-leaf contract
cfg0 = EngineConfig(cascade=CascadeConfig(w=w, v=4, candidate_chunk=32,
                                          use_pallas=False),
                    verify_chunk=8, k=k)
idx0 = build_index(series, w, sketch=None)
sidx0 = shard_index(mesh, idx0, ("data",))
step0 = make_distributed_search(mesh, cfg0, data_axes=("data",),
                                query_axis="model")
d0, i0, ndtw0 = step0(sidx0.series, sidx0.labels, sidx0.upper, sidx0.lower,
                      sidx0.kim, sidx0.kim_ok, qj)
assert np.allclose(np.array(d0), np.array(bd), rtol=1e-4)
assert np.all(np.array(ndtw) <= np.array(ndtw0)), (np.array(ndtw), np.array(ndtw0))
print("OK", int(np.array(ndtw).sum()), "<=", int(np.array(ndtw0).sum()))
""")


def test_jitted_step_exact_on_pinned_repro():
    """The jitted distributed step equals brute force on the mesh/shape
    that jax 0.4.x miscompiled — (4, 2), N=256, L=128, k=3 — where
    ``jit(shard_map(while))`` silently dropped candidates.  The step is
    always jitted now, so this pins the installed jax to the fix."""
    _run("""
import numpy as np, jax, jax.numpy as jnp
from repro.data import make_dataset
from repro.search import (build_index, brute_force, EngineConfig, CascadeConfig,
                          make_distributed_search, shard_index)
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh((4, 2), ("data", "model"))
ds = make_dataset(n_classes=4, n_train_per_class=64, n_test_per_class=1,
                  length=128, seed=3)
idx = build_index(ds.x_train, 16, ds.y_train)   # N = 256, L = 128
cfg = EngineConfig(cascade=CascadeConfig(w=16, v=4, candidate_chunk=64,
                                         use_pallas=False), verify_chunk=8, k=3)
sidx = shard_index(mesh, idx, ("data",))
q = jnp.asarray(ds.x_test)
bd, bi = brute_force(idx, ds.x_test, 16, k=3, use_pallas=False)
step = make_distributed_search(mesh, cfg, data_axes=("data",),
                               query_axis="model")
d, i, _ = step(sidx.series, sidx.labels, sidx.upper, sidx.lower,
               sidx.kim, sidx.kim_ok, q)
assert np.allclose(np.array(d), np.array(bd), rtol=1e-4), "jitted step != brute force"
assert (np.sort(np.array(i), 1) == np.sort(np.array(bi), 1)).all()
print("OK | jax", jax.__version__)
""")


def test_distributed_search_multipod_axes():
    _run("""
import numpy as np, jax, jax.numpy as jnp
from repro.data import make_dataset
from repro.search import (build_index, brute_force, EngineConfig, CascadeConfig,
                          make_distributed_search, shard_index)
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh((2, 2, 2), ("pod", "data", "model"))
ds = make_dataset(n_classes=2, n_train_per_class=16, n_test_per_class=4,
                  length=32, seed=9)
idx = build_index(ds.x_train, 8, ds.y_train)
cfg = EngineConfig(cascade=CascadeConfig(w=8, v=4, candidate_chunk=16,
                                         use_pallas=False), verify_chunk=4, k=1)
sidx = shard_index(mesh, idx, ("pod", "data"))
step = make_distributed_search(mesh, cfg, data_axes=("pod", "data"),
                               query_axis="model")
d, i, n = step(sidx.series, sidx.labels, sidx.upper, sidx.lower,
               sidx.kim, sidx.kim_ok, jnp.asarray(ds.x_test))
bd, _ = brute_force(idx, ds.x_test, 8, k=1, use_pallas=False)
assert np.allclose(np.array(d), np.array(bd), rtol=1e-4)
print("OK")
""")


def test_sharded_train_step_runs():
    """A reduced model trains under a real (data, model) mesh with the
    production sharding rules; loss finite, params stay sharded."""
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.registry import ARCHS, reduced
from repro.distributed.sharding import AxisRules, param_shardings
from repro.models.model import LM
from repro.train import OptConfig, init_state, make_train_step
import dataclasses
from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh((2, 2), ("data", "model"))
rules = AxisRules()
r = reduced(ARCHS["qwen2-moe-a2.7b"])
r = dataclasses.replace(r, n_experts=8, top_k=2)
model = LM(cfg=r, mesh=mesh, dp_axes=("data",))
opt = OptConfig(lr=1e-3, warmup=1)
state = init_state(model, jax.random.PRNGKey(0), opt)
pspecs = param_shardings(r, mesh, rules, state.params)
state = dataclasses.replace(state, params=jax.device_put(state.params, pspecs))
step = jax.jit(make_train_step(model, opt))
B, S = 4, 16
batch = {
  "tokens": jax.device_put(jnp.zeros((B, S), jnp.int32),
                           NamedSharding(mesh, P("data", None))),
  "labels": jax.device_put(jnp.ones((B, S), jnp.int32),
                           NamedSharding(mesh, P("data", None))),
}
for _ in range(2):
    state, m = step(state, batch)
assert np.isfinite(float(m["loss"]))
print("OK", float(m["loss"]))
""")


def test_elastic_restart_reshard():
    """Save under a 4-device mesh, restore under a 2-device mesh."""
    _run("""
import os, tempfile
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train import save_checkpoint, restore_checkpoint
devs = jax.devices()
from repro.launch.mesh import make_host_mesh
m4 = make_host_mesh((4,), ("data",))
m2 = make_host_mesh((2,), ("data",))
x = jax.device_put(jnp.arange(16.0).reshape(8, 2),
                   NamedSharding(m4, P("data", None)))
with tempfile.TemporaryDirectory() as d:
    save_checkpoint(d, 3, {"x": x})
    like = jax.ShapeDtypeStruct((8, 2), jnp.float32)
    restored, _ = restore_checkpoint(
        d, {"x": jnp.zeros((8, 2))},
        shardings={"x": NamedSharding(m2, P("data", None))})
    assert np.allclose(np.array(restored["x"]), np.arange(16.0).reshape(8, 2))
    assert restored["x"].sharding.mesh.shape["data"] == 2
print("OK")
""")
