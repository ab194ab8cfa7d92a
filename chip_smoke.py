"""Smoke run of the exact NN-DTW search on a TPU.

One chip (the default): builds a store of 2^18 seeded random walks at
the paper's deployment width (L=512, w=154 = 0.3 L, the int8 sketch on,
the verification plan calibrated at build time), serves a query batch
through ``nn_search`` with the Pallas kernels, checks the answers of a
few queries against the unpruned jnp brute force, runs the streaming DTW
kernel at L=65536 against its jnp reference, and checks that the jitted
search step holds a ``tpu_custom_call`` for every kernel its plan serves.

``--chips 4`` runs only the sharded path instead: the full 2^20 store
sharded over a data=4 mesh of the host's chips, served by the jitted
``make_distributed_search`` step and compared with the brute force.

Every line but the last is a labelled smoke line: single-shot times that
show the path runs, not benchmark numbers.  The last line is one JSON
object, ``{"ok": true, "device": {...}}``.  The script exits non-zero,
without that line, when JAX finds no TPU, when a result disagrees with
its reference, or when a guard trips, a search degrades, or a kernel op
falls back to its jnp reference.

Run from the repository root: ``python chip_smoke.py [--chips 4]``.
"""

from __future__ import annotations

import argparse
import collections
import json
import re
import sys
import time
import warnings
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# the kernel each plan tier dispatches to (kim is plain jnp)
KERNEL_OF_TIER = {
    "sketch": "sketch_bound_pallas",
    "bands": "lb_enhanced_pallas",
    "enhanced_dense": "lb_enhanced_pallas",
    "enhanced_pairwise": "lb_enhanced_pairwise_pallas",
}


class SmokeFailure(RuntimeError):
    """A phase's result disagreed with its reference or a guard fired."""


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def random_walks(key, n: int, length: int):
    """``(n, length)`` z-normalised random walks, generated on device."""
    import jax
    import jax.numpy as jnp

    x = jnp.cumsum(jax.random.normal(key, (n, length), jnp.float32), axis=1)
    x = x - jnp.mean(x, axis=1, keepdims=True)
    return x / jnp.std(x, axis=1, keepdims=True)


def served_kernels(hlo_text: str) -> collections.Counter:
    """Pallas kernels in a compiled program, by name: every
    ``tpu_custom_call`` names its kernel's jitted wrapper in its
    ``op_name`` metadata (``.../jit(dtw_band_pallas)/pallas_call``)."""
    found = collections.Counter()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r"jit\((\w+_pallas)\)[^\"]*pallas_call", line)
            found[m.group(1) if m else "<unnamed>"] += 1
    return found


def expected_kernels(plan) -> set[str]:
    """The kernels a search under ``plan`` must run: one per bound tier
    that has a kernel, and the banded DTW that verifies."""
    names = {KERNEL_OF_TIER[t.name] for t in plan.tiers
             if t.name in KERNEL_OF_TIER}
    return names | {"dtw_band_pallas"}


def check_kernels(compiled_text: str, want: set[str], what: str) -> None:
    found = served_kernels(compiled_text)
    log(f"{what}: tpu_custom_call per kernel {dict(sorted(found.items()))}")
    missing = sorted(want - set(found))
    if missing:
        raise SmokeFailure(f"{what}: no tpu_custom_call for {missing}")


def check_guards(report, what: str) -> None:
    tripped = report.tripped()
    log(f"{what}: {report.summary()}")
    if tripped:
        raise SmokeFailure(f"{what}: guards tripped: {', '.join(tripped)}")


def check_neighbours(dists, idx, ref_d, ref_i, what: str,
                     rtol: float = 1e-5) -> None:
    """Same neighbours as the reference, distances to float tolerance."""
    import numpy as np

    dists, idx = np.asarray(dists), np.asarray(idx)
    ref_d, ref_i = np.asarray(ref_d), np.asarray(ref_i)
    err = float(np.max(np.abs(dists - ref_d) / np.maximum(np.abs(ref_d),
                                                          1e-30)))
    same = bool(np.array_equal(idx, ref_i))
    log(f"{what}: {idx.shape[0]} queries, neighbours equal: {same}, "
        f"max relative distance error {err:.3g}")
    if not same or not err <= rtol:
        raise SmokeFailure(f"{what}: differs from the reference")


def timed(fn, *args):
    import jax

    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t


def engine_config(w: int, candidate_chunk: int, verify_chunk: int):
    from repro.search import CascadeConfig, EngineConfig

    return EngineConfig(
        cascade=CascadeConfig(w=w, v=4, use_pallas=True, use_sketch=True,
                              candidate_chunk=candidate_chunk),
        verify_chunk=verify_chunk, k=1, auto_plan=True,
    )


def search_phase(*, n: int, length: int, w: int, n_queries: int,
                 n_check: int, seed: int = 0, candidate_chunk: int = 1024,
                 verify_chunk: int = 32, ref_chunk: int = 4096) -> dict:
    """Build, serve and check one store on one device (module docstring).
    The ``tpu_custom_call`` check runs only on a TPU: the CPU interprets
    the kernels."""
    import jax

    from repro.search import brute_force, build_index, default_plan, \
        nn_search
    from repro.search import planner as plr

    cfg = engine_config(w, candidate_chunk, verify_chunk)
    k_store, k_query = jax.random.split(jax.random.PRNGKey(seed))
    store = jax.block_until_ready(random_walks(k_store, n, length))
    queries = jax.block_until_ready(random_walks(k_query, n_queries, length))

    index, t_build = timed(
        lambda s: build_index(s, w, calibrate=cfg, sketch=16), store)
    decision = plr.lookup_plan(index, cfg.cascade, cfg.k,
                               default_plan(cfg.cascade))
    if decision is None:
        raise SmokeFailure("build_index committed no calibrated plan")
    log(f"build: N={n} L={length} w={w} sketch S=16, {t_build:.3f} s; "
        f"calibrated plan: {decision.summary()}")

    (res, stats), t_cold = timed(
        lambda q: nn_search(index, q, cfg, with_stats=True), queries)
    log(f"nn_search (first call, compiles included): Q={n_queries} "
        f"{t_cold:.3f} s; mean pruning power "
        f"{float(stats.pruning_power().mean()):.4f}")
    check_guards(stats.guards, "nn_search guards")
    if stats.degraded:
        raise SmokeFailure("nn_search degraded to the reference brute force")

    def step(idx, q):
        return nn_search(idx, q, cfg, plan=decision.plan, with_guards=True)

    t = time.perf_counter()
    compiled = jax.jit(step).lower(index, queries).compile()
    t_compile = time.perf_counter() - t
    log(f"jitted search step compile: {t_compile:.3f} s")
    if jax.devices()[0].platform == "tpu":
        check_kernels(compiled.as_text(), expected_kernels(decision.plan),
                      "jitted search step")
        build_text = jax.jit(
            lambda s: build_index(s, w, sketch=16)).lower(store).compile()
        check_kernels(build_text.as_text(), {"envelope_pallas"},
                      "jitted build")
    (res_j, guard_j), t_steady = timed(compiled, index, queries)
    log(f"jitted search step (compiled, single shot): Q={n_queries} "
        f"{t_steady:.3f} s")
    check_guards(guard_j, "jitted step guards")
    check_neighbours(res_j.dists, res_j.idx, res.dists, res.idx,
                     "jitted step vs nn_search")

    (ref_d, ref_i), t_ref = timed(
        lambda q: brute_force(index, q, w, k=1, use_pallas=False,
                              chunk=ref_chunk), queries[:n_check])
    log(f"reference brute force (jnp, unpruned): {n_check} queries "
        f"{t_ref:.3f} s")
    check_neighbours(res.dists[:n_check], res.idx[:n_check], ref_d, ref_i,
                     "nn_search vs reference brute force")
    return {"build_s": t_build, "compile_s": t_compile,
            "steady_s": t_steady}


def stream_phase(*, length: int, w: int, n_pairs: int, seed: int = 1) -> dict:
    """The streaming DTW kernel (operands in HBM) against its reference,
    with and without per-pair early-abandon cutoffs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.kernels.ref import dtw_band_ref

    if length <= ops._DTW_RESIDENT_MAX_L:
        raise ValueError(f"L={length} is not past the residency crossover")
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    a = random_walks(ka, n_pairs, length)
    b = random_walks(kb, n_pairs, length)
    got, t_kernel = timed(lambda x, y: ops.dtw_band_op(x, y, w), a, b)
    want, t_ref = timed(lambda x, y: dtw_band_ref(x, y, w), a, b)
    got, want = np.asarray(got), np.asarray(want)
    err = float(np.max(np.abs(got - want) / np.abs(want)))
    log(f"stream dtw_band: L={length} w={w} P={n_pairs}: kernel "
        f"{t_kernel:.3f} s, reference {t_ref:.3f} s, max relative error "
        f"{err:.3g}")
    if not err <= 1e-5:
        raise SmokeFailure("streaming dtw_band differs from the reference")
    # half the pairs get a cutoff below their distance: those must
    # abandon (>= cutoff), the rest must stay exact
    tight = np.arange(n_pairs) % 2 == 0
    cut = np.where(tight, 0.5 * want, 2.0 * want).astype(np.float32)
    got_c = np.asarray(ops.dtw_band_op(a, b, w, jnp.asarray(cut)))
    ok = bool(np.all(got_c[tight] >= cut[tight])
              and np.allclose(got_c[~tight], want[~tight], rtol=1e-5))
    log(f"stream dtw_band with cutoffs: abandoned {int(tight.sum())} "
        f"pairs, exact on the rest: {ok}")
    if not ok:
        raise SmokeFailure("streaming dtw_band cutoff semantics broken")
    return {"stream_s": t_kernel}


def _sharded_brute_force(sidx, queries, w: int, chunk: int):
    """``brute_force(use_pallas=False)`` over a row-sharded index: each
    device scans its own rows (dispatch is asynchronous, so the devices
    run together), then the per-shard nearest neighbours merge on the
    host."""
    import jax
    import numpy as np

    from repro.search import DTWIndex, brute_force

    leaves = ("series", "labels", "upper", "lower", "kim", "kim_ok")
    shards = zip(*(getattr(sidx, f).addressable_shards for f in leaves))
    parts = []
    for shard in shards:
        local = DTWIndex(**{f: s.data for f, s in zip(leaves, shard)},
                         w=sidx.w)
        q = jax.device_put(queries, shard[0].device)
        d, i = brute_force(local, q, w, k=1, use_pallas=False, chunk=chunk)
        parts.append((d, i + (shard[0].index[0].start or 0)))
    d = np.concatenate([np.asarray(p[0]) for p in parts], axis=1)
    i = np.concatenate([np.asarray(p[1]) for p in parts], axis=1)
    best = np.argmin(d, axis=1)[:, None]
    return (np.take_along_axis(d, best, axis=1),
            np.take_along_axis(i, best, axis=1))


def sharded_phase(*, n: int, length: int, w: int, n_queries: int,
                  n_check: int, seed: int = 0, candidate_chunk: int = 1024,
                  verify_chunk: int = 32, ref_chunk: int = 4096) -> dict:
    """The store sharded over every device (data axis), served by the
    jitted distributed step and checked against the brute force."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_host_mesh
    from repro.search import (GuardReport, build_index,
                              make_distributed_search, shard_index)

    n_dev = len(jax.devices())
    mesh = make_host_mesh((n_dev, 1), ("data", "model"))
    cfg = engine_config(w, candidate_chunk, verify_chunk)
    k_store, k_query = jax.random.split(jax.random.PRNGKey(seed))
    store = random_walks(k_store, n, length)
    queries = jax.block_until_ready(random_walks(k_query, n_queries, length))

    def build(s):
        idx = build_index(s, w, sketch=16)
        return shard_index(mesh, idx, ("data",))

    sidx, t_build = timed(build, store)
    del store
    live = jnp.ones((n,), bool)
    log(f"sharded build: N={n} over data={n_dev}, L={length} w={w}, "
        f"{t_build:.3f} s")
    step = make_distributed_search(mesh, cfg, data_axes=("data",),
                                   query_axis="model", with_guards=True,
                                   with_sketch=True)
    args = (sidx.series, sidx.labels, sidx.upper, sidx.lower, sidx.kim,
            sidx.kim_ok, queries, sidx.sk_lo, sidx.sk_hi, sidx.sk_scale,
            jax.device_put(live, sidx.labels.sharding))
    t = time.perf_counter()
    compiled = step.lower(*args).compile()
    t_compile = time.perf_counter() - t
    log(f"distributed step compile: {t_compile:.3f} s")
    if jax.devices()[0].platform == "tpu":
        from repro.search.pipeline import default_plan

        check_kernels(compiled.as_text(), expected_kernels(
            default_plan(cfg.cascade)), "distributed step")
    (d, i, n_dtw, gv), t_steady = timed(compiled, *args)
    log(f"distributed step (compiled, single shot): Q={n_queries} "
        f"{t_steady:.3f} s; mean verified per query "
        f"{float(jnp.mean(n_dtw.astype(jnp.float32))):.1f} of {n}")
    check_guards(GuardReport.from_vector(gv), "distributed step guards")
    (ref_d, ref_i), t_ref = timed(
        lambda q: _sharded_brute_force(sidx, q, w, ref_chunk),
        queries[:n_check])
    log(f"reference brute force (jnp, unpruned, per shard): {n_check} "
        f"queries {t_ref:.3f} s")
    check_neighbours(d[:n_check], i[:n_check], ref_d, ref_i,
                     "distributed step vs reference brute force")
    return {"build_s": t_build, "compile_s": t_compile,
            "steady_s": t_steady}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not (Path(__file__).resolve().parent / "src" / "repro").is_dir():
        log("no src/repro beside this script: run it from a checkout")
        return 2

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"no TPU: JAX found {dev.platform!r} devices; nothing to smoke")
        return 2
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} chips, found "
            f"{len(devices)}")
        return 2

    from repro.kernels.ops import KernelFallbackWarning
    from repro.launch.cache import enable_compile_cache
    from repro.search import GuardWarning

    log(f"device: {dev.device_kind} x{len(devices)}; compile cache "
        f"{enable_compile_cache()}")
    log("smoke run: single-shot times that show the path runs; "
        "not benchmark numbers")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        try:
            if args.chips == 4:
                # Q=128: at 256 the sharded step's unrolled chunk loops
                # ask ~16.7 GiB per chip; 4 checks, as the per-shard
                # references run one after another
                sharded_phase(n=2**20, length=512, w=154, n_queries=128,
                              n_check=4)
            else:
                search_phase(n=2**18, length=512, w=154, n_queries=256,
                             n_check=8)
                stream_phase(length=65536, w=655, n_pairs=64)
        except SmokeFailure as e:
            log(f"FAILED: {e}")
            return 1
    loud = [w for w in caught
            if issubclass(w.category, (GuardWarning, KernelFallbackWarning))]
    for w in loud:
        log(f"{w.category.__name__}: {w.message}")
    if loud:
        log("FAILED: guard or kernel-fallback warnings fired")
        return 1
    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        log(f"peak bytes in use on {d}: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
