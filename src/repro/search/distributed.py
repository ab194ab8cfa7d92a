"""Distributed NN-DTW search over a (pod, data, model) device mesh.

Sharding contract (DESIGN.md SS6):
  * the candidate store is sharded along its N axis over the *data* axes
    (``('data',)`` single-pod, ``('pod', 'data')`` multi-pod) — this is the
    axis that grows with corpus size, the paper's scaling bottleneck;
  * the query batch is sharded over the *model* axis — queries are
    independent, so this is embarrassing parallelism;
  * each device runs the full tier pipeline + verification engine on its
    local shard, then the per-query top-k candidates are merged with a
    single ``all_gather`` over the data axes (k * n_data_shards values per
    query — tiny compared to the local work it summarises).

Global survivor budget (``global_budget=True``): the tier pipeline's
compaction is per shard, and a purely *local* budget distributes pairwise
refinement uniformly on skewed stores — a shard holding none of a query's
plausible neighbours gets exactly as much bound tightening as the shard
holding all of them, so the shard that decides the query's fate may enter
verification with bounds far looser than the fleet could afford it.  The
global budget reuses the pipeline's compaction primitive
(cascade.run_plan + pipeline.Compaction.limit_fn) with a policy that spans
the mesh:

  1. each shard computes its all-pairs (tier-0/1) bounds locally and
     ``all_gather``s two per-query scalars over the data axes: its k-th
     smallest cheap bound, and its survivor *mass* — how many local
     candidates beat the tightest shard's k-th minimum;
  2. the uniform total budget ``D * B`` is split per query in proportion
     to shard mass (float ceil share, clamped to the static packed width
     ``2 * B``), so the shard that holds the real neighbourhood refines
     up to twice the uniform share while empty shards drop to the floor;
  3. each shard's packed pairwise batch then flows through the existing
     ``lb_enhanced_pairwise`` layout unchanged — the allocation is a
     per-query *refine limit* over the packed slots, not a new shape.

Shapes stay trace-static — every shard's packed batch is the same
``2 * B`` width — but the allocation is now *work*, not just tightness:
the executor threads each query's refine limit into the pairwise tier as
a per-slot ``live`` mask, and the kernel skips fully-dead pair tiles
outright (the same SMEM-flag liveness mechanism the DTW tiles use — see
kernels/lb_enhanced_pairwise.py), so a light shard's unallocated slots
cost neither FLOPs nor bound tightness (they keep their tier-0/1 bound —
still a valid lower bound, so exactness of the merged result never
depends on the policy; tested against single-device brute force on
skewed shards).  The remaining savings land downstream, where tighter
bounds on the heavy shard mean fewer DTW verifications and earlier
kernel abandons.

The communication volume is O(Q * shards) scalars for the budget exchange
plus O(Q * k * shards) floats for the top-k merge — independent of both N
and L — so the collective roofline term stays negligible at any corpus
size (quantified in EXPERIMENTS.md SSRoofline).

``make_distributed_search`` returns the step under ``jax.jit``: one
program per mesh, its verification ``while_loop`` included.
``tests/test_distributed.py`` pins the jitted step against brute force
on the (4, 2), N=256, L=128, k=3 shape that jax 0.4.x miscompiled.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.search import guards as _guards
from repro.search.engine import EngineConfig, nn_search
from repro.search.index import DTWIndex
from repro.search.pipeline import (
    Compaction,
    TierStats,
    VerificationPlan,
    default_plan,
    dense_plan,
)

Array = jax.Array


def _combined_axis_index(axes: Sequence[str]) -> Array:
    idx = lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx


def global_budget_limit_fn(axes: tuple[str, ...]):
    """Compaction ``limit_fn`` allocating one global budget across shards.

    Returns a traceable ``(lb01, budget, k) -> (Q,)`` callback for use
    *inside* ``shard_map`` over ``axes``: all-gathers each shard's
    per-query k-th smallest tier-0/1 bound, takes the tightest shard's
    value as the survivor threshold, all-gathers the per-shard survivor
    mass under that threshold, and returns this shard's mass-proportional
    share of the global ``D * budget`` (ceil division; ``run_plan`` clamps
    it into ``[k, 2 * budget]``).  Excluded candidates arrive as +inf in
    ``lb01`` and never count toward mass.
    """

    def limit_fn(lb01: Array, budget: int, k: int) -> Array:
        n_local = lb01.shape[1]
        kq = max(1, min(k, n_local))
        neg, _ = lax.top_k(-lb01, kq)
        kth_local = -neg[:, kq - 1]                    # (Q,) local k-th min
        kth_all = lax.all_gather(kth_local, axes)      # (D, Q)
        theta = jnp.min(kth_all, axis=0)               # tightest shard's
        mass_local = jnp.sum(lb01 <= theta[:, None], axis=1)    # (Q,)
        mass_all = lax.all_gather(mass_local, axes)    # (D, Q)
        total = jnp.maximum(jnp.sum(mass_all, axis=0), 1)
        n_shards = mass_all.shape[0]
        # float share: the integer product n_shards * budget * mass would
        # wrap int32 at production scale (256 data shards x budget 1024 x
        # ~1e5 survivors) and pin the heaviest shard to the floor; the
        # fraction is exact enough and run_plan clamps the result anyway
        frac = mass_local.astype(jnp.float32) / total.astype(jnp.float32)
        want = jnp.ceil(float(n_shards * budget) * frac)
        return want.astype(jnp.int32)

    return limit_fn


def _default_distributed_plan(
    cfg: EngineConfig,
    axes: tuple[str, ...],
    global_budget: bool,
) -> VerificationPlan:
    plan = (
        default_plan(cfg.cascade) if cfg.cascade.staged
        else dense_plan(cfg.cascade)
    )
    if global_budget and cfg.cascade.staged:
        plan = dataclasses.replace(
            plan, compaction=Compaction(limit_fn=global_budget_limit_fn(axes))
        )
    return plan


def gather_tier_stats(
    stats: TierStats,
    data_axes: tuple[str, ...],
    query_axis: str | None = None,
) -> TierStats:
    """Merge shard-local ``TierStats`` into one fleet measurement.

    For use *inside* ``shard_map`` (the same collective machinery as
    ``global_budget_limit_fn``): per-tier mass/scored/work and the pair
    count are summed over every shard (candidate partitions over the data
    axes, disjoint query blocks over ``query_axis``), the query count over
    the query axis only, and the per-query survivor counts are
    max-reduced — the committed refine limit must cover the *heaviest*
    shard's measured need, not the fleet average.  After the merge every
    shard holds the same global measurement, so every shard derives the
    same plan decision — one committed plan for the fleet.
    """
    daxes = tuple(data_axes)
    axes = daxes + ((query_axis,) if query_axis is not None else ())
    surv = lax.pmax(stats.survivors, daxes)
    if query_axis is not None:
        surv = lax.pmax(jnp.max(surv, keepdims=True), query_axis)
    return dataclasses.replace(
        stats,
        mass=lax.psum(stats.mass, axes),
        scored=lax.psum(stats.scored, axes),
        work=lax.psum(stats.work, axes),
        pairs=lax.psum(stats.pairs, axes),
        queries=(
            lax.psum(stats.queries, (query_axis,))
            if query_axis is not None else stats.queries
        ),
        survivors=surv,
    )


def calibrate_distributed_plan(
    mesh: Mesh,
    cfg: EngineConfig,
    series, labels, upper, lower, kim, kim_ok, queries,
    *,
    data_axes: tuple[str, ...] = ("data",),
    query_axis: str = "model",
    global_budget: bool = True,
    sample: int = 8,
    pcfg=None,
) -> "PlanDecision":
    """Measure the base plan across the mesh and derive one global plan.

    The distributed calibrate-then-commit: every shard runs the
    instrumented executor on a ``sample``-query block of its local query
    shard against its local candidate shard, the shard measurements are
    ``psum``/``pmax``-merged over the mesh (``gather_tier_stats`` — the
    ``global_budget_limit_fn`` gather machinery applied to stats), and the
    host turns the *global* measurement into a single ``PlanDecision``.
    Because the merged stats are identical on every shard, the decision is
    too: pass ``decision.plan`` to ``make_distributed_search(plan=...)``
    and all shards commit to the same rewritten plan, with the planner's
    refine limit composed into the global-budget allocation
    (``limit = min(mass-proportional share, committed cap)``).

    Takes the sharded index leaves + queries the search step itself takes.
    Calibration cost: one instrumented bound pass + ``sample * k`` seed
    DTWs per shard, paid once per (store, config).
    """
    from repro.search.cascade import run_plan
    from repro.search.planner import calibration_sample, optimise_plan

    axes = tuple(data_axes)
    base = _default_distributed_plan(cfg, axes, global_budget)
    k = cfg.k
    n_data_shards = 1
    for a in axes:
        n_data_shards *= mesh.shape[a]

    def probe(series, labels, upper, lower, kim, kim_ok, queries):
        index = DTWIndex(
            series=series, labels=labels, upper=upper, lower=lower,
            kim=kim, kim_ok=kim_ok, w=cfg.cascade.w,
        )
        # strided local sample (static indices): every region of a
        # class-ordered query shard lands in the measurement
        qs = queries[calibration_sample(queries.shape[0], sample)]
        cres = run_plan(qs, index, cfg.cascade, base, k=k,
                        collect_stats=True)
        st = gather_tier_stats(cres.stats, axes, query_axis)
        return (st.mass, st.scored, st.work, st.pairs[None],
                st.queries[None], st.survivors)

    in_specs = (
        P(axes, None), P(axes), P(axes, None), P(axes, None),
        P(axes, None), P(axes, None), P(query_axis, None),
    )
    out_specs = (P(None), P(None), P(None), P(None), P(None), P(None))
    from repro.distributed.sharding import shard_map_compat
    probe_fn = shard_map_compat(
        probe, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
    )
    mass, scored, work, pairs, n_q, surv = probe_fn(
        series, labels, upper, lower, kim, kim_ok, queries
    )
    stats = TierStats(
        names=tuple(t.name for t in base.tiers),
        costs=tuple(t.cost for t in base.tiers),
        scopes=tuple(t.scope for t in base.tiers),
        mass=mass, scored=scored, work=work,
        pairs=pairs[0], queries=n_q[0], survivors=surv,
    )
    n_local = max(1, series.shape[0] // n_data_shards)
    return optimise_plan(
        base, stats, n=n_local, k=k,
        base_budget=cfg.cascade.budget(n_local, k), pcfg=pcfg,
    )


def _build_step(
    mesh: Mesh,
    cfg: EngineConfig,
    *,
    data_axes: tuple[str, ...] = ("data",),
    query_axis: str = "model",
    global_budget: bool = True,
    plan: VerificationPlan | None = None,
    with_guards: bool = False,
    with_sketch: bool = False,
):
    """The shard_map search step that ``make_distributed_search`` jits.

    ``with_sketch`` extends the leaf contract with the quantised sketch
    store (search/index.py): ``sk_lo``/``sk_hi`` row-shard along N like
    every per-candidate feature, ``sk_scale`` (a store-wide scalar)
    replicates, and the store-level candidate mask ``live`` vec-shards
    along N — the mask is per *candidate*, so each shard masks exactly
    its own rows and the top-k merge semantics are untouched (dead
    candidates keep their finite cheap-tier bounds; see cascade.run_plan).
    ``False`` (the default) keeps the historical 7-leaf shape that the
    subprocess repro scripts and every existing caller pin."""
    axes = tuple(data_axes)
    if plan is None:
        plan = _default_distributed_plan(cfg, axes, global_budget)
    gcfg = _guards.resolve_guards(cfg.guards)

    def local_step(series, labels, upper, lower, kim, kim_ok, queries,
                   sk_lo=None, sk_hi=None, sk_scale=None, live=None):
        index = DTWIndex(
            series=series, labels=labels, upper=upper, lower=lower,
            kim=kim, kim_ok=kim_ok, w=cfg.cascade.w,
            sk_lo=sk_lo, sk_hi=sk_hi, sk_scale=sk_scale, live=live,
        )
        res, grep = nn_search(index, queries, cfg, plan=plan,
                              with_guards=True)
        n_local = series.shape[0]
        gidx = res.idx + (_combined_axis_index(axes) * n_local).astype(jnp.int32)
        # merge local top-k across the data axes
        d_all = lax.all_gather(res.dists, axes)        # (D, Qloc, k)
        i_all = lax.all_gather(gidx, axes)
        hook = _guards.fault_hook("allgather_topk")
        if hook is not None:
            d_all = hook(d_all)
        if gcfg.enabled and gcfg.conservation:
            # shard-dropout echo check: this shard's own top-k must come
            # back intact from the gather — a dead or corrupted shard
            # loses candidates from every query's merge, silently
            mine = jnp.take(d_all, _combined_axis_index(axes), axis=0)
            lost = jnp.sum(
                jnp.any(mine != res.dists, axis=-1)
            ).astype(jnp.float32)
            grep = dataclasses.replace(
                grep,
                conserve_checked=grep.conserve_checked
                + float(res.dists.shape[0]),
                conserve_viol=grep.conserve_viol + lost,
            )
        d_flat = jnp.moveaxis(d_all, 0, 1).reshape(res.dists.shape[0], -1)
        i_flat = jnp.moveaxis(i_all, 0, 1).reshape(res.dists.shape[0], -1)
        k = res.dists.shape[1]
        neg, sel = lax.top_k(-d_flat, k)
        merged_d = -neg
        merged_i = jnp.take_along_axis(i_flat, sel, axis=1)
        n_dtw = lax.psum(res.n_dtw, axes)
        if not with_guards:
            return merged_d, merged_i, n_dtw
        # fleet-wide guard merge, TierStats-style: counts psum over the
        # whole mesh, the admissibility gap pmaxes; the flat vector form
        # crosses the out_specs as a plain replicated array
        gv = grep.to_vector()
        all_axes = axes + (query_axis,)
        merged = lax.psum(gv, all_axes)
        gap_i = _guards._VEC_FIELDS.index("admiss_gap")
        merged = merged.at[gap_i].set(lax.pmax(gv[gap_i], all_axes))
        return merged_d, merged_i, n_dtw, merged

    in_specs = (
        P(axes, None),   # series      (N, L)  sharded on N
        P(axes),         # labels      (N,)
        P(axes, None),   # upper       (N, L)
        P(axes, None),   # lower       (N, L)
        P(axes, None),   # kim         (N, 4)
        P(axes, None),   # kim_ok      (N, 2)
        P(query_axis, None),  # queries (Q, L) sharded on Q
    )
    if with_sketch:
        in_specs = in_specs + (
            P(axes, None),   # sk_lo    (N, S)  int8, sharded on N
            P(axes, None),   # sk_hi    (N, S)  int8, sharded on N
            P(),             # sk_scale ()      store-wide, replicated
            P(axes),         # live     (N,)    candidate mask, sharded
        )
    out_specs = (P(query_axis, None), P(query_axis, None), P(query_axis))
    if with_guards:
        out_specs = out_specs + (P(None),)     # replicated guard vector
    from repro.distributed.sharding import shard_map_compat
    return shard_map_compat(
        local_step, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
    )


def make_distributed_search(
    mesh: Mesh,
    cfg: EngineConfig,
    *,
    data_axes: tuple[str, ...] = ("data",),
    query_axis: str = "model",
    global_budget: bool = True,
    plan: VerificationPlan | None = None,
    with_guards: bool = False,
    with_sketch: bool = False,
):
    """Build the jitted distributed search step for ``mesh``.

    Returns ``step(series, labels, upper, lower, kim, kim_ok, queries)``
    mapping sharded index leaves + queries to ``(dists, idx, n_dtw)`` with
    the query axis sharded over ``query_axis``.  Candidate indices in the
    output are *global* (shard offset applied).

    ``with_sketch`` appends the quantised sketch leaves to the input
    contract — ``step(..., queries, sk_lo, sk_hi, sk_scale, live)`` — so
    a store built with ``build_index(sketch=..., mask=True)`` serves its
    tier-(-1) bounds and candidate mask across the fleet (``_build_step``
    documents the sharding; pass ``live = ones(N, bool)`` when the store
    has features but no mask).

    ``global_budget`` (staged cascades only) swaps the per-shard local
    survivor budget for the mass-proportional global allocation described
    in the module docstring; ``False`` keeps fully-local compaction.

    ``plan`` overrides the default tier plan on every shard — this is how
    a ``calibrate_distributed_plan`` decision commits: the calibrated
    plan already carries the composed global-budget/refine-limit
    compaction, so it is used as-is.

    ``with_guards`` appends the fleet-merged ``GuardReport`` *vector*
    (``GuardReport.from_vector`` restores the struct) as a fourth output:
    per-shard reports are psum/pmax-merged over the whole mesh inside the
    step, so every host sees one global report covering admissibility,
    conservation (including the shard-dropout echo check on the top-k
    all_gather), accounting, and finite gates.
    """
    return jax.jit(_build_step(
        mesh, cfg, data_axes=data_axes, query_axis=query_axis,
        global_budget=global_budget, plan=plan, with_guards=with_guards,
        with_sketch=with_sketch,
    ))


def shard_index(mesh: Mesh, index: DTWIndex, data_axes=("data",)) -> DTWIndex:
    """Device-put an index with its N axis sharded over the data axes.

    The sketch store shards like every other per-candidate feature:
    ``sk_lo``/``sk_hi`` rows over the data axes, the store-wide
    ``sk_scale`` replicated, and the candidate mask ``live`` as a sharded
    vector.  Absent leaves stay ``None``.
    """
    axes = tuple(data_axes)
    row = NamedSharding(mesh, P(axes, None))
    vec = NamedSharding(mesh, P(axes))
    rep = NamedSharding(mesh, P())
    return DTWIndex(
        series=jax.device_put(index.series, row),
        labels=jax.device_put(index.labels, vec),
        upper=jax.device_put(index.upper, row),
        lower=jax.device_put(index.lower, row),
        kim=jax.device_put(index.kim, row),
        kim_ok=jax.device_put(index.kim_ok, row),
        w=index.w,
        sk_lo=(None if index.sk_lo is None
               else jax.device_put(index.sk_lo, row)),
        sk_hi=(None if index.sk_hi is None
               else jax.device_put(index.sk_hi, row)),
        sk_scale=(None if index.sk_scale is None
                  else jax.device_put(index.sk_scale, rep)),
        live=(None if index.live is None
              else jax.device_put(index.live, vec)),
    )
