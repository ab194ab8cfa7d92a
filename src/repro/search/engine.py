"""Exact NN-DTW search engine with lower-bound pruning.

TPU adaptation of the paper's sequential early-abandon NN loop
(DESIGN.md SS3): instead of visiting candidates one at a time, the engine

  1. computes per-pair lower bounds by executing the verification plan's
     tier pipeline (cascade.run_plan): all-pairs tiers -> compaction ->
     pairwise tiers -> k verified seeds (or the dense full-tier matrix
     when ``cascade.staged`` is off),
  2. warm-starts the per-query top-k from the verified seeds and sorts the
     remaining candidates by ascending bound (UCR-suite ordering),
  3. verifies banded DTW in fixed-size *rounds*, threading each query's
     current k-th best distance into the kernel's per-pair ``cutoff`` so
     hopeless lanes abandon early (PrunedDTW-style), and
  4. stops a query as soon as its k-th best verified DTW is <= the smallest
     unverified bound — an *exactness certificate*: no remaining candidate
     can displace the current top-k, because bounds never exceed true DTW.

Bound-ordered verification schedule (``plan.schedule == "bound"``): each
round's flat batch of (query, candidate) slots is argsorted ascending by
its tightest bound *before* packing into the DTW kernel's pair tiles; the
engine composes the permutation into its slot->row gathers and scatters
the (P,) results back (kernels/tiling.py — external callers get the same
packing via the ops' ``perm=`` gather), so downstream accounting sees the
original slot order.  The kernel's row-block early exit skips a tile's
remaining anti-diagonal blocks only when *every* lane in the tile is
abandoned — under the unsorted stripe packing a doomed pair almost always
shares its tile with a live one, so the exit rarely fires.  Sorting
clusters the doomed pairs (loosest bounds, Herrmann & Webb's early-abandon
ordering, arXiv:2102.05221) into the same tiles, converting the per-tile
exit into an effective per-pair early exit.  The permutation changes
*packing only*: per-lane DTW values are independent of tile composition,
so results are bit-identical and per-query ``n_dtw`` (computed in slot
order from the same values) is unchanged — property-tested against the
``"index"`` schedule and brute force.

The cutoff never changes results: a lane abandons only when its frontier
minimum proves the true distance exceeds the query's current k-th best, so
the abandoned candidate could not have entered the top-k anyway.

The result is exact (identical neighbours to brute force — property-tested)
and the number of verified candidates matches what the paper's pruning-power
metric counts: ``P = 1 - n_dtw / N``.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.kernels.ops import _interpret, dtw_band_op
from repro.kernels.ref import dtw_band_ref
from repro.kernels.tiling import sched_pair_tile, unpermute_pairs
from repro.search import guards as _g
from repro.search import planner as _planner
from repro.search.cascade import (
    CascadeConfig,
    compute_bounds,
    run_plan,
)
from repro.search.index import DTWIndex
from repro.search.pipeline import (
    TierStats,
    VerificationPlan,
    default_plan,
    dense_plan,
    resolve_adaptive_budget,
)
from repro.search.planner import PlannerConfig

Array = jax.Array

_INF = jnp.inf


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SearchResult:
    """Exact k-NN under DTW_w plus pruning accounting.

    Attributes:
      dists: (Q, k) squared-cost DTW distances, ascending.
      idx:   (Q, k) candidate indices into the store.
      n_dtw: (Q,) number of DTW verifications actually performed.
      lb:    (Q, N) the cascade bound matrix (for diagnostics/benchmarks).
    """

    dists: Array
    idx: Array
    n_dtw: Array
    lb: Array

    def pruning_power(self, n: int | None = None) -> Array:
        n = n if n is not None else self.lb.shape[1]
        return 1.0 - self.n_dtw / n


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine knobs on top of the cascade config.

    Attributes:
      cascade: the lower-bound cascade configuration.
      verify_chunk: DTW verifications per round (the TPU batch analogue of
        the paper's one-at-a-time loop; each round is one fused kernel
        launch of ``Q * verify_chunk`` banded-DTW lane problems).
      k: neighbours to return.
      auto_plan: calibrate-then-commit (staged cascades, concrete inputs
        only): a cold search runs its first query block under the base
        plan with the instrumented executor, hands the measured
        ``TierStats`` to the planner, and runs every remaining block —
        and every later search against the same store/config — under the
        committed optimised plan (search/planner.py).  Results are
        bit-equal by construction: the planner only removes bound work,
        and unrefined pairs keep a valid looser bound.  Under tracing the
        flag is inert (the base plan runs unchanged), like the adaptive
        budget.
      planner: decision thresholds for the commit (``None`` =
        ``PlannerConfig()`` defaults).
      guards: exactness-guard configuration (search/guards.py).  ``None``
        means the *default-on* ``GuardConfig()`` — admissibility spot
        checks, conservation, accounting and finite gates all run (their
        host sync is measured on the chip by the benchmark's
        ``guard_sync_ms_per_request.online``; see PERF.md and the
        ledger).  Pass ``GuardConfig(enabled=False)`` to opt out;
        ``REPRO_FORCE_GUARDS=1`` in the environment overrides everything
        on.
    """

    cascade: CascadeConfig
    verify_chunk: int = 32
    k: int = 1
    auto_plan: bool = False
    planner: PlannerConfig | None = None
    guards: _g.GuardConfig | None = None


@dataclasses.dataclass(frozen=True)
class SearchStats:
    """Public pruning report for one search (host-side).

    The paper's Fig.-style pruning-power readout as an API: which tiers
    the committed plan ran, what each measured tier bought (realised
    pruning mass vs cost-weighted work), what the planner decided, and
    what the engine verified.  Produced by ``nn_search(...,
    with_stats=True)``; ``table()`` renders the per-tier table the
    examples print.

    Attributes:
      tiers: the measured ``TierStats`` (base-plan pricing when the
        search calibrated, the executed plan's pricing otherwise).
      plan_tiers: committed tier names, in committed order.
      schedule: committed verification schedule.
      dropped: tiers the planner removed (empty without ``auto_plan``).
      budget / limit: committed compaction bucket / refine limit
        (``None`` = untouched).
      calibrated: whether a planner decision produced the committed plan.
      n_dtw: (Q,) DTW verifications per query.
      n: store size (the pruning-power denominator).
      guards: the merged ``GuardReport`` (cascade + engine) for the
        search, ``None`` when guards were disabled.
      degraded: whether a tripped guard forced the degradation-ladder
        fallback to reference brute force (the returned result is the
        fallback's).
    """

    tiers: TierStats
    plan_tiers: tuple[str, ...]
    schedule: str
    dropped: tuple[str, ...]
    budget: int | None
    limit: int | None
    calibrated: bool
    n_dtw: Array
    n: int
    guards: "_g.GuardReport | None" = None
    degraded: bool = False

    def pruning_power(self) -> Array:
        return 1.0 - np.asarray(self.n_dtw) / self.n

    def table(self) -> str:
        nd = np.asarray(self.n_dtw)
        lines = [self.tiers.table(), "-" * 78]
        commit = f"plan: {' -> '.join(self.plan_tiers) or '<no tiers>'} " \
                 f"[{self.schedule}]"
        if self.dropped:
            commit += f"   dropped: {', '.join(self.dropped)}"
        if self.budget is not None:
            commit += f"   budget={self.budget}"
        if self.limit is not None:
            commit += f"   limit={self.limit}"
        if self.calibrated:
            commit += "   (planner-committed)"
        lines.append(commit)
        lines.append(
            f"n_dtw: {int(nd.sum())} of {nd.size * self.n} pairs verified "
            f"(mean pruning power {float(np.mean(self.pruning_power())):.1%})"
        )
        if self.guards is not None:
            gline = self.guards.summary()
            if self.degraded:
                gline += "   [DEGRADED: reference brute force served]"
            lines.append(gline)
        return "\n".join(lines)


def _all_concrete(q: Array, index: DTWIndex,
                  exclude: Array | None) -> bool:
    """Whether every search input is a concrete (host) value.

    The one definition behind the host-only gates — the adaptive budget
    estimate, the planner's calibrate-then-commit and the bound pass's
    cached program — so they always defer under tracing together."""
    return not (
        isinstance(q, jax.core.Tracer)
        or isinstance(index.series, jax.core.Tracer)
        or isinstance(exclude, jax.core.Tracer)
    )


def _resolve_cascade(
    q: Array,
    index: DTWIndex,
    cascade: CascadeConfig,
    k: int,
    exclude: Array | None,
    plan: VerificationPlan,
) -> CascadeConfig:
    """Adaptive survivor budget: only on concrete (host) inputs — under
    jit/shard_map tracing the static bucketed rule applies unchanged."""
    if (
        cascade.staged
        and cascade.adaptive_budget
        and cascade.survivor_budget is None
        and plan.compaction.budget is None
        and _all_concrete(q, index, exclude)
    ):
        budget = resolve_adaptive_budget(q, index, cascade, k, exclude)
        return dataclasses.replace(cascade, survivor_budget=budget)
    return cascade


def nn_search(
    index: DTWIndex,
    queries: Array,
    cfg: EngineConfig,
    *,
    exclude: Array | None = None,
    plan: VerificationPlan | None = None,
    with_stats: bool = False,
    with_guards: bool = False,
    sanitize: bool = False,
):
    """Exact k-NN-DTW for a batch of queries.

    Args:
      index: candidate store (build_index).
      queries: (Q, L) query batch.
      cfg: engine config; ``cfg.cascade.w`` is the DTW window.
      exclude: optional (Q,) candidate index to exclude per query
        (leave-one-out evaluation).
      plan: verification plan (tier list + compaction policy + schedule);
        ``None`` uses ``pipeline.default_plan(cfg.cascade)``.  The
        distributed path passes a plan whose compaction ``limit_fn``
        allocates the global survivor budget.  With ``cfg.auto_plan``
        this is the *base* plan the calibration prices; the committed
        optimised plan is what most blocks actually run.
      with_stats: also return a ``SearchStats`` report (host-side only —
        staged cascades on concrete inputs).  Returns ``(SearchResult,
        SearchStats)`` instead of the bare result.
      with_guards: return ``(SearchResult, GuardReport)`` instead of the
        bare result — unlike ``with_stats`` this works under tracing
        (the report is a pytree of scalars), which is how the
        distributed step surfaces guard outcomes across ``shard_map``.
        Ignored when ``with_stats`` is set (the report rides on
        ``SearchStats.guards``).
      sanitize: input hygiene for *queries* on concrete inputs: without
        it a query batch containing NaN/Inf raises; with it the bad
        values are masked to the per-series finite mean, warned about,
        and counted into the guard report (guards.validate_series).
        Store-side hygiene belongs to ``build_index``.

    Degradation (see search/guards.py): when the engine's default-on
    guards trip on concrete inputs, the batch is re-served via reference
    brute force (jnp kernels, no bound pruning — a tripped guard means
    the bounds themselves are untrusted, so any pruned rerun could
    consult the same lie), a ``GuardWarning`` fires, and the incident is
    surfaced in ``SearchStats`` (``guards`` / ``degraded``).

    Calibrate-then-commit (``cfg.auto_plan``): a cold search runs its
    first ``cfg.planner.calibrate_block`` queries under the base plan
    with stats collection, the planner turns the measurement into a
    committed plan (drop / reorder / limit-mask — search/planner.py), and
    the rest of the batch plus every later search against this store and
    config runs the committed plan.  Neighbours are bit-equal to the
    base plan's by construction; only bound work changes.

    Each call is the span ``repro.nn_search``, its steps spans inside it
    (``repro.obs``; README, Observability).
    """
    with obs.span("nn_search"):
        q = jnp.asarray(queries, jnp.float32)
        hyg = None
        if not isinstance(q, jax.core.Tracer):
            with obs.span("nn_search.hygiene"):
                q, hyg = _g.validate_series(q, name="query", sanitize=sanitize)
        Q = q.shape[0]
        N = index.n
        k = min(cfg.k, N)
        cascade = cfg.cascade
        if plan is None:
            # dense engines bound every pair with the all-pairs tier list; a
            # staged default would smuggle pairwise tiers into a path that has
            # no compaction to feed them (compute_bounds rejects that loudly)
            plan = default_plan(cascade) if cascade.staged \
                else dense_plan(cascade)
        concrete = _all_concrete(q, index, exclude)
        if with_stats and not (cascade.staged and concrete):
            raise ValueError(
                "with_stats is a host-side report over the staged tier "
                "pipeline: it needs cascade.staged=True and concrete inputs"
            )

        pcfg = cfg.planner if cfg.planner is not None else PlannerConfig()
        decision = None
        stats = None
        if cfg.auto_plan and cascade.staged and concrete and Q > 0:
            with obs.span("nn_search.plan"):
                decision = _planner.lookup_plan(index, cascade, k, plan, pcfg)
            if decision is not None:
                # committed: the whole batch runs the optimised plan
                res, _, guard = _search(index, q, cfg, plan=decision.plan,
                                        exclude=exclude)
                stats = decision.stats
            else:
                # calibrate: a strided query block runs the full base plan
                # (its bound pass doubles as the measurement), the rest of
                # the batch commits.  The stride keeps class-ordered batches
                # honest — a contiguous prefix can miss whole classes and
                # mis-price every tier (planner.calibration_sample).
                with obs.span("nn_search.plan"):
                    pick = _planner.calibration_sample(Q, pcfg.calibrate_block)
                    rest = np.setdiff1d(np.arange(Q), pick)
                    qa = q[pick]
                    ex_a = None if exclude is None else exclude[pick]
                    cascade_a = _resolve_cascade(qa, index, cascade, k, ex_a,
                                                 plan)
                res_a, stats, guard = _search(index, qa, cfg, plan=plan,
                                              exclude=ex_a, cascade=cascade_a,
                                              collect_stats=True)
                with obs.span("nn_search.plan"):
                    decision = _planner.optimise_plan(
                        plan, stats, n=N, k=k,
                        base_budget=_planner.base_budget_for(
                            index, cascade_a, k, plan),
                        pcfg=pcfg,
                    )
                    _planner.commit_plan(index, cascade, k, plan, decision,
                                         pcfg)
                if rest.size:
                    ex_b = None if exclude is None else exclude[rest]
                    res_b, _, guard_b = _search(index, q[rest], cfg,
                                                plan=decision.plan,
                                                exclude=ex_b)
                    if guard is not None and guard_b is not None:
                        with obs.span("nn_search.guards"):
                            guard = guard.merge(guard_b)
                    inv = jnp.asarray(np.argsort(np.concatenate([pick, rest])))
                    res = SearchResult(
                        dists=jnp.concatenate([res_a.dists, res_b.dists])[inv],
                        idx=jnp.concatenate([res_a.idx, res_b.idx])[inv],
                        n_dtw=jnp.concatenate([res_a.n_dtw, res_b.n_dtw])[inv],
                        lb=jnp.concatenate([res_a.lb, res_b.lb])[inv],
                    )
                else:
                    res = res_a
            committed = decision.plan
        else:
            res, stats, guard = _search(index, q, cfg, plan=plan,
                                        exclude=exclude,
                                        collect_stats=with_stats)
            committed = plan

        # ---- degradation ladder layer 2 (search/guards.py) -----------------
        # a tripped admissibility / conservation / accounting / NaN-DTW guard
        # means *neither the bounds nor the compiled verification path* can
        # be trusted for this batch — pruning with a lying bound silently
        # loses neighbours, and re-running the same cascade would consult the
        # same lie.  The only sound serve is full verification: reference
        # brute force (jnp kernels, no bound pruning, no Pallas dispatch),
        # with the incident surfaced.  Host-side only — tripped() syncs.
        with obs.span("nn_search.guards"):
            gcfg = _g.resolve_guards(cfg.guards)
            if hyg is not None and hyg.any() and guard is not None:
                guard = guard.merge(_g.hygiene_to_report(hyg))
            trip = (
                ", ".join(guard.tripped())
                if guard is not None and gcfg.enabled and gcfg.degrade
                and concrete else ""
            )
        degraded = False
        if trip:
            warnings.warn(
                f"exactness guards tripped ({trip}): serving this query "
                "batch via reference brute force (jnp kernels, bounds "
                "untrusted); see SearchStats.guards",
                _g.GuardWarning,
                stacklevel=2,
            )
            with obs.span("nn_search.degrade"):
                bf_d, bf_i = brute_force(index, q, cascade.w, k=k,
                                         exclude=exclude, use_pallas=False)
                res = SearchResult(
                    dists=bf_d, idx=bf_i,
                    n_dtw=jnp.full((Q,), N, jnp.int32),
                    lb=res.lb,   # diagnostics only — untrusted via degraded
                )
                guard = dataclasses.replace(guard,
                                            degraded=guard.degraded + 1.0)
            degraded = True

        if not with_stats:
            if with_guards:
                return res, (guard if guard is not None
                             else _g.GuardReport.zeros())
            return res
        report = SearchStats(
            tiers=stats,
            plan_tiers=tuple(t.name for t in committed.tiers),
            schedule=committed.schedule,
            dropped=decision.dropped if decision is not None else (),
            budget=decision.budget if decision is not None else None,
            limit=decision.limit if decision is not None else None,
            calibrated=decision is not None,
            n_dtw=res.n_dtw,
            n=N,
            guards=guard,
            degraded=degraded,
        )
        return res, report


@dataclasses.dataclass(frozen=True)
class _LoopKey:
    """What the verification loop is traced from besides its arguments'
    shapes: one compiled program per key and shapes (``_verify_loop``).

    The fault seams and the kernels' interpret mode are read while the
    loop is traced, so they key the program too (hooks by identity): an
    injected fault never runs a clean program, nor a clean call a faulty
    one."""

    k: int
    P: int
    T_max: int
    max_rounds: int
    w: int
    bound_sched: bool
    round_tile: int | None
    dtw_fn: Callable
    guards: bool
    finite_gates: bool
    admissibility: bool
    accounting: bool
    rtol: float
    atol: float
    engine_count: Callable | None
    dtw_out: Callable | None
    interpret: bool | None


@dataclasses.dataclass(frozen=True)
class _BoundsKey:
    """What the bound pass is traced from besides its arguments' shapes:
    one compiled program per key and shapes (``_bound_pass``).

    The committed plan (its tiers' functions and its compaction's
    ``limit_fn`` by identity), the budget-resolved cascade config with
    its survivor-budget bucket, and the guard config; the fault seams the
    pass reads while traced, by identity, so an injected fault never runs
    a clean program; and the kernels' interpret mode."""

    cascade: CascadeConfig
    plan: VerificationPlan
    k: int
    dtw_fn: Callable
    collect_stats: bool
    guards: _g.GuardConfig
    faults: tuple
    interpret: bool | None


# the fault seams the bound pass reads while it is traced (the seeds' DTW
# op reads ``dtw_out``)
_BOUND_SEAMS = ("tier_out", "compaction_cand", "packed_rows", "dtw_out")


def _shapes(*arrays) -> tuple:
    leaves, tree = jax.tree_util.tree_flatten(arrays)
    return tree, tuple((tuple(x.shape), x.dtype) for x in leaves)


# warnings raised and ``repro.obs`` counts kept while a program was traced
# (a kernel's grid, a kernel fallback), by key and argument shapes:
# ``_call`` raises and counts them again on every call of the program
_traced: dict = {}
_warn_registry: dict = {}


def _program(body):
    """``body(key, *arrays)`` as one jitted program per static key and
    argument shapes, keeping what its tracing warned and counted for
    ``_call``."""

    def traced(key, *args):
        with warnings.catch_warnings(record=True) as caught, \
                obs.recording() as counts:
            warnings.simplefilter("always")
            out = body(key, *args)
        _traced[key, _shapes(*args)] = (caught, counts)
        return out

    return jax.jit(functools.wraps(body)(traced), static_argnums=0)


def _call(program, key, *args):
    """Run a cached ``_program``, raising the warnings its tracing raised
    (a kernel fallback among them) and counting the ops its tracing
    recorded, on every call."""
    out = program(key, *args)
    caught, counts = _traced.get((key, _shapes(*args)), ((), ()))
    for w in caught:
        warnings.warn_explicit(w.message, w.category, w.filename, w.lineno,
                               registry=_warn_registry)
    obs.replay(counts)
    return out


def _bounds(key: _BoundsKey, q, index, exclude):
    """The bound pass: ``run_plan`` for a staged cascade, the dense tier
    matrix otherwise."""
    if not key.cascade.staged:
        return compute_bounds(q, index, key.cascade, k=key.k, plan=key.plan)
    return run_plan(q, index, key.cascade, key.plan, k=key.k,
                    dtw_fn=key.dtw_fn, exclude=exclude,
                    collect_stats=key.collect_stats, guards=key.guards)


# Every array the pass reads is an argument, so the store is never a
# constant of the program.
_bound_pass = _program(_bounds)


@_program
def _verify_loop(key: _LoopKey, q, series, order, slb, slb_pad, state):
    """Bound-ordered rounds of banded DTW until every query certifies.

    Every array the loop reads is an argument, so no store or bound
    matrix is embedded in the program.  Returns ``(rounds, best_d,
    best_i, n_dtw, guard accumulators)``.
    """
    Q = q.shape[0]
    N = series.shape[0]
    k, P, T_max, w = key.k, key.P, key.T_max, key.w
    dtw_fn = key.dtw_fn
    qarange = jnp.arange(Q)
    jarange = jnp.arange(P)

    def body(state):
        r, best_d, best_i, n_dtw, cursor, done, gacc = state
        n_un = jnp.maximum(jnp.sum(~done), 1)
        quota = jnp.minimum(P // n_un, T_max)             # ranks per query
        qorder = jnp.argsort(done)                        # undone first
        pos = jnp.argsort(qorder)                         # query -> stripe
        qi = qorder[jarange % n_un]                       # (P,) slot query
        stripe = jarange // n_un
        rank = cursor[qi] + stripe
        valid = (~done[qi]) & (rank < N) & (stripe < quota)
        rank_c = jnp.minimum(rank, N - 1)
        cidx = order[qi, rank_c]                          # candidate ids
        # exactly-+inf-sorted ranks are masked-out entries (verified
        # seeds / excluded candidates) — never re-verify them, or their
        # results would duplicate existing top-k members.  Only +inf is
        # an intentional mask: NaN or -inf there means a poisoned bound,
        # and those candidates must STAY eligible so a bad bound
        # degrades to verification (safe) instead of silent exclusion
        # (wrong answers) — guards.verification_eligible
        valid = valid & _g.verification_eligible(slb[qi, rank_c])
        lbv = jnp.where(valid, slb[qi, rank_c], _INF)
        kth0 = best_d[:, k - 1]
        # thread each query's current k-th best into the kernel's per-pair
        # early-abandon cutoff: lanes that cannot beat it return +inf
        if key.bound_sched:
            # bound-ordered packing: argsort the flat batch ascending by
            # its tightest bound so the loosest (most-doomed) pairs share
            # pair tiles; invalid slots sort last (+inf bound) and get a
            # -inf cutoff so they die at the first block boundary instead
            # of pinning their tile's liveness flag.  The permutation is
            # composed into the slot->row index gathers (one (P, L)
            # gather per operand, same packing the ops' ``perm=`` gather
            # would produce) and inverted on the (P,) output — everything
            # below sees the original slot order.
            perm = jnp.argsort(lbv)
            cut = jnp.where(valid, kth0[qi], -_INF)[perm]
            dp = dtw_fn(q[qi[perm]], series[cidx[perm]], w, cut,
                        tile_p=key.round_tile)
            d = unpermute_pairs(perm, dp)                 # (P,) flat
        else:
            # round_tile is None here unless the plan pinned verify_tile_p
            d = dtw_fn(q[qi], series[cidx], w, kth0[qi],
                       tile_p=key.round_tile)             # (P,)
        z32 = jnp.zeros((), jnp.float32)
        a_chk = a_vio = a_gap = acc_chk = acc_vio = nf_dtw = z32
        if key.guards and key.finite_gates:
            # a NaN verification value would poison the top-k merge:
            # gate it to +inf (cannot enter the top-k) and count it —
            # nn_search's degradation decides whether +inf was safe
            d, nf_dtw = _g.finite_gate_dtw(d, valid=valid)
        d = jnp.where(valid, d, _INF)
        if key.guards and key.admissibility:
            # every verified lane doubles as an admissibility sample:
            # its tier bound must not exceed its exact DTW
            a_chk, a_vio, a_gap = _g.admissibility_check(
                lbv, d, key.rtol, key.atol, valid=valid
            )
        # per-query gather of this round's results (stripe layout)
        t = jnp.arange(T_max)
        slots = pos[:, None] + t[None, :] * n_un          # (Q, T_max)
        ok = (t[None, :] < quota) & (slots < P)
        slots_c = jnp.minimum(slots, P - 1)
        gd = jnp.where(ok & (qi[slots_c] == qarange[:, None]),
                       d[slots_c], _INF)
        gi = cidx[slots_c]
        alld = jnp.concatenate([best_d, gd], axis=1)
        alli = jnp.concatenate([best_i, gi], axis=1)
        neg, sel = lax.top_k(-alld, k)
        best_d = -neg
        best_i = jnp.take_along_axis(alli, sel, axis=1)
        # semantic count (the paper's pruning-power numerator): a slot is a
        # *necessary* verification if its bound still beats the post-round
        # k-th best (the sequential loop could not have skipped it) or it
        # entered the top-k.  Counting against the pre-round k-th best
        # would charge slots the sequential loop skips once the earlier
        # candidates of the same round have updated the running best.
        kth1 = best_d[:, k - 1]
        active = valid & ((lbv < kth1[qi]) | (d <= kth1[qi]))
        inc = active.astype(jnp.int32)
        seg = jax.ops.segment_sum(inc, qi, num_segments=Q)
        if key.engine_count is not None:
            seg = key.engine_count(seg)
        if key.guards and key.accounting:
            # the per-query scatter must conserve the flat liveness
            # mirror's total — a dropped or double-counted slot here is
            # the while-loop miscompile's accounting signature
            acc_chk = jnp.asarray(1.0, jnp.float32)
            acc_vio = (jnp.sum(seg) != jnp.sum(inc)).astype(jnp.float32)
        n_dtw = n_dtw + seg
        cursor = jnp.minimum(cursor + jnp.where(~done, quota, 0), N)
        next_lb = slb_pad[qarange, cursor]
        done = done | (best_d[:, k - 1] <= next_lb) | (cursor >= N)
        if key.guards:
            gacc = jnp.stack([
                gacc[0] + a_chk, gacc[1] + a_vio,
                jnp.maximum(gacc[2], a_gap),
                gacc[3] + acc_chk, gacc[4] + acc_vio,
                gacc[5] + nf_dtw,
            ])
        return r + 1, best_d, best_i, n_dtw, cursor, done, gacc

    def cond(state):
        r, _, _, _, _, done, _ = state
        return (r < key.max_rounds) & ~jnp.all(done)

    r, best_d, best_i, n_dtw, _, _, gacc = lax.while_loop(cond, body, state)
    return r, best_d, best_i, n_dtw, gacc


def _search(
    index: DTWIndex,
    queries: Array,
    cfg: EngineConfig,
    *,
    plan: VerificationPlan,
    exclude: Array | None = None,
    cascade: CascadeConfig | None = None,
    collect_stats: bool = False,
) -> tuple[SearchResult, TierStats | None, "_g.GuardReport | None"]:
    """One engine pass under one plan (the pre-planner ``nn_search`` body).

    ``cascade`` is the budget-resolved config (``None`` resolves here);
    ``collect_stats`` threads the instrumented executor through the bound
    pass and returns its ``TierStats`` alongside the result.  The third
    return is the merged cascade + engine ``GuardReport`` (``None`` when
    guards are disabled); the degradation decision belongs to
    ``nn_search``, not here.
    """
    q = jnp.asarray(queries, jnp.float32)
    Q, L = q.shape
    N = index.n
    k = min(cfg.k, N)
    M = min(cfg.verify_chunk, N)
    g = _g.resolve_guards(cfg.guards)
    gon = g.enabled
    if exclude is not None:
        exclude = jnp.asarray(exclude)
    with obs.span("engine.bounds"):
        if cascade is None:
            cascade = _resolve_cascade(q, index, cfg.cascade, k, exclude,
                                       plan)
        dtw_fn = dtw_band_op if cascade.use_pallas else dtw_band_ref
        bkey = _BoundsKey(
            cascade=cascade, plan=plan, k=k, dtw_fn=dtw_fn,
            collect_stats=collect_stats, guards=g,
            faults=tuple(_g.fault_hook(s) for s in _BOUND_SEAMS),
            interpret=_interpret() if cascade.use_pallas else None,
        )
        # concrete inputs run the cached program; traced ones (the
        # distributed step under shard_map, a caller's jit) trace the
        # pass inline into the caller's program
        if _all_concrete(q, index, exclude):
            obs.count("bound_pass", "program")
            bres = _call(_bound_pass, bkey, q, index, exclude)
        else:
            obs.count("bound_pass", "inline")
            bres = _bounds(bkey, q, index, exclude)
        if cascade.staged:
            cres = bres
        else:
            lb = bres
    w = cascade.w

    # ---- work-conserving flat verification scheduler -------------------
    # The naive per-query round scheme wastes whole rounds on finished
    # queries (one ambiguous straggler forces Q*M DTWs per extra round).
    # Instead each round builds a flat batch of P = Q*M (query, candidate)
    # slots striped over the *undone* queries only: every undone query
    # receives a uniform quota = min(P // n_undone, T_max) of its next
    # unverified ranks, so stragglers soak up the slots finished queries
    # no longer need (up to the static gather cap T_max = 8*M).  Total DTW
    # compute tracks the semantic verified count instead of rounds*Q*M.
    P = Q * M
    T_max = min(N, 8 * M)
    max_rounds = -(-Q * N // P) + 2
    bound_sched = plan.schedule == "bound"
    # per-round pair-tile sizing: bound-ordered rounds cluster their
    # doomed tail, so a smaller tile lands the kernel's liveness exit on
    # the cluster boundary (tiling.sched_pair_tile); the plan can pin an
    # explicit size.  Unsorted rounds keep the kernel default — geometry
    # only, results and n_dtw are invariant (see pipeline.py).
    round_tile = (
        plan.verify_tile_p if plan.verify_tile_p is not None
        else sched_pair_tile(P)
    ) if bound_sched else plan.verify_tile_p
    tier_stats = None
    guard0 = None
    with obs.span("engine.order"):
        qarange = jnp.arange(Q)
        if cascade.staged:
            tier_stats = cres.stats
            guard0 = cres.guard
            lb = cres.lb
            # seeds are already verified: warm-start the top-k with them
            # and drop them from the unverified ordering
            sel = jnp.argsort(cres.seed_d, axis=1)
            best_d0 = jnp.take_along_axis(cres.seed_d, sel, axis=1)
            best_i0 = jnp.take_along_axis(cres.seed_idx, sel, axis=1)
            n_dtw0 = jnp.full((Q,), k, jnp.int32)
            if gon and g.finite_gates:
                # a gated (+inf) seed was never really verified: leave its
                # bound in the ordering so the loop verifies the candidate
                # instead of losing it behind the seed mask
                cur = jnp.take_along_axis(lb, cres.seed_idx, axis=1)
                lb_order = lb.at[qarange[:, None], cres.seed_idx].set(
                    jnp.where(jnp.isfinite(cres.seed_d), _INF, cur)
                )
            else:
                lb_order = lb.at[qarange[:, None], cres.seed_idx].set(
                    _INF)
        else:
            best_d0 = jnp.full((Q, k), _INF, jnp.float32)
            best_i0 = jnp.full((Q, k), -1, jnp.int32)
            n_dtw0 = jnp.zeros((Q,), jnp.int32)
            lb_order = lb
        if exclude is not None:
            lb = lb.at[qarange, exclude].set(_INF)
            lb_order = lb_order.at[qarange, exclude].set(_INF)

        order = jnp.argsort(lb_order, axis=1)                 # (Q, N)
        slb = jnp.take_along_axis(lb_order, order, axis=1)
        slb_pad = jnp.pad(slb, ((0, 0), (0, 1)), constant_values=_INF)
        # queries whose seeded k-th best already certifies against the
        # smallest unverified bound never enter the loop
        done0 = best_d0[:, k - 1] <= slb_pad[:, 0]
        state = (
            jnp.int32(0),
            best_d0,
            best_i0,
            n_dtw0,
            jnp.zeros((Q,), jnp.int32),
            done0,
            jnp.zeros((6,), jnp.float32),
        )

    key = _LoopKey(
        k=k, P=P, T_max=T_max, max_rounds=max_rounds, w=w,
        bound_sched=bound_sched, round_tile=round_tile, dtw_fn=dtw_fn,
        guards=gon, finite_gates=g.finite_gates,
        admissibility=g.admissibility, accounting=g.accounting,
        rtol=g.rtol, atol=g.atol,
        engine_count=_g.fault_hook("engine_count"),
        dtw_out=_g.fault_hook("dtw_out"),
        interpret=_interpret() if dtw_fn is dtw_band_op else None,
    )
    with obs.span("engine.verify"):
        r, best_d, best_i, n_dtw, gacc = _call(
            _verify_loop, key, q, index.series, order, slb, slb_pad, state)
        obs.count_rounds(r)
    guard = None
    if gon:
        # the loop's guard counters merge into the cascade's report
        with obs.span("nn_search.guards"):
            guard = dataclasses.replace(
                _g.GuardReport.zeros(),
                admiss_checked=gacc[0], admiss_viol=gacc[1],
                admiss_gap=gacc[2],
                account_checked=gacc[3], account_viol=gacc[4],
                nonfinite_dtw=gacc[5],
            )
            if g.accounting:
                # end-of-search bounds: every query verified at least its
                # seeds (staged) and never more than the whole store
                floor = k if cascade.staged else 0
                bv = jnp.sum((n_dtw > N) | (n_dtw < floor)).astype(
                    jnp.float32)
                guard = dataclasses.replace(
                    guard,
                    account_checked=guard.account_checked + float(Q),
                    account_viol=guard.account_viol + bv,
                )
            if guard0 is not None:
                guard = guard0.merge(guard)
    return SearchResult(dists=best_d, idx=best_i, n_dtw=n_dtw, lb=lb), \
        tier_stats, guard


def classify(
    index: DTWIndex,
    queries: Array,
    cfg: EngineConfig,
    *,
    exclude: Array | None = None,
) -> tuple[Array, SearchResult]:
    """k-NN-DTW classification: majority vote over the k neighbours."""
    res = nn_search(index, queries, cfg, exclude=exclude)
    votes = index.labels[res.idx]                                     # (Q, k)
    n_cls = int(jnp.max(index.labels)) + 1 if index.labels.size else 1
    counts = jax.vmap(
        lambda v: jnp.bincount(v, length=max(n_cls, 1))
    )(jnp.maximum(votes, 0))
    pred = jnp.argmax(counts, axis=1)
    return pred, res


def brute_force(
    index: DTWIndex, queries: Array, w: int, k: int = 1,
    *, exclude: Array | None = None, use_pallas: bool = True,
    chunk: int = 512,
) -> tuple[Array, Array]:
    """Unpruned exact k-NN (the O(N * L * W) baseline the paper speeds up).

    Chunked over candidates with a running top-k merge, so peak memory is
    O(Q * chunk * L) instead of the (Q*N, L) broadcast materialisation that
    OOMed at store scale (N=10k, L=2048).
    """
    q = jnp.asarray(queries, jnp.float32)
    Q, L = q.shape
    N = index.n
    k = min(k, N)
    chunk = min(chunk, N)
    dtw_fn = dtw_band_op if use_pallas else dtw_band_ref
    best_d = jnp.full((Q, k), _INF, jnp.float32)
    best_i = jnp.full((Q, k), -1, jnp.int32)
    for s in range(0, N, chunk):
        e = min(s + chunk, N)
        C = e - s
        qrep = jnp.repeat(q, C, axis=0)                  # (Q*C, L)
        crep = jnp.tile(index.series[s:e], (Q, 1))       # (Q*C, L)
        d = dtw_fn(qrep, crep, w).reshape(Q, C)
        ids = jnp.broadcast_to(jnp.arange(s, e, dtype=jnp.int32)[None], (Q, C))
        if exclude is not None:
            d = jnp.where(ids == exclude[:, None], _INF, d)
        alld = jnp.concatenate([best_d, d], axis=1)
        alli = jnp.concatenate([best_i, ids], axis=1)
        neg, sel = lax.top_k(-alld, k)
        best_d = -neg
        best_i = jnp.take_along_axis(alli, sel, axis=1)
    return best_d, best_i
