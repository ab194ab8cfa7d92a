"""Tier pipeline: the declarative vocabulary of the lower-bound cascade.

The cascade used to be a hard-coded kim -> bands -> gather -> pairwise
chain baked into ``search/cascade.py``; adding a tier (a two-pass LB, a
second bands pass at a different ``V``) or reordering the existing ones
meant rewriting the cascade.  This module factors the chain into three
explicit, composable pieces (Lemire's two-pass argument, arXiv:0811.3301:
bound tiers should *compose*, the pipeline should not care which bounds it
is running):

  * ``BoundTier`` — one bound stage: a name, a *cost class* (documentation
    + bench label: "O(1)", "O(S)", "O(V^2)", "O(L)"), a *scope*, and the
    bound function itself.  ``all_pairs`` tiers produce a dense ``(Q, N)``
    matrix over every (query, candidate); ``pairwise`` tiers refine only
    the compacted survivor pack — packed ``(P, L)`` rows -> ``(P,)``
    bounds, the layout shared by the pairwise LB kernel, the engine's flat
    verification scheduler, and the DTW kernel's pair tiles.

Tier -1 (the ``sketch`` tier) extends the scope taxonomy in one direction
without changing it: it is an ``all_pairs`` tier like Kim, but it reads
the index's quantised *feature* store (``index.sk_lo/sk_hi``, int8 PAA
segment means — search/index.py), never the ``(N, L)`` series.  That is
the design point: every tier whose operand is the raw store is bounded by
store bandwidth at HBM scale, while a feature tier's operand is ~S bytes
per candidate and stays resident.  Sketch-scope rules: the tier must
score *every* candidate (the store-level ``live`` mask is derived FROM
its bounds, so it must not consume the mask), it prices as ``"O(S)"``,
and on an index built without features it returns the all-zero bound —
trivially admissible, measured idle, dropped by the planner — so plans
mentioning it compose with any index.
  * ``Compaction`` — the single pipeline stage between the all-pairs and
    pairwise tiers: gather the ``B`` best-bounded candidates per query
    (ascending running bound) into packed batches.  Its *policy* decides
    how much of the packed width each query may refine: the default refines
    everything; a ``limit_fn`` callback computes per-query refine limits at
    trace time, which is how the distributed path allocates one *global*
    budget across shards (limits beyond the allocation keep their tier-0/1
    bound — still valid, so exactness never depends on the policy).
  * ``VerificationPlan`` — the ordered tier list + compaction + the
    verification *schedule*.  ``schedule="bound"`` argsorts every
    verification round's flat (query, candidate) batch ascending by its
    tightest bound before packing it into DTW pair tiles, so doomed pairs
    cluster into the same tiles and the kernel's per-tile liveness exit
    fires per cluster instead of almost never; ``schedule="index"`` keeps
    the unsorted stripe order (the PR 2 baseline the bench measures
    against).  The schedule is a packing permutation only — results and
    per-query ``n_dtw`` are invariant under it.

Every stage of this pipeline is also a *checked invariant boundary*
(search/guards.py): tier outputs pass a finite-value gate (a registered
tier that emits NaN degrades its pairs to verification instead of
poisoning the ranking), the compaction gather is covered by the
survivor-mass conservation check (every selected candidate appears in
the pack exactly once, scatter-max refinement is monotone), and the
executor's seed verification doubles as the admissibility spot-check
(tier bound <= verified DTW).  A custom tier therefore does not need to
be trusted to be *correct* to be safe to register — an inadmissible
bound trips the guard and the engine serves the reference fallback —
but it does need to be admissible to be *useful*.  The deterministic
fault injectors in testing/faults.py target exactly these stage
boundaries (``tier_out``, ``compaction_cand``, ``packed_rows``).

Registering a custom tier (worked example — this exact pattern is
exercised by tests/test_scheduler.py and tests/test_planner.py).  A
registered tier is not just runnable, it is *priced*: the executor can
measure its realised pruning mass against its cost class (``TierStats``
below), and the planner (search/planner.py) drops it from the committed
plan when the measurement says it does not pay — no hand-tuning:

    from repro.search import pipeline as pl

    @pl.register_tier("bands_v2")
    def bands_v2_tier() -> pl.BoundTier:
        # a second, cheaper bands pass at V=2 in front of the V=4 one
        def fn(q, index, cfg):
            from repro.search.cascade import bands_prefilter
            import dataclasses
            return bands_prefilter(q, index, dataclasses.replace(cfg, v=2))
        return pl.BoundTier("bands_v2", cost="O(V^2)", scope="all_pairs",
                            fn=fn)

    plan = pl.default_plan(cfg)
    plan = dataclasses.replace(
        plan, tiers=(pl.get_tier("kim"), pl.get_tier("bands_v2"),
                     *plan.tiers[1:]))
    ecfg = EngineConfig(cascade=cfg, k=1, auto_plan=True)
    res, stats = nn_search(index, queries, ecfg, plan=plan,
                           with_stats=True)      # exactness is untouched
    print(stats.table())
    # tier        cost    scored   mass  mass%   work  mass/work
    # kim         O(1)      3072    410  13.3%   3.1e3  1.3e-1
    # bands_v2    O(V^2)    3072      0   0.0%   1.2e4  0.0      <- dropped
    # bands       O(V^2)    3072   2231  72.6%   4.9e4  4.5e-2
    # ...
    # committed: kim -> bands -> enhanced_pairwise   dropped: bands_v2

The V=2 pass here is fully shadowed by the V=4 pass that runs after it,
so its measured incremental mass is zero and the committed plan stops
paying for it from the second query block on.  ``list_tiers()`` /
``unregister_tier()`` keep calibration experiments from leaking registry
state across tests.

Every tier must return a valid lower bound on ``DTW_w``; the executor
(cascade.run_plan) keeps the running elementwise max, so a loose custom
tier can only cost work, never correctness — and the planner can only
*remove* tier work, so a committed plan inherits exactness from the same
argument.
"""

from __future__ import annotations

import dataclasses
import weakref
from typing import Callable

import jax

Array = jax.Array

# ---------------------------------------------------------------------------
# pipeline vocabulary
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BoundTier:
    """One composable bound stage of the cascade.

    Attributes:
      name: stable identifier (registry key, bench label; the planner
        keys its drop/reorder decisions by name, so tiers sharing a plan
        must have distinct names).
      cost: cost class per pair ("O(1)", "O(V)", "O(V^2)", "O(L)",
        "O(L*W)").  Since the planner, this is *priced*, not just
        documentation: ``tier_cost_weight`` turns it into the work
        denominator of the mass/cost ratio the plan optimiser gates on,
        and unrecognised spellings price at ``O(L)`` — declare a known
        class or expect dense-tier pricing.
      scope: ``"all_pairs"`` (fn maps ``(q, index, cfg) -> (Q, N)`` bounds)
        or ``"pairwise"`` (fn maps packed rows
        ``(qrows, crows, urows, lrows, cfg) -> (P,)`` bounds over the
        compacted survivors; when the plan's compaction carries a
        ``limit_fn`` the executor also passes ``live=`` — a ``(P,)``
        slot-liveness mask the tier should honour by returning ``-inf``
        on dead slots, ideally skipping their work like the built-in
        kernel does).
      fn: the bound function for that scope.  Must return a valid lower
        bound on ``DTW_w`` for every pair it scores.
    """

    name: str
    cost: str
    scope: str
    fn: Callable

    def __post_init__(self):
        if self.scope not in ("all_pairs", "pairwise"):
            raise ValueError(f"unknown tier scope: {self.scope!r}")


@dataclasses.dataclass(frozen=True)
class Compaction:
    """Gather-compaction policy between all-pairs and pairwise tiers.

    Attributes:
      budget: static per-query packed width ``B`` override; ``None`` defers
        to ``CascadeConfig.budget`` (static bucket rule / adaptive memo).
      limit_fn: optional traceable callback ``(lb01, budget, k) -> (Q,)``
        int limits: query ``i`` refines only its first ``limit[i]`` packed
        slots (ascending tier-0/1 bound — the tightest survive), the rest
        keep their tier-0/1 bound.  This is the *global survivor budget*
        hook: the distributed path all-gathers per-shard tier-0/1 minima
        inside ``limit_fn`` and returns this shard's mass-proportional
        share.  ``None`` refines the full packed width.
      width_scale: with a ``limit_fn`` the *static* packed width is
        ``min(n, width_scale * B)`` so a skewed shard can be allocated more
        than the uniform per-shard budget while shapes stay trace-static.
        The executor turns the per-query limits into a per-slot ``live``
        mask for the pairwise tiers; the built-in kernel skips fully-dead
        pair tiles outright (kernels/lb_enhanced_pairwise.py), so the
        static width costs a light shard VMEM shape, not FLOPs — the
        allocation moves real work between shards, not just tightness
        (see search/distributed.py).
    """

    budget: int | None = None
    limit_fn: Callable | None = None
    width_scale: int = 2


@dataclasses.dataclass(frozen=True)
class VerificationPlan:
    """Ordered tiers + compaction + verification schedule.

    The executor (cascade.run_plan) runs the ``all_pairs`` tiers in order
    (running elementwise max), compacts once, then runs the ``pairwise``
    tiers on the packed survivors.  ``all_pairs`` tiers listed after a
    ``pairwise`` tier are rejected — the pipeline has exactly one
    compaction point.

    ``schedule`` steers the engine's verification loop:
      * ``"bound"``: each round's flat batch is argsorted ascending by its
        tightest bound and the permutation is pushed into the DTW kernel's
        pair-tile packing (kernels/ops.py ``perm=``) — doomed pairs land in
        the same tiles, converting the per-tile liveness exit into an
        effective per-pair early exit;
      * ``"index"``: PR 2's unsorted stripe packing (bench baseline).

    ``verify_tile_p`` makes the pair-tile size a scheduler decision: it is
    threaded into every verification DTW dispatch (the engine's rounds and
    ``run_plan``'s seed verification) as the kernel's ``tile_p`` cap.
    ``None`` defers to the per-round policy — bound-ordered engine rounds
    shrink the tile (``kernels.tiling.sched_pair_tile``) so the doomed
    cluster's boundary lands on a tile boundary and the liveness exit
    fires there, while seed verification and unsorted rounds keep the
    kernel default (seeds are the tightest-bound pairs: almost all live,
    nothing to exit, so full tiles win).  Tile size is packing geometry
    only — results and per-query ``n_dtw`` are invariant under it
    (property-tested like the schedule itself).
    """

    tiers: tuple[BoundTier, ...]
    compaction: Compaction = Compaction()
    schedule: str = "bound"
    verify_tile_p: int | None = None

    def __post_init__(self):
        if self.schedule not in ("bound", "index"):
            raise ValueError(f"unknown schedule: {self.schedule!r}")
        seen_pairwise = False
        for t in self.tiers:
            if t.scope == "pairwise":
                seen_pairwise = True
            elif seen_pairwise:
                raise ValueError(
                    "all_pairs tier after a pairwise tier: the pipeline "
                    f"has one compaction point (tier {t.name!r})"
                )

    @property
    def all_pairs_tiers(self) -> tuple[BoundTier, ...]:
        return tuple(t for t in self.tiers if t.scope == "all_pairs")

    @property
    def pairwise_tiers(self) -> tuple[BoundTier, ...]:
        return tuple(t for t in self.tiers if t.scope == "pairwise")


# ---------------------------------------------------------------------------
# tier pricing: measured mass / cost-weighted work
# ---------------------------------------------------------------------------


def bucket_pow2(x: int, floor: int) -> int:
    """Round ``x`` up to the next power-of-two bucket (>= ``floor``) —
    the one bucketing rule behind both the cascade's survivor budgets
    (floor 64, see cascade.py) and the planner's committed right-sizing
    (floor 8): bounded bucket vocabulary = bounded recompilation."""
    b = floor
    while b < x:
        b <<= 1
    return b


def tier_cost_weight(cost: str, L: int, v: int, w: int,
                     s: int = 16) -> float:
    """Per-pair work weight of a tier's declared cost class.

    The cost class strings were documentation until now; the planner
    prices tiers with them, so the executor turns them into per-pair
    weights here (one definition for stats, planner, and bench).
    ``"O(S)"`` is the sketch tier's class (``s`` = segment count of the
    int8 feature store, default 16).  Unrecognised classes price at
    ``O(L)`` — the costliest *built-in* class — which under-charges
    anything genuinely ``O(L*W)``-shaped, so a custom tier above
    ``O(L)`` should declare one of the recognised spellings to be priced
    (and gated) honestly.
    """
    key = cost.replace(" ", "").upper()
    if key == "O(1)":
        return 1.0
    if key == "O(S)":
        return float(max(s, 1))
    if key == "O(V)":
        return float(max(v, 1))
    if key in ("O(V^2)", "O(V2)", "O(V*V)"):
        return float(max(v, 1)) ** 2
    if key == "O(L)":
        return float(max(L, 1))
    if key in ("O(L*W)", "O(LW)", "O(W*L)", "O(WL)"):
        return float(max(L, 1)) * float(max(min(w, L), 1))
    return float(max(L, 1))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TierStats:
    """Measured per-tier pruning mass + cost-weighted work for one plan.

    This generalises what ``choose_survivor_budget`` estimates (survivor
    mass under a verified threshold) into a reusable per-tier accumulator:
    ``cascade.run_plan(collect_stats=True)`` fills one of these while
    executing a plan, pricing every tier against the seed-verified
    threshold ``tau`` (the k-th seed distance upper-bounds the final k-th
    best, so a pair whose running bound reaches ``tau`` is realised
    pruning — the paper's pruning-power numerator, attributed to the tier
    that crossed it).  All measured fields are arrays, so the struct is a
    pytree: it traces through ``jit``/``shard_map`` and the distributed
    path can ``psum`` it across shards before anyone syncs to host
    (search/distributed.py ``gather_tier_stats``).

    Attributes:
      names/costs/scopes: static per-tier labels, in plan order.
      mass: (T,) incremental realised pruning mass — pairs whose running
        bound first reached ``tau`` at this tier.
      scored: (T,) pairs the tier actually scored (pairwise tiers under a
        refine limit score only their live slots).
      work: (T,) ``scored * tier_cost_weight(cost)`` — the cost-weighted
        denominator of the planner's mass/cost ratio.
      pairs: () total measured (query, candidate) pairs (excluded
        candidates removed).
      queries: () measured query count.
      survivors: (Q,) per-query cheap-tier survivor mass at ``tau`` —
        ``choose_survivor_budget``'s estimator, kept per query so the
        planner can bucket a refine limit from it.
    """

    names: tuple[str, ...] = dataclasses.field(metadata=dict(static=True))
    costs: tuple[str, ...] = dataclasses.field(metadata=dict(static=True))
    scopes: tuple[str, ...] = dataclasses.field(metadata=dict(static=True))
    mass: Array
    scored: Array
    work: Array
    pairs: Array
    queries: Array
    survivors: Array

    def mass_per_work(self):
        """(T,) realised mass per unit of cost-weighted work (host-side)."""
        import numpy as np

        w = np.maximum(np.asarray(self.work, dtype=float), 1e-30)
        return np.asarray(self.mass, dtype=float) / w

    def table(self) -> str:
        """Human-readable per-tier pricing table (host-side)."""
        import numpy as np

        pairs = max(float(self.pairs), 1.0)
        ratio = self.mass_per_work()
        rows = [f"{'tier':<20} {'cost':<8} {'scored':>9} {'mass':>9} "
                f"{'mass%':>7} {'work':>10} {'mass/work':>10}"]
        for i, name in enumerate(self.names):
            m = float(np.asarray(self.mass)[i])
            s = float(np.asarray(self.scored)[i])
            wk = float(np.asarray(self.work)[i])
            rows.append(
                f"{name:<20} {self.costs[i]:<8} {s:>9.0f} {m:>9.0f} "
                f"{100.0 * m / pairs:>6.1f}% {wk:>10.3g} {ratio[i]:>10.3g}"
            )
        return "\n".join(rows)


# ---------------------------------------------------------------------------
# tier registry + the built-in tiers
# ---------------------------------------------------------------------------

_TIER_REGISTRY: dict[str, Callable[[], BoundTier]] = {}


def register_tier(name: str):
    """Decorator: register a zero-arg ``BoundTier`` factory under ``name``."""

    def deco(factory: Callable[[], BoundTier]):
        _TIER_REGISTRY[name] = factory
        return factory

    return deco


def get_tier(name: str) -> BoundTier:
    try:
        return _TIER_REGISTRY[name]()
    except KeyError:
        raise KeyError(
            f"unknown tier {name!r}; registered: {sorted(_TIER_REGISTRY)}"
        ) from None


def list_tiers() -> tuple[str, ...]:
    """Sorted names of every registered tier factory.

    The listing half of the registry's bookkeeping pair (with
    ``unregister_tier``): calibration experiments that register throwaway
    tiers can enumerate and remove exactly what they added instead of
    leaking registry state across tests.
    """
    return tuple(sorted(_TIER_REGISTRY))


def registered_tiers() -> tuple[str, ...]:
    """Alias of ``list_tiers`` (the pre-planner name, kept for callers)."""
    return list_tiers()


def unregister_tier(name: str) -> bool:
    """Remove a registered tier factory; ``True`` if it was present.

    Idempotent: unregistering a name twice (or a name never registered)
    is a no-op returning ``False``, so test teardown never races.
    """
    return _TIER_REGISTRY.pop(name, None) is not None


# The built-in tiers' bound functions live at module level, so every
# ``get_tier(name)`` returns an equal ``BoundTier``: plans built on
# different calls compare equal and key one compiled bound pass
# (engine.py ``_BoundsKey``) instead of one per call.


def _sketch_bound(q, index, cfg):
    import jax.numpy as jnp

    if getattr(index, "sk_lo", None) is None:
        return jnp.zeros((q.shape[0], index.n), jnp.float32)
    from repro.kernels import ref as _ref
    from repro.kernels.ops import sketch_bound_op
    from repro.search.index import (
        sketch_query_means,
        sketch_segment_sizes,
    )

    s = index.sk_lo.shape[1]
    qbar = sketch_query_means(q, s)
    seg = sketch_segment_sizes(index.length, s)
    op = sketch_bound_op if cfg.use_pallas else _ref.sketch_bound_ref
    return op(qbar, index.sk_lo, index.sk_hi, index.sk_scale, seg)


@register_tier("sketch")
def _sketch_tier() -> BoundTier:
    """Tier -1: O(S)/pair quantised sketch bound from the int8 PAA
    feature store (module docstring; search/index.py for the layout).

    Scores every candidate unconditionally — the store-level ``live``
    mask is *derived from* these bounds, so this tier must never consume
    it.  On an index built without features the bound is all zeros
    (valid, idle, planner-dropped), which is what lets ``default_plan``
    include the tier without knowing the index.
    """
    return BoundTier("sketch", cost="O(S)", scope="all_pairs",
                     fn=_sketch_bound)


def _lb_improved_bound(qrows, crows, urows, lrows, cfg, *, live=None):
    import jax.numpy as jnp

    from repro.core.envelopes import envelope
    from repro.core.lower_bounds import lb_keogh_env

    first = lb_keogh_env(qrows, urows, lrows)
    proj = jnp.clip(qrows, lrows, urows)
    up, lp = envelope(proj, cfg.w)
    out = first + lb_keogh_env(crows, up, lp)
    if live is not None:
        liv = jnp.broadcast_to(
            jnp.asarray(live), out.shape
        ).astype(bool)
        out = jnp.where(liv, out, float("-inf"))
    return out


@register_tier("lb_improved")
def _lb_improved_tier() -> BoundTier:
    """Lemire's two-pass LB_Improved (arXiv:0811.3301) over the packed
    survivor rows — optional, jnp-only, priced like any tier.

    Pass 1 is LB_Keogh of the query against the candidate's (index-
    precomputed) envelope; pass 2 projects the query onto that envelope
    and runs LB_Keogh of the *candidate* against the projection's
    envelope (core/envelopes.py — batched, so the packed ``(P, L)``
    layout runs in one shot).  Sum of the two passes is Lemire's bound.
    Registered but not in ``default_plan``: the point it pins is that a
    second real bound is a config edit plus this factory — the planner
    prices it per store and keeps it only where the measured mass says
    the extra O(L) pass pays.
    """
    return BoundTier("lb_improved", cost="O(L)", scope="pairwise",
                     fn=_lb_improved_bound)


def _kim_bound(q, index, cfg):
    from repro.search.cascade import lb_kim_tier

    return lb_kim_tier(q, index)


@register_tier("kim")
def _kim_tier() -> BoundTier:
    """O(1)/pair Kim bound from precomputed index features."""
    return BoundTier("kim", cost="O(1)", scope="all_pairs", fn=_kim_bound)


def _bands_bound(q, index, cfg, *, live=None):
    from repro.search.cascade import bands_prefilter

    return bands_prefilter(q, index, cfg, live=live)


@register_tier("bands")
def _bands_tier() -> BoundTier:
    """O(V^2)/pair elastic-bands tier (Alg. 1 lines 1-11).

    Honours the store-level ``live`` mask (cross-block kernel liveness:
    dead candidates emit ``-inf``, fully-dead candidate tiles skip)."""
    return BoundTier("bands", cost="O(V^2)", scope="all_pairs",
                     fn=_bands_bound)


def _enhanced_pairwise_bound(qrows, crows, urows, lrows, cfg, *, live=None):
    return cfg.pairwise_fn()(qrows, crows, urows, lrows, cfg.w, cfg.v,
                             live=live)


@register_tier("enhanced_pairwise")
def _enhanced_pairwise_tier() -> BoundTier:
    """O(L)/pair fused LB_ENHANCED^V over the packed survivor rows."""
    return BoundTier("enhanced_pairwise", cost="O(L)", scope="pairwise",
                     fn=_enhanced_pairwise_bound)


def _enhanced_dense_bound(q, index, cfg, *, live=None):
    from repro.search.cascade import enhanced_all_pairs

    return enhanced_all_pairs(q, index, cfg, live=live)


@register_tier("enhanced_dense")
def _enhanced_dense_tier() -> BoundTier:
    """O(L)/pair LB_ENHANCED^V on *all* pairs — the unstaged diagnostic
    tier (cross-block kernel shape), bypassing compaction entirely."""
    return BoundTier("enhanced_dense", cost="O(L)", scope="all_pairs",
                     fn=_enhanced_dense_bound)


def default_plan(cfg, *, schedule: str = "bound") -> VerificationPlan:
    """The paper's staged cascade as a tier list: [sketch ->] kim ->
    bands -> compact -> pairwise LB_ENHANCED.  ``cfg.use_sketch=True``
    prepends the tier-(-1) sketch (safe with any index — see the sketch
    tier factory); ``cfg.use_kim=False`` drops the Kim tier."""
    tiers = []
    if getattr(cfg, "use_sketch", False):
        tiers.append(get_tier("sketch"))
    if cfg.use_kim:
        tiers.append(get_tier("kim"))
    tiers.append(get_tier("bands"))
    tiers.append(get_tier("enhanced_pairwise"))
    return VerificationPlan(tiers=tuple(tiers), schedule=schedule)


def dense_plan(cfg, *, schedule: str = "bound") -> VerificationPlan:
    """The seed behaviour: every pair pays the full O(L) tier (diagnostics
    and the baseline the staged pipeline is property-tested against)."""
    tiers = []
    if getattr(cfg, "use_sketch", False):
        tiers.append(get_tier("sketch"))
    if cfg.use_kim:
        tiers.append(get_tier("kim"))
    tiers.append(get_tier("enhanced_dense"))
    return VerificationPlan(tiers=tuple(tiers), schedule=schedule)


# ---------------------------------------------------------------------------
# adaptive survivor-budget memo
# ---------------------------------------------------------------------------

# choose_survivor_budget costs one tier-0/1 pass plus S*k uncut DTWs, so the
# chosen bucket is cached and re-estimated only when the store or the
# query shape of the problem changes.  The key is explicit about what the
# estimate depends on — the *index* (series identity + size), the window
# ``w``, and ``k`` — plus the config knobs that change which bounds the
# estimator runs.  A budget chosen for k=1 must never be reused for a
# larger k: tau is the k-th seed distance, so the survivor mass grows with
# k and a stale k=1 bucket would silently under-cover the refinement.
# Entries hold a weakref to the series array and hit only while that exact
# array is alive — a freed buffer whose id() gets reused cannot inherit a
# stale budget.
_BUDGET_CACHE: dict = {}
_BUDGET_CACHE_MAX = 64


def _budget_cache_key(index, cascade, k: int, exclude) -> tuple:
    return (
        id(index.series),            # index identity (validated by weakref)
        index.n,                     # index size
        cascade.w,                   # window the bounds are built for
        k,                           # tau = k-th seed distance -> mass
        cascade.v,
        cascade.use_kim,
        cascade.use_pallas,
        exclude is not None,
    )


def budget_cache_clear() -> None:
    _BUDGET_CACHE.clear()


def budget_cache_len() -> int:
    return len(_BUDGET_CACHE)


def resolve_adaptive_budget(q, index, cascade, k: int, exclude) -> int:
    """Memoised ``choose_survivor_budget`` — see ``_budget_cache_key`` for
    exactly what the memo keys on.  Concrete (host) inputs only."""
    from repro.search.cascade import choose_survivor_budget

    ckey = _budget_cache_key(index, cascade, k, exclude)
    hit = _BUDGET_CACHE.get(ckey)
    if hit is not None and hit[0]() is index.series:
        return hit[1]
    budget = choose_survivor_budget(q, index, cascade, k, exclude=exclude)
    if len(_BUDGET_CACHE) >= _BUDGET_CACHE_MAX:
        _BUDGET_CACHE.clear()
    _BUDGET_CACHE[ckey] = (weakref.ref(index.series), budget)
    return budget
