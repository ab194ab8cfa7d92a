"""Lower-bound cascade: the tier-pipeline executor.

DESIGN — vocabulary (defined in search/pipeline.py, executed here):

  * **tier** (``BoundTier``): one bound stage with a *cost class* and a
    *scope*.  The default plan is the paper's cascade expressed as data:

      tier "sketch"             O(S)/pair    all_pairs  int8 PAA features
                                (tier -1, ``cfg.use_sketch`` — reads the
                                quantised feature store, never the series)
      tier "kim"                O(1)/pair    all_pairs  index features
      tier "bands"              O(V^2)/pair  all_pairs  bands (Alg. 1 1-11)
      tier "enhanced_pairwise"  O(L)/pair    pairwise   bands+Keogh bridge

    Every tier is a valid lower bound, so the *running elementwise max* of
    the executed tiers is the tightest available bound per pair — a loose
    or reordered tier changes work, never correctness.
  * **plan** (``VerificationPlan``): the ordered tier list + compaction +
    verification schedule.  Adding a tier (a second bands pass at another
    ``V``, a two-pass LB a la Lemire arXiv:0811.3301) or reordering tiers
    is a plan edit — see pipeline.py's module docstring for the worked
    ``register_tier`` example — not a cascade rewrite.
  * **compaction** (``Compaction``): the single gather point between the
    all-pairs and pairwise tiers: the ``B`` best-bounded candidates per
    query (ascending running bound) are packed into dense ``(Q*chunk, L)``
    row batches.  A ``limit_fn`` policy may cap, per query, how many packed
    slots the pairwise tiers refine (the *global survivor budget*:
    search/distributed.py all-gathers per-shard tier-0/1 minima inside its
    ``limit_fn`` and returns each shard's mass-proportional share).
    Unrefined slots keep their all-pairs bound — still valid, so the
    policy trades bound tightness for tier work, never exactness.
  * **schedule**: how the engine orders each verification round's flat
    (query, candidate) batch — ``"bound"`` argsorts ascending by tightest
    bound so doomed pairs cluster into the same DTW pair tiles (see
    engine.py), ``"index"`` keeps the unsorted stripe packing.

Pipeline (``run_plan``):

  1. all-pairs tiers in plan order, running max (O(Q*N) .. O(Q*N*V^2));
  2. gather-compact the most promising ``B`` candidates per query into
     packed batches (static budget, so the pipeline stays jit/shard_map-
     traceable), optionally capped per query by the compaction policy;
  3. pairwise tiers on the packed survivors only (O(Q*B*L) instead of
     O(Q*N*L)), scatter-maxed back into the bound matrix;
  4. *provisional k-th best*: verify the k best-bounded candidates per
     query with banded DTW — their k-th best distance ``tau`` upper-bounds
     the final k-th best, so the engine starts its loop already knowing
     that any pair whose bound exceeds ``tau`` can never enter the top-k
     (and threads ``tau`` into the DTW kernel's early-abandon cutoff).

DESIGN — two LB_ENHANCED kernel shapes, and which scope picks each:

  * **cross-block** (kernels/lb_enhanced.py): ``(TQ, L) x (TC, L) ->
    (TQ, TC)``.  ``all_pairs`` tiers are genuinely all-pairs — every query
    meets every candidate — so the block shape *is* the work
    (``bands_prefilter``/``enhanced_all_pairs`` route here).
  * **pairwise** (kernels/lb_enhanced_pairwise.py): packed ``(P, L)``
    query/candidate/envelope batches -> ``(P,)``.  Compacted survivors are
    (query, candidate) *pairs* — the diagonal of a cross block — so
    ``pairwise`` tiers route here (``cfg.pairwise_fn``): one VMEM round
    trip per pair tile instead of a ``TQ x TC`` block per ``min(TQ, TC)``
    useful answers.  This packed layout is also what the engine's flat
    verification scheduler and the DTW kernel's pair tiles consume, so
    everything downstream of compaction shares one shape — including the
    distributed path's globally-budgeted batches.

Survivor budget (step 2): budgets come from a static set of power-of-two
buckets (>= 64), so jit sees at most O(log N) distinct shapes.  When the
inputs are concrete, ``choose_survivor_budget`` picks the bucket from the
observed tier-0/1 pruning mass (how many candidates' cheap bounds fall
below a verified upper bound on the k-th best); under tracing the static
rule ``bucket(max(64, 4k, N/8))`` applies.
"""

from __future__ import annotations

import dataclasses
import inspect
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro import obs
from repro.kernels import ref as kref
from repro.kernels.ops import (
    dtw_band_op,
    lb_enhanced_op,
    lb_enhanced_pairwise_op,
)
from repro.kernels.ref import dtw_band_ref
from repro.search import guards as _guards
from repro.search.index import DTWIndex, kim_features
from repro.search.pipeline import (
    TierStats,
    VerificationPlan,
    bucket_pow2,
    default_plan,
    dense_plan,
    tier_cost_weight,
)

Array = jax.Array

_INF = jnp.inf

# Survivor budgets are drawn from power-of-two buckets (floor 64) so the
# compacted tier shapes — and therefore jit recompilations — stay bounded
# at O(log N) regardless of how the adaptive selection moves between calls.
_BUDGET_FLOOR = 64


def _bucket_up(x: int) -> int:
    """Round ``x`` up to the next power-of-two budget bucket (>= 64)."""
    return bucket_pow2(x, _BUDGET_FLOOR)


@dataclasses.dataclass(frozen=True)
class CascadeConfig:
    """Static configuration of the pruning cascade.

    Attributes:
      w: Sakoe-Chiba window.
      v: LB_ENHANCED speed-tightness parameter (paper SS III-A); the paper's
         recommended V=4 is the default.
      use_kim: include the O(1) Kim tier in the default plans.
      use_sketch: prepend the tier-(-1) quantised sketch tier to the
        default plans (pipeline.py).  Off by default: the tier only pays
        on an index built with sketch features (``build_index`` computes
        them by default) — without features it scores an all-zero bound
        that the planner measures idle and drops.
      candidate_chunk: packed survivors per query in one chunk of the
        pairwise tiers: only one chunk's ``(Q * chunk, L)`` gathered rows
        are live at a time.  The all-pairs tiers make one kernel call over
        the whole store; their kernels tile the candidates themselves.
      use_pallas: route the bound tiers through the Pallas kernels (True) or
        the pure-jnp references (False).  The jnp path is used when lowering
        the distributed search for the multi-pod dry-run, where kernel
        dispatch is orthogonal to the sharding being validated.
      staged: engine uses the staged tier pipeline (``run_plan`` over the
        default plan) instead of dense full-tier bounds.
      survivor_budget: per-query compaction width; ``None`` derives a
        power-of-two bucket from ``max(64, 4k, N/8)`` (clamped to N).  Must
        stay static for tracing.
      adaptive_budget: with ``survivor_budget=None`` and concrete (host)
        inputs, let the engine pick the bucket from the observed tier-0/1
        pruning mass (``choose_survivor_budget``) instead of the static
        rule.  Under tracing the static rule silently applies.
    """

    w: int
    v: int = 4
    use_kim: bool = True
    use_sketch: bool = False
    candidate_chunk: int = 512
    use_pallas: bool = True
    staged: bool = True
    survivor_budget: int | None = None
    adaptive_budget: bool = True

    def lb_fn(self):
        return lb_enhanced_op if self.use_pallas else kref.lb_enhanced_ref

    def pairwise_fn(self):
        """Pairwise-tier refinement over packed (P, L) survivor rows."""
        return (
            lb_enhanced_pairwise_op
            if self.use_pallas
            else kref.lb_enhanced_pairwise_ref
        )

    def dtw_fn(self):
        return dtw_band_op if self.use_pallas else dtw_band_ref

    def budget(self, n: int, k: int = 1) -> int:
        if self.survivor_budget is not None:
            return max(1, min(n, self.survivor_budget))
        return min(n, _bucket_up(max(_BUDGET_FLOOR, 4 * k, -(-n // 8))))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CascadeResult:
    """Tier-pipeline output consumed by the engine (a pytree, so a
    compiled bound pass returns it whole).

    Attributes:
      lb: (Q, N) per-pair lower bounds (all-pairs tiers everywhere,
        pairwise tiers on the compacted survivors, exact DTW at the seeds).
      seed_idx: (Q, k) candidate ids verified for the provisional threshold.
      seed_d: (Q, k) their exact banded-DTW distances.
      stats: measured per-tier pricing (``TierStats``) when the plan was
        executed with ``collect_stats=True`` — the planner's input;
        ``None`` otherwise.
      guard: the executor's ``GuardReport`` (admissibility seed
        spot-check, compaction conservation, finite gates) when guards
        ran; ``None`` when disabled.
    """

    lb: Array
    seed_idx: Array
    seed_d: Array
    stats: TierStats | None = None
    guard: _guards.GuardReport | None = None


def lb_kim_tier(q: Array, index: DTWIndex) -> Array:
    """(Q, N) Kim bounds from precomputed features — O(1) per pair."""
    qf, qok = kim_features(q)                        # (Q, 4), (Q, 2)
    cf, cok = index.kim, index.kim_ok                # (N, 4), (N, 2)
    d = qf[:, None, :] - cf[None, :, :]              # (Q, N, 4)
    d = d * d
    base = d[..., 0] + d[..., 1]
    # witness interiority: the series with the more extreme extremum
    q_mx, c_mx = qf[:, None, 2], cf[None, :, 2]
    ok_max = jnp.where(q_mx >= c_mx, qok[:, None, 0], cok[None, :, 0])
    t_max = jnp.where(ok_max, d[..., 2], 0.0)
    q_mn, c_mn = qf[:, None, 3], cf[None, :, 3]
    ok_min = jnp.where(q_mn <= c_mn, qok[:, None, 1], cok[None, :, 1])
    t_min = jnp.where(ok_min, d[..., 3], 0.0)
    return base + jnp.maximum(t_max, t_min)


def _accepts_kw(fn, name: str) -> bool:
    """Whether ``fn`` takes the keyword ``name`` (or ``**kwargs``).

    The executor's newer hooks are optional keywords — ``live`` on
    pairwise tier fns, ``tile_p`` on the DTW dispatch — and custom
    callbacks written to the older positional contracts must keep
    working: they get the plain call and the executor's own fallbacks
    (the belt mask below, the kernel-default tile) cover the rest.
    """
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):   # builtins/partials without signatures
        return False
    return name in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
    )


def _accepts_live(fn) -> bool:
    return _accepts_kw(fn, "live")


def choose_survivor_budget(
    q: Array,
    index: DTWIndex,
    cfg: CascadeConfig,
    k: int = 1,
    *,
    exclude: Array | None = None,
    sample: int = 8,
    safety: float = 2.0,
) -> int:
    """Pick a power-of-two survivor budget from tier-0/1 pruning mass.

    Host-side (concrete inputs required): runs the cheap all-pairs tiers on
    a small query sample, verifies each sample query's ``k`` best-bounded
    candidates with banded DTW — their worst distance ``tau`` upper-bounds
    that query's final k-th best — and counts candidates whose cheap bound
    falls below ``tau``.  That count is the survivor mass the compaction
    budget must cover for the pairwise tiers to reach every candidate the
    engine could still verify; the max over the sample (times ``safety``)
    is rounded up to the next power-of-two bucket, so jit sees at most
    O(log N) distinct compacted shapes across calls (bounded
    recompilation).  The result is capped at 4x the static rule's bucket:
    on loose-bound data the mass estimate approaches N, and an uncapped
    budget would silently restore the dense tier cost the pipeline exists
    to avoid.

    ``exclude`` mirrors ``nn_search``'s per-query leave-one-out exclusion;
    without it a self-match candidate yields ``tau = 0`` and collapses the
    estimate to the floor.

    Cost: one cheap-tier pass over the sample plus ``S * k`` uncut DTW
    verifications, and a host sync on the mass count.  The engine memoises
    the chosen bucket per (index, k, w, config) — see
    ``pipeline.resolve_adaptive_budget`` — so repeated searches pay this
    once; the sample DTWs are estimator overhead outside the ``n_dtw``
    pruning-power metric (which counts the verification loop only).

    Raises ``jax.errors.ConcretizationTypeError`` under tracing — callers
    (engine.py) catch tracers beforehand and keep the static bucketed rule.
    """
    n = index.n
    k = min(k, n)
    q = jnp.asarray(q, jnp.float32)
    S = min(sample, q.shape[0])
    qs = q[:S]
    kim = (
        lb_kim_tier(qs, index) if cfg.use_kim
        else jnp.zeros((S, n), qs.dtype)
    )
    lb01 = jnp.maximum(kim, bands_prefilter(qs, index, cfg))
    if exclude is not None:
        lb01 = lb01.at[jnp.arange(S), exclude[:S]].set(_INF)
    _, cand = lax.top_k(-lb01, k)                    # (S, k) best-bounded
    qrep = jnp.repeat(qs, k, axis=0)
    d = cfg.dtw_fn()(qrep, index.series[cand.reshape(-1)], cfg.w)
    tau = jnp.max(d.reshape(S, k), axis=1, keepdims=True)
    mass = jnp.sum(lb01 < tau, axis=1)               # per-query survivors
    need = int(jnp.max(mass))                        # host sync (concrete)
    static_cap = 4 * _bucket_up(max(_BUDGET_FLOOR, 4 * k, -(-n // 8)))
    base = min(max(_BUDGET_FLOOR, 4 * k, int(need * safety)), static_cap)
    return min(n, _bucket_up(base))


def compute_bounds(
    q: Array,
    index: DTWIndex,
    cfg: CascadeConfig,
    *,
    k: int = 1,
    plan: VerificationPlan | None = None,
) -> Array:
    """(Q, N) tightest-available lower bound for every (query, candidate).

    With ``cfg.staged`` this executes the (given or default) tier plan and
    returns its bound matrix; otherwise it runs the *dense* plan — every
    pair pays the full O(L) tier (the seed behaviour, kept for diagnostics
    and as the baseline the staged pipeline is property-tested against).
    Both paths are the same declarative machinery: a tier list folded with
    a running elementwise max.
    """
    if cfg.staged:
        return run_plan(q, index, cfg, plan=plan, k=k).lb
    q = jnp.asarray(q, jnp.float32)
    plan = plan if plan is not None else dense_plan(cfg)
    if plan.pairwise_tiers:
        raise ValueError(
            "dense (cfg.staged=False) bounds have no compaction stage to "
            "feed pairwise tiers "
            f"({[t.name for t in plan.pairwise_tiers]}); use a dense_plan "
            "or enable staging"
        )
    store_live = getattr(index, "live", None)
    lb = None
    for tier in plan.all_pairs_tiers:
        if store_live is not None and _accepts_live(tier.fn):
            t = tier.fn(q, index, cfg, live=store_live)
        else:
            t = tier.fn(q, index, cfg)
        lb = t if lb is None else jnp.maximum(lb, t)
    if lb is None:
        lb = jnp.zeros((q.shape[0], index.n), q.dtype)
    return lb


def enhanced_all_pairs(
    q: Array, index: DTWIndex, cfg: CascadeConfig,
    *, live: Array | None = None,
) -> Array:
    """(Q, N) dense O(L) LB_ENHANCED tier — the ``enhanced_dense`` tier's
    bound fn.  One kernel call over the whole store: the kernel grids
    over candidate tiles itself (kernels/lb_enhanced.py).

    ``live`` (optional ``(N,)``) limit-masks the dense tier the way the
    refine limit masks the packed pairwise tiers: dead candidates come
    back ``-inf`` (the running-max identity) and fully-dead candidate
    tiles skip their compute in the kernel — the planner's lever for a
    cross-block tier whose mass does not pay everywhere.
    """
    return cfg.lb_fn()(q, index.series, index.upper, index.lower, cfg.w,
                       cfg.v, live=live)


def run_plan(
    q: Array,
    index: DTWIndex,
    cfg: CascadeConfig,
    plan: VerificationPlan | None = None,
    k: int = 1,
    dtw_fn: Callable | None = None,
    *,
    exclude: Array | None = None,
    collect_stats: bool = False,
    guards: "_guards.GuardConfig | None" = None,
) -> CascadeResult:
    """Execute a ``VerificationPlan``: all-pairs tiers -> compact ->
    pairwise tiers -> seed verification.

    Fully traceable (static compaction width), so it works under ``jit``
    and inside the distributed ``shard_map``.  ``exclude`` removes a
    per-query candidate (leave-one-out) from seeding and compaction; its
    bound entry is left untouched for the engine to mask.

    ``guards`` (``None`` = the default-on config; see search/guards.py)
    threads the exactness guards through the executor: finite gates on
    every tier output, conservation checks on the compaction gather and
    scatter-max, and the admissibility spot-check on the seed pairs
    (the seeds already carry exact DTW values, so the spot-check costs
    only comparisons).  The checks are pure jnp and never raise — the
    outcome lands in ``CascadeResult.guard``.  On clean finite data
    every gate is the identity, so guarded results are bit-equal to
    unguarded ones (property-tested).  Their cost on the chip is in the
    benchmark's traced runs (``cascade_host_ms_per_request.batch`` holds
    the engine's host time around this pass; PERF.md and the ledger).

    Each all-pairs tier runs in the span ``repro.cascade.<tier name>``,
    the compaction in ``repro.cascade.compact``, the pairwise chunk loop
    in ``repro.cascade.pairwise`` and the seed verification in
    ``repro.cascade.seeds`` (``repro.obs``).  Where the pass is traced
    into a program (the engine compiles it once per static key,
    engine.py ``_bound_pass``), these spans open only while it is traced.

    ``collect_stats`` makes the executor *instrumented*: it snapshots the
    running bound after every tier and, once the seeds fix the threshold
    ``tau`` (k-th seed distance), prices each tier — incremental realised
    pruning mass, pairs scored, cost-class-weighted work — into a
    ``TierStats`` on the result (the planner's measurement input, see
    search/planner.py).  The accounting is pure jnp reductions, so the
    instrumented executor still traces under jit/shard_map; the snapshots
    cost ``O(T)`` extra bound-matrix copies, which is why stats are
    opt-in calibration machinery, not an always-on path.
    """
    plan = plan if plan is not None else default_plan(cfg)
    q = jnp.asarray(q, jnp.float32)
    Q, L = q.shape
    n = index.n
    k = min(k, n)
    if dtw_fn is None:
        dtw_fn = cfg.dtw_fn()
    qarange = jnp.arange(Q)

    g = _guards.resolve_guards(guards)
    gon = g.enabled
    z32 = jnp.zeros((), jnp.float32)
    nf_bounds = nf_dtw = z32                       # finite-gate counters
    c_checked = c_viol = z32                       # conservation
    a_checked = a_viol = a_gap = z32               # admissibility

    # ---- all-pairs tiers, in plan order (running elementwise max) ------
    # The store-level candidate mask (index.live, derived from the sketch
    # store at build time — search/index.py) feeds liveness-conforming
    # cross-block tiers the same way the refine limit feeds pairwise
    # tiers: dead candidates come back -inf and whole-dead tiles skip
    # compute.  Tiers without ``live`` support (kim, sketch — the sketch
    # tier *derives* the mask and must never consume it) score everyone,
    # so every dead candidate keeps a finite cheap bound: the mask can
    # only remove work, never a neighbour (exactness argument in
    # search/index.py).
    store_live = getattr(index, "live", None)
    lb01 = None
    ap_snaps = []                      # running max after each tier (stats)
    ap_masked = []                     # which tiers saw the store mask
    hook_tier = _guards.fault_hook("tier_out")
    for tier in plan.all_pairs_tiers:
        masked = store_live is not None and _accepts_live(tier.fn)
        ap_masked.append(masked)
        with obs.span("cascade." + tier.name):
            if masked:
                t = tier.fn(q, index, cfg, live=store_live)
            else:
                t = tier.fn(q, index, cfg)
            if hook_tier is not None:
                t = hook_tier(t, tier.name)
            if gon and g.finite_gates:
                t, gated = _guards.finite_gate_bounds(t)
                nf_bounds = nf_bounds + gated
            lb01 = t if lb01 is None else jnp.maximum(lb01, t)
        if collect_stats:
            ap_snaps.append(lb01)
    if lb01 is None:
        lb01 = jnp.zeros((Q, n), q.dtype)

    pairwise_tiers = plan.pairwise_tiers
    if pairwise_tiers:
        # ---- compaction: gather the B most promising survivors ---------
        with obs.span("cascade.compact"):
            comp = plan.compaction
            B = comp.budget if comp.budget is not None else cfg.budget(n, k)
            B = max(1, min(n, B))
            sel_key = (
                lb01 if exclude is None
                else lb01.at[qarange, exclude].set(_INF)
            )
            if comp.limit_fn is None:
                W, limit = B, None
            else:
                # static packed width leaves headroom above the uniform
                # budget so the policy can over-allocate to a skewed shard;
                # the per-query limits are traced values, the shapes are not
                W = max(1, min(n, comp.width_scale * B))
                limit = jnp.clip(
                    comp.limit_fn(sel_key, B, k), min(k, W), W
                ).astype(jnp.int32)
            _, cand = lax.top_k(-sel_key, W)         # ascending cheap bound
            hook_cand = _guards.fault_hook("compaction_cand")
            if hook_cand is not None:
                cand = hook_cand(cand)
            if gon and g.conservation:
                cc, cv = _guards.conservation_check(cand, n)
                c_checked, c_viol = c_checked + cc, c_viol + cv

        # ---- pairwise tiers on the packed survivor batches -------------
        # Chunked over the packed width, so only one chunk's gathered
        # (Q * chunk, L) rows are live at a time: a loop over the full
        # chunks, then the ragged tail, each writing its columns of the
        # (Q, W) result.  Every bound depends on its pair alone, so the
        # chunking changes packing, never values.
        with obs.span("cascade.pairwise"):
            chunk = min(cfg.candidate_chunk, W)
            # per-slot liveness from each query's refine allocation: the
            # packed layout keeps one query's slots contiguous, so light
            # queries yield whole dead pair tiles and the tier kernels
            # skip them outright (dead slots come back -inf — the
            # identity of the scatter-max below, so unrefined slots keep
            # their cheap tier-0/1 bound).  The store-level mask ANDs in
            # per *candidate*: a dead-store slot is dead in every
            # query's allocation.
            live_all = (None if limit is None
                        else jnp.arange(W)[None, :] < limit[:, None])
            if store_live is not None:
                sl = store_live[cand]
                live_all = sl if live_all is None else live_all & sl
            plive = (None if live_all is None
                     else jnp.sum(live_all).astype(jnp.float32))
            hook_rows = _guards.fault_hook("packed_rows")

            def refine(s, size):
                """Packed columns ``[s, s + size)``: the (Q, size) running
                pairwise max, its snapshot after each tier (stats only)
                and the count of gated non-finite bounds."""
                cidx = lax.dynamic_slice_in_dim(cand, s, size, 1).reshape(-1)
                qf = jnp.repeat(q, size, axis=0)
                crows = index.series[cidx]
                urows = index.upper[cidx]
                lrows = index.lower[cidx]
                if hook_rows is not None:
                    crows, urows, lrows = hook_rows(crows, urows, lrows)
                live2d = (None if live_all is None
                          else lax.dynamic_slice_in_dim(live_all, s, size, 1))
                live = None if live2d is None else live2d.reshape(-1)
                gated_n = z32
                pe = None
                snaps = []
                for tier in pairwise_tiers:
                    if live is not None and _accepts_live(tier.fn):
                        t = tier.fn(qf, crows, urows, lrows, cfg, live=live)
                    else:   # no limit, or a pre-liveness custom tier
                        t = tier.fn(qf, crows, urows, lrows, cfg)
                    if hook_tier is not None:
                        t = hook_tier(t, tier.name)
                    if gon and g.finite_gates:
                        t, gated = _guards.finite_gate_bounds(t)
                        gated_n = gated_n + gated
                    pe = t if pe is None else jnp.maximum(pe, t)
                    if collect_stats:
                        # running pairwise max after this tier, dead slots at
                        # the -inf scatter-max identity (the belt mask keeps
                        # pre-liveness custom tiers honest here too)
                        snap = pe.reshape(Q, size)
                        if live2d is not None:
                            snap = jnp.where(live2d, snap, -_INF)
                        snaps.append(snap)
                block = pe.reshape(Q, size)
                if live2d is not None:
                    # belt for tiers without ``live`` support: the mask is
                    # idempotent over the kernel's own -inf dead slots
                    block = jnp.where(live2d, block, -_INF)
                return block, snaps, gated_n

            def write(carry, s, size):
                enh, snaps, nf = carry
                block, new, gated = refine(s, size)

                def put(buf, x):
                    return lax.dynamic_update_slice_in_dim(buf, x, s, 1)

                return (put(enh, block),
                        [put(b, x) for b, x in zip(snaps, new)], nf + gated)

            zeros = jnp.zeros((Q, W), q.dtype)
            snaps0 = [zeros] * len(pairwise_tiers) if collect_stats else []
            carry = (zeros, snaps0, nf_bounds)
            n_full = W // chunk
            carry = lax.fori_loop(
                0, n_full, lambda i, c: write(c, i * chunk, chunk), carry)
            if W % chunk:
                carry = write(carry, n_full * chunk, W % chunk)
            enh, pw_full, nf_bounds = carry
            lb = lb01.at[qarange[:, None], cand].max(enh)
            if gon and g.conservation:
                mc, mv = _guards.scatter_monotone_check(lb01, lb)
                c_checked, c_viol = c_checked + mc, c_viol + mv
    else:
        lb = lb01

    # ---- provisional k-th best: verify the k best-bounded candidates --
    # Seeds are picked from the *refined* bound order, so the k seed
    # verifications are exactly the first k verifications the engine's
    # ascending-bound loop would perform anyway — the threshold tier costs
    # no extra DTW, it only moves those verifications before the loop so
    # tau = k-th seed distance can warm-start pruning and cutoffs.
    with obs.span("cascade.seeds"):
        seed_sel = lb if exclude is None else lb.at[qarange, exclude].set(_INF)
        _, seed_idx = lax.top_k(-seed_sel, k)            # (Q, k)
        qs = jnp.repeat(q, k, axis=0)                    # (Q*k, L)
        cs = index.series[seed_idx.reshape(-1)]
        # seeds are the tightest-bound pairs — almost all live, so the
        # per-round tile policy keeps full tiles here; an explicit plan
        # verify_tile_p still overrides (pipeline.py) when the dispatch
        # understands it (a custom dtw_fn on the old (a, b, w) contract gets
        # the plain call — tile size is packing geometry, never semantics)
        if plan.verify_tile_p is not None and _accepts_kw(dtw_fn, "tile_p"):
            seed_d = dtw_fn(qs, cs, cfg.w, tile_p=plan.verify_tile_p)
        else:
            seed_d = dtw_fn(qs, cs, cfg.w)
        seed_d = seed_d.reshape(Q, k)
        if gon and g.finite_gates:
            # a NaN seed DTW would poison tau and the engine's warm start:
            # gate it to +inf (unverifiable) and count the incident
            seed_d, gated = _guards.finite_gate_dtw(seed_d)
            nf_dtw = nf_dtw + gated
        if gon and g.admissibility:
            # the seeds *are* the sampled survivor pairs — their bound (the
            # running max before the exact value lands) must not exceed
            # their verified DTW; the comparison reuses values that already
            # exist, so the spot-check costs no extra DTW
            pre = jnp.take_along_axis(lb, seed_idx, axis=1)
            ac, av, ag = _guards.admissibility_check(pre, seed_d, g.rtol,
                                                     g.atol)
            a_checked, a_viol = a_checked + ac, a_viol + av
            a_gap = jnp.maximum(a_gap, ag)
        # seed pairs are exactly verified: their distance is the perfect bound
        if gon and g.finite_gates:
            # a gated (+inf) seed must not poison the bound matrix — +inf
            # there means "never verify", the exact failure the gates exist
            # to prevent; the engine re-opens such seeds for verification
            lb = lb.at[qarange[:, None], seed_idx].max(
                jnp.where(jnp.isfinite(seed_d), seed_d, -_INF)
            )
        else:
            lb = lb.at[qarange[:, None], seed_idx].max(seed_d)

    stats = None
    if collect_stats:
        # ---- tier pricing against the seed-verified threshold ----------
        # tau upper-bounds each query's final k-th best, so a pair whose
        # running bound reaches tau is realised pruning; the crossing is
        # attributed to the tier whose fold first took it across.
        tau = jnp.max(seed_d, axis=1, keepdims=True)          # (Q, 1)
        excl = (
            None if exclude is None
            else jnp.arange(n)[None, :] == exclude[:, None]
        )

        def _crossed(prev, cur, emask):
            newly = (cur >= tau) & (prev < tau)
            if emask is not None:
                newly = newly & ~emask
            return jnp.sum(newly).astype(jnp.float32)

        # the sketch tier's "O(S)" cost class prices by the committed
        # segment count; tiers on an unsketched index keep the default
        s_sk = (
            int(index.sk_lo.shape[1])
            if getattr(index, "sk_lo", None) is not None else 16
        )
        names, costs, scopes = [], [], []
        mass, scored, work = [], [], []
        prev_ap = jnp.zeros((Q, n), q.dtype)
        n_live = (
            None if store_live is None
            else jnp.sum(store_live).astype(jnp.float32)
        )
        for i, tier in enumerate(plan.all_pairs_tiers):
            names.append(tier.name)
            costs.append(tier.cost)
            scopes.append(tier.scope)
            mass.append(_crossed(prev_ap, ap_snaps[i], excl))
            # a store-masked cross-block tier scores only live columns —
            # that is the work the planner prices
            sc = (
                jnp.asarray(float(Q), jnp.float32) * n_live
                if ap_masked[i]
                else jnp.asarray(float(Q * n), jnp.float32)
            )
            scored.append(sc)
            work.append(
                sc * tier_cost_weight(tier.cost, L, cfg.v, cfg.w, s_sk)
            )
            prev_ap = ap_snaps[i]
        if pairwise_tiers:
            base = lb01[qarange[:, None], cand]               # (Q, W)
            pexcl = None if exclude is None else cand == exclude[:, None]
            # under a refine limit a liveness-conforming tier scores only
            # its live slots — that is the work the planner prices, and
            # the belt mask holds pre-liveness custom tiers to the same
            # semantics
            pscored = (
                plive if plive is not None
                else jnp.asarray(float(Q * W), jnp.float32)
            )
            prev_pw = base
            for ti, tier in enumerate(pairwise_tiers):
                cur_pw = jnp.maximum(base, pw_full[ti])
                names.append(tier.name)
                costs.append(tier.cost)
                scopes.append(tier.scope)
                mass.append(_crossed(prev_pw, cur_pw, pexcl))
                scored.append(pscored)
                work.append(
                    pscored
                    * tier_cost_weight(tier.cost, L, cfg.v, cfg.w, s_sk)
                )
                prev_pw = cur_pw
        surv_key = (
            lb01 if exclude is None
            else lb01.at[qarange, exclude].set(_INF)
        )
        survivors = jnp.sum(surv_key < tau, axis=1).astype(jnp.float32)
        zero = jnp.zeros((0,), jnp.float32)
        stats = TierStats(
            names=tuple(names),
            costs=tuple(costs),
            scopes=tuple(scopes),
            mass=jnp.stack(mass) if mass else zero,
            scored=jnp.stack(scored) if scored else zero,
            work=jnp.stack(work) if work else zero,
            pairs=jnp.asarray(
                float(Q * (n - 1 if exclude is not None else n)),
                jnp.float32,
            ),
            queries=jnp.asarray(float(Q), jnp.float32),
            survivors=survivors,
        )
    guard = None
    if gon:
        guard = dataclasses.replace(
            _guards.GuardReport.zeros(),
            admiss_checked=a_checked, admiss_viol=a_viol, admiss_gap=a_gap,
            conserve_checked=c_checked, conserve_viol=c_viol,
            nonfinite_bounds=nf_bounds, nonfinite_dtw=nf_dtw,
        )
    return CascadeResult(lb=lb, seed_idx=seed_idx, seed_d=seed_d,
                         stats=stats, guard=guard)


def staged_bounds(
    q: Array,
    index: DTWIndex,
    cfg: CascadeConfig,
    k: int = 1,
    dtw_fn: Callable | None = None,
    *,
    exclude: Array | None = None,
    plan: VerificationPlan | None = None,
) -> CascadeResult:
    """Execute the default (or given) staged tier plan — the historical
    entry point; ``run_plan`` is the general executor it wraps."""
    return run_plan(q, index, cfg, plan=plan, k=k, dtw_fn=dtw_fn,
                    exclude=exclude)


def bands_prefilter(
    q: Array, index: DTWIndex, cfg: CascadeConfig,
    *, live: Array | None = None,
) -> Array:
    """(Q, N) bands-only tier (Alg. 1 lines 1-11) — the cheap pre-bound.

    The ``bands`` tier's bound fn: picks compaction survivors before the
    pipeline pays for the O(L) bridge; on the roofline it is ~V^2/L of the
    pairwise tier.

    ``live`` (optional ``(N,)``) is the store-level candidate mask
    (search/index.py): dead candidates come back ``-inf`` and fully-dead
    candidate tiles skip their compute in the kernel.  One kernel call
    over the whole store, which reads only the band columns.
    """
    return cfg.lb_fn()(q, index.series, index.upper, index.lower, cfg.w,
                       cfg.v, live=live, bands_only=True)
