"""Runtime exactness guards: invariant checking, containment, degradation.

DESIGN — why this module exists
-------------------------------

The whole search stack sells one contract: results bit-equal to brute
force.  PRs 1-5 *prove* that contract on clean inputs and a correct
compiler — but the jax 0.4.x ``jit(shard_map(while))`` miscompile
showed the contract can fail *silently* (candidates dropped with no
error), and nothing validated inputs: a single NaN in a stored series
poisons envelopes, Kim features, and every admissible bound without any
signal (a NaN bound compares ``False`` everywhere, so the cascade simply
stops pruning — or worse, a +inf bound excludes a true neighbour).  This
module adds the three layers that make wrong-answer and poison-input
failure modes *detectable*, *contained*, and *recoverable*.

DESIGN — guard taxonomy
-----------------------

Every guard is a cheap, jit-compatible invariant check that **counts
violations into a ``GuardReport`` instead of raising** (raising is
impossible under trace; a count is psum-mergeable across shards like
``TierStats``):

  * **admissibility** (``admissibility_check``): sampled (bound, verified
    DTW) pairs must satisfy ``LB <= DTW`` within float tolerance — the
    paper's admissibility argument (and Lemire arXiv:0811.3301) is the
    exactness foundation, so a single violation means a tier, a kernel,
    or the data is lying.  Sampling is free: the cascade's seed
    verification and every engine round already compute exact DTW for
    the tightest-bound pairs, so the guard only compares numbers that
    were going to exist anyway.
  * **conservation** (``conservation_check`` + the scatter-monotonicity
    check in ``cascade.run_plan``): gather-compaction must select exactly
    ``W`` *distinct* candidates per query, and the scatter-max back into
    the bound matrix can only tighten (``lb_after >= lb_before``
    everywhere).  This is the guard that catches the shard_map
    miscompile *shape* — a live candidate silently dropped by a
    gather/pack — at the pipeline stage where it would happen.
  * **accounting** (engine): the engine's counted verifications
    (``n_dtw`` via ``segment_sum``) must match an independent total each
    round, and ``k <= n_dtw <= N`` must hold at the end.  A while-loop
    miscompile that drops rounds or double-counts shows up here.
  * **finite gates** (``finite_gate_bounds``): tier outputs must be
    finite or ``-inf`` (the legitimate dead-slot identity).  NaN / +inf
    tier values are *gated to -inf* — a trivially valid lower bound, so
    a poisoned bound degrades to "verify this candidate" (safe) instead
    of "never verify it" (wrong answers).  NaN DTW outputs in the engine
    are gated to +inf and counted; +inf there means "treat as
    unverifiable", which the host-side degradation ladder then repairs.

DESIGN — trace-compatibility rules
----------------------------------

  1. Guards never raise under trace: every check folds into float32
     counters carried in ``GuardReport`` (a registered pytree).
  2. Guard arithmetic is pure jnp (elementwise compares + reductions),
     so guarded executors still trace under ``jit`` / ``shard_map`` and
     reports ``psum``-merge across mesh axes
     (``GuardReport.to_vector`` crosses shard_map boundaries as a plain
     ``(G,)`` array).
  3. Host-only decisions (degradation reruns, preflight, input hygiene)
     run only on concrete inputs — under tracing they silently defer,
     the same contract as the adaptive budget and the planner.
  4. On clean finite data every gate is the identity, so guarded and
     unguarded runs are bit-equal (property-tested); guards change
     *work*, never results.  Their host sync on the chip is the
     benchmark's ``guard_sync_ms_per_request.online`` (the span
     ``repro.nn_search.guards``; PERF.md and the ledger).

DESIGN — degradation ladder
---------------------------

  0. **preflight** — before serving traffic, prove the compiled path on
     a canary: ``preflight_engine()`` (single-device jitted engine vs
     brute force).
  1. **in-trace containment** — finite gates replace poisoned bounds
     with -inf (degrade to verification) and poisoned DTW values with
     +inf, and count every gated value.  Exactness is preserved whenever
     the *verification* values are sound; the counts say when they were
     not.
  2. **host-side rerun** — on a tripped admissibility / conservation /
     accounting / NaN-DTW guard, ``nn_search`` re-serves the affected
     query block via reference brute force (``kernels/ref.py`` jnp
     mirrors, *no bound pruning* — a tripped guard means the bounds are
     untrusted, and a pruned rerun would consult the same lie), marks
     the result ``degraded``, and surfaces the incident in
     ``SearchStats``.
  3. **input hygiene** — ``validate_series`` at ``build_index`` /
     ``nn_search`` rejects (or, with ``sanitize=True``, masks and
     reports) NaN/Inf values and zero-variance series *before* z-norm,
     so layer 1 and 2 never fire on garbage the boundary could have
     refused.

Fault-injection seams
---------------------

``testing/faults.py`` proves every guard *trips*, not just that clean
runs pass.  The injectors install hooks into the ``_FAULT_HOOKS``
registry below; production call sites consult it with a single dict
lookup that is ``None`` outside the harness (zero cost, no behaviour).
The seams are: ``compaction_cand`` (corrupt the gather-compaction's
selected candidates — the miscompile replay), ``packed_rows`` (NaN/Inf
corruption of the packed survivor tiles), ``tier_out`` (corrupt a bound
tier's output), ``dtw_out`` (corrupt the DTW kernel dispatch's results,
kernels/ops.py), ``engine_count`` (perturb the engine's round
accounting), ``allgather_topk`` (simulated shard dropout in the
distributed top-k merge), and ``sketch_feats`` (break the build-time
sketch quantiser's outward-rounding invariant, search/index.py — the
admissibility spot-check covers the tier-(-1) bound because the seeds'
running-max ``pre`` includes the dequantised sketch term, so an
inward-rounded store trips it like any lying tier).
"""

from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

_INF = jnp.inf


class GuardWarning(UserWarning):
    """Category for every guard / preflight / hygiene warning."""


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """Which invariant checks run, and the degradation policy.

    Default-on: the checks are cheap enough to leave on in serving;
    what their host sync costs on the chip is the benchmark's
    ``guard_sync_ms_per_request.online`` (PERF.md and the ledger).
    ``REPRO_FORCE_GUARDS=1`` in the environment forces every
    check on regardless of the config (the CI fault-injection job).

    Attributes:
      enabled: master switch; ``False`` makes every guard a no-op and
        the guarded paths bit-identical to the unguarded ones.
      admissibility: sampled ``LB <= DTW`` spot-checks (cascade seeds +
        engine rounds).
      conservation: compaction distinct-count + scatter-monotonicity.
      accounting: engine ``n_dtw`` totals vs the independent mirror and
        the ``k <= n_dtw <= N`` bounds.
      finite_gates: NaN/+inf tier outputs gated to -inf (degrade to
        verification), NaN DTW outputs gated to +inf, both counted.
      rtol / atol: float tolerance of the admissibility comparison
        (bounds and DTW are sums of squares accumulated in different
        orders; 1-ulp re-association must not trip the guard).
      degrade: host-side re-serve via reference brute force when a
        trigger guard (admissibility / conservation / accounting /
        NaN-DTW) trips on concrete inputs (degradation ladder layer 2).
    """

    enabled: bool = True
    admissibility: bool = True
    conservation: bool = True
    accounting: bool = True
    finite_gates: bool = True
    rtol: float = 1e-4
    atol: float = 1e-5
    degrade: bool = True


_FORCED = GuardConfig()


def resolve_guards(cfg: GuardConfig | None) -> GuardConfig:
    """The one place guard configs are normalised: ``None`` means the
    default-on config, and ``REPRO_FORCE_GUARDS=1`` overrides everything
    (so the CI fault-injection job cannot be accidentally disarmed)."""
    if os.environ.get("REPRO_FORCE_GUARDS", "") not in ("", "0"):
        return _FORCED
    return cfg if cfg is not None else GuardConfig()


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------

_VEC_FIELDS = (
    "admiss_checked",
    "admiss_viol",
    "admiss_gap",
    "conserve_checked",
    "conserve_viol",
    "account_checked",
    "account_viol",
    "nonfinite_bounds",
    "nonfinite_dtw",
    "hygiene_values",
    "hygiene_series",
    "hygiene_flat",
    "degraded",
)

# fields that *trip* the degradation ladder (layer 2) when > 0.
# ``nonfinite_dtw`` is a trigger: a NaN verification value is gated to
# +inf, and +inf there may *exclude a true neighbour* — only a rerun
# through the reference kernels can restore soundness.  The
# ``nonfinite_bounds`` gate (-inf = "must verify") IS exactness-
# preserving, so it — and the hygiene counters, which report what the
# boundary already handled — stay containment/reporting only.
_TRIP_FIELDS = (
    "admiss_viol", "conserve_viol", "account_viol", "nonfinite_dtw",
)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GuardReport:
    """Structured guard outcome for one executor pass (pytree).

    Every field is a float32 scalar array, so the struct traces under
    ``jit`` / ``shard_map`` and merges across shards exactly like
    ``TierStats``: counts and ``*_checked`` totals add (``psum``), the
    admissibility ``gap`` maxes (``pmax``) — ``merge`` does the local
    composition, ``to_vector``/``from_vector`` give the flat ``(G,)``
    form that crosses ``shard_map`` output specs without pytree
    ceremony.

    Attributes:
      admiss_checked / admiss_viol: sampled ``LB <= DTW`` comparisons
        performed / failed (beyond ``rtol``/``atol``).
      admiss_gap: the worst observed ``LB - DTW`` overshoot (0 when
        clean) — how badly admissibility was violated, not just whether.
      conserve_checked / conserve_viol: compaction conservation checks
        performed / failed (lost or duplicated survivors, scatter-max
        that *loosened* a bound).
      account_checked / account_viol: engine verification-accounting
        checks performed / failed.
      nonfinite_bounds: tier-output values gated -inf (NaN / +inf).
      nonfinite_dtw: DTW outputs gated +inf (NaN).
      hygiene_values / hygiene_series / hygiene_flat: input-hygiene
        counts (non-finite values, series containing them, zero-variance
        series) found at the ``build_index`` / ``nn_search`` boundary.
      degraded: how many degradation-ladder reruns (layer 2) produced
        this result — > 0 means the engine fell back to reference brute
        force after a tripped guard.
    """

    admiss_checked: Array
    admiss_viol: Array
    admiss_gap: Array
    conserve_checked: Array
    conserve_viol: Array
    account_checked: Array
    account_viol: Array
    nonfinite_bounds: Array
    nonfinite_dtw: Array
    hygiene_values: Array
    hygiene_series: Array
    hygiene_flat: Array
    degraded: Array

    @staticmethod
    def zeros() -> "GuardReport":
        z = jnp.zeros((), jnp.float32)
        return GuardReport(**{f: z for f in _VEC_FIELDS})

    def merge(self, other: "GuardReport") -> "GuardReport":
        """Compose two reports: counts add, the admissibility gap maxes."""
        vals = {}
        for f in _VEC_FIELDS:
            a, b = getattr(self, f), getattr(other, f)
            vals[f] = jnp.maximum(a, b) if f == "admiss_gap" else a + b
        return GuardReport(**vals)

    def to_vector(self) -> Array:
        """Flat ``(G,)`` float32 form (fixed field order) — the shape
        that crosses ``shard_map`` output specs and psum collectives."""
        return jnp.stack(
            [jnp.asarray(getattr(self, f), jnp.float32) for f in _VEC_FIELDS]
        )

    @staticmethod
    def from_vector(v: Array) -> "GuardReport":
        return GuardReport(**{f: v[i] for i, f in enumerate(_VEC_FIELDS)})

    # -- host-side readout --------------------------------------------------

    def tripped(self) -> tuple[str, ...]:
        """Names of the guards whose violation counters are non-zero
        (host sync).  These are the degradation-ladder triggers; the
        nonfinite/hygiene counters are containment-only and do not
        appear here (read them off ``summary()``)."""
        return tuple(
            f for f in _TRIP_FIELDS if float(np.asarray(getattr(self, f))) > 0
        )

    def ok(self) -> bool:
        return not self.tripped()

    def summary(self) -> str:
        """One-line human-readable guard readout (host-side)."""
        g = {f: float(np.asarray(getattr(self, f))) for f in _VEC_FIELDS}
        parts = [
            f"admissibility {g['admiss_viol']:.0f}/{g['admiss_checked']:.0f}"
            + (f" (gap {g['admiss_gap']:.3g})" if g["admiss_viol"] else ""),
            f"conservation {g['conserve_viol']:.0f}/"
            f"{g['conserve_checked']:.0f}",
            f"accounting {g['account_viol']:.0f}/{g['account_checked']:.0f}",
        ]
        gated = g["nonfinite_bounds"] + g["nonfinite_dtw"]
        if gated:
            parts.append(
                f"gated {g['nonfinite_bounds']:.0f} bounds / "
                f"{g['nonfinite_dtw']:.0f} dtw"
            )
        hyg = g["hygiene_values"] + g["hygiene_flat"]
        if hyg:
            parts.append(
                f"hygiene {g['hygiene_values']:.0f} values in "
                f"{g['hygiene_series']:.0f} series, "
                f"{g['hygiene_flat']:.0f} flat"
            )
        if g["degraded"]:
            parts.append(f"degraded x{g['degraded']:.0f} (jnp ref rerun)")
        status = "TRIPPED " + ",".join(self.tripped()) if self.tripped() \
            else "ok"
        return f"guards[{status}]: " + "   ".join(parts)


# ---------------------------------------------------------------------------
# the checks (pure jnp — safe under jit / shard_map)
# ---------------------------------------------------------------------------


def finite_gate_bounds(t: Array) -> tuple[Array, Array]:
    """Gate a tier's bound output: NaN / +inf values become ``-inf``.

    ``-inf`` is the running-max identity *and* a trivially valid lower
    bound, so a poisoned bound degrades to "verify this candidate"
    (safe) instead of "never verify it" (wrong answers).  ``-inf``
    inputs pass through — they are the legitimate dead-slot identity
    the liveness kernels emit.  Returns ``(gated, n_gated)``.
    """
    bad = jnp.isnan(t) | jnp.isposinf(t)
    return jnp.where(bad, -_INF, t), jnp.sum(bad).astype(jnp.float32)


def finite_gate_dtw(d: Array, valid: Array | None = None
                    ) -> tuple[Array, Array]:
    """Gate DTW outputs: NaN becomes ``+inf`` ("treat as unverifiable"),
    counted so the host-side ladder knows verification values were
    unsound.  ``+inf`` inputs pass through — they are the legitimate
    early-abandon result.  ``valid`` restricts the count to live slots.
    """
    bad = jnp.isnan(d)
    n = bad if valid is None else (bad & valid)
    return jnp.where(bad, _INF, d), jnp.sum(n).astype(jnp.float32)


def verification_eligible(slb: Array) -> Array:
    """Which sorted-bound entries the engine may verify.

    The engine masks verified seeds and excluded candidates by setting
    their bound to exactly ``+inf`` — that is the *only* value that
    legitimately means "never verify".  Everything else, including NaN
    (a poisoned bound) and ``-inf`` (a gated one), must stay eligible:
    the old ``isfinite`` filter silently converted a non-finite bound
    into "never verify this candidate", turning a poisoned bound into
    missing neighbours.  Degrading to verification is always safe.
    """
    return ~jnp.isposinf(slb)


def admissibility_check(
    lb: Array, d: Array, rtol: float, atol: float,
    valid: Array | None = None,
) -> tuple[Array, Array, Array]:
    """Sampled ``LB <= DTW`` spot-check on pairs with exact DTW values.

    Only pairs whose DTW is finite participate (+inf = early-abandoned,
    nothing to compare; NaN compares ``False`` and is the finite gate's
    problem).  Returns ``(checked, viol, gap)`` — comparisons made,
    violations beyond tolerance, and the worst ``LB - DTW`` overshoot.
    """
    fin = jnp.isfinite(d) & jnp.isfinite(lb)
    if valid is not None:
        fin = fin & valid
    over = jnp.where(fin, lb - d, -_INF)
    viol = jnp.sum(fin & (lb > d * (1.0 + rtol) + atol))
    return (
        jnp.sum(fin).astype(jnp.float32),
        viol.astype(jnp.float32),
        jnp.maximum(jnp.max(over, initial=-_INF), 0.0).astype(jnp.float32),
    )


def conservation_check(cand: Array, n: int) -> tuple[Array, Array]:
    """Survivor-mass conservation across gather-compaction.

    The compaction's ``top_k`` must hand the pairwise tiers exactly
    ``W`` *distinct* candidates per query — a duplicated index means a
    live candidate was silently dropped from the pack (the shard_map
    miscompile shape: no error, one fewer real survivor refined).
    Returns ``(checked, viol)`` with one check per query.
    """
    Q, W = cand.shape
    marks = jnp.zeros((Q, n), jnp.int32).at[
        jnp.arange(Q)[:, None], cand
    ].add(1)
    distinct = jnp.sum(marks > 0, axis=1)
    return (
        jnp.asarray(float(Q), jnp.float32),
        jnp.sum(distinct != W).astype(jnp.float32),
    )


def scatter_monotone_check(lb_before: Array, lb_after: Array
                           ) -> tuple[Array, Array]:
    """The scatter-max back into the bound matrix can only tighten:
    ``lb_after >= lb_before`` everywhere (running max is monotone by
    construction — only a miscompiled gather/scatter breaks it).
    NaN entries compare ``False`` on both sides and are the finite
    gate's to count.  Returns ``(checked, viol)``, one check per query.
    """
    viol = jnp.sum(lb_after < lb_before)
    return (
        jnp.asarray(float(lb_before.shape[0]), jnp.float32),
        viol.astype(jnp.float32),
    )


# ---------------------------------------------------------------------------
# input hygiene (degradation ladder layer 3 — host-side, boundary only)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HygieneReport:
    """Host-side input-hygiene outcome (plain ints — never traced)."""

    bad_values: int = 0
    bad_series: int = 0
    flat_series: int = 0

    def any(self) -> bool:
        return bool(self.bad_values or self.flat_series)


def validate_series(
    x,
    *,
    name: str = "series",
    sanitize: bool = False,
    check_flat: bool = False,
) -> tuple[Array, HygieneReport]:
    """Reject or sanitize NaN/Inf values and zero-variance series.

    Host-side only (callers gate on concrete inputs).  Without
    ``sanitize`` any non-finite value — or, with ``check_flat``, any
    zero-variance series (z-norm turns those into all-zeros, which then
    matches *every* flat query at distance 0) — raises ``ValueError``
    naming the offending rows.  With ``sanitize=True`` non-finite values
    are masked to the series' finite mean (0.0 when nothing is finite),
    flat series are left numerically unchanged (``znorm``'s epsilon maps
    them to zeros), and everything found is counted into the returned
    ``HygieneReport`` plus a ``GuardWarning``.
    """
    arr = np.asarray(x, np.float32)
    bad = ~np.isfinite(arr)
    bad_rows = np.where(bad.any(axis=tuple(range(1, arr.ndim))))[0] \
        if arr.ndim > 1 else np.where(bad)[0]
    flat_rows = np.array([], np.int64)
    if check_flat and arr.ndim > 1:
        span = arr.max(axis=-1) - arr.min(axis=-1)
        span = np.where(np.isfinite(span), span, np.inf)  # bad rows != flat
        flat_rows = np.where(span == 0.0)[0]
    report = HygieneReport(
        bad_values=int(bad.sum()),
        bad_series=int(bad_rows.size),
        flat_series=int(flat_rows.size),
    )
    if not report.any():
        # clean path: hand back the caller's own array when it is already
        # on-device — validation must not cost a host->device copy
        out = x if isinstance(x, jax.Array) else jnp.asarray(arr)
        return out, report
    if not sanitize:
        msgs = []
        if report.bad_values:
            msgs.append(
                f"{report.bad_values} non-finite values in "
                f"{report.bad_series} {name} rows "
                f"(first: {bad_rows[:8].tolist()})"
            )
        if report.flat_series:
            msgs.append(
                f"{report.flat_series} zero-variance {name} rows "
                f"(first: {flat_rows[:8].tolist()}) — z-norm would map "
                "these to all-zeros"
            )
        raise ValueError(
            "; ".join(msgs)
            + f"; pass sanitize=True to mask and report instead"
        )
    if report.bad_values:
        clean = np.where(bad, np.nan, arr)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)  # all-NaN rows
            fill = np.nanmean(clean, axis=-1, keepdims=True)
        fill = np.where(np.isfinite(fill), fill, 0.0)
        arr = np.where(bad, np.broadcast_to(fill, arr.shape), arr)
    warnings.warn(
        f"sanitized {name}: masked {report.bad_values} non-finite values "
        f"in {report.bad_series} rows"
        + (f", {report.flat_series} zero-variance rows kept (z-norm maps "
           "them to zeros)" if report.flat_series else ""),
        GuardWarning,
        stacklevel=2,
    )
    return jnp.asarray(arr), report


def hygiene_to_report(h: HygieneReport) -> GuardReport:
    """Lift host-side hygiene counts into the pytree report so one
    ``GuardReport`` tells the whole story of a search."""
    r = GuardReport.zeros()
    return dataclasses.replace(
        r,
        hygiene_values=jnp.asarray(float(h.bad_values), jnp.float32),
        hygiene_series=jnp.asarray(float(h.bad_series), jnp.float32),
        hygiene_flat=jnp.asarray(float(h.flat_series), jnp.float32),
    )


# ---------------------------------------------------------------------------
# fault-injection seams (populated only by testing/faults.py)
# ---------------------------------------------------------------------------

_FAULT_HOOKS: dict[str, Callable] = {}


def fault_hook(name: str) -> Callable | None:
    """The injection seam: production call sites do one dict lookup that
    is ``None`` outside the fault harness.  Never install hooks here
    directly — use ``repro.testing.faults.inject`` so teardown is
    guaranteed."""
    return _FAULT_HOOKS.get(name)


# ---------------------------------------------------------------------------
# preflight (degradation ladder layer 0 — prove the compiled path)
# ---------------------------------------------------------------------------

_PREFLIGHT_CACHE: dict = {}


def preflight_clear() -> None:
    """Drop cached preflight verdicts (tests)."""
    _PREFLIGHT_CACHE.clear()


def _canary_store(n: int, length: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    series = rng.normal(size=(n, length)).astype(np.float32)
    queries = rng.normal(size=(max(2, n // 16), length)).astype(np.float32)
    return series, queries


def preflight_engine() -> bool:
    """Single-device self-test: the jitted engine must equal brute force
    on a canary store.  Cached per process; ``build_index(preflight=
    True)`` runs it before a store starts serving.  Returns ``True``
    when the compiled path is exact; on mismatch warns (once) and
    returns ``False`` — callers stay on the guarded/degraded paths.
    """
    key = ("engine", jax.__version__)
    hit = _PREFLIGHT_CACHE.get(key)
    if hit is not None:
        return hit
    from repro.search.engine import EngineConfig, brute_force, nn_search
    from repro.search.cascade import CascadeConfig
    from repro.search.index import build_index

    series, queries = _canary_store(32, 16)
    idx = build_index(series, 4)
    cfg = EngineConfig(
        cascade=CascadeConfig(w=4, v=4, candidate_chunk=8, use_pallas=False),
        verify_chunk=4, k=2,
    )
    got = jax.jit(lambda q: nn_search(idx, q, cfg).dists)(
        jnp.asarray(queries)
    )
    want, _ = brute_force(idx, queries, 4, k=2, use_pallas=False)
    ok = bool(np.allclose(np.asarray(got), np.asarray(want), rtol=1e-4))
    if not ok:
        # the verdict is cached below, so this fires once per process
        warnings.warn(
            "preflight: jitted single-device engine does not match brute "
            "force on the canary store — keep runtime guards on and "
            "expect degradation reruns",
            GuardWarning,
            stacklevel=2,
        )
    _PREFLIGHT_CACHE[key] = ok
    return ok
