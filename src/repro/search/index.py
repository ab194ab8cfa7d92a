"""Candidate-store index for lower-bounded NN-DTW search.

The index precomputes everything that depends only on the store and the
window ``w`` (paper SS II-B: envelopes are query-independent, so an index
amortises them across every query): the Sakoe-Chiba envelopes, the O(1)
Kim feature vector, and the int8 PAA *sketch* of every candidate.

Sketch store layout (tier -1, ``search/pipeline.py``):

  Every tier before this one reads the full ``(N, L)`` float32 store, so
  store *size* — not compute — is the scaling wall for HBM-scale corpora.
  The sketch is a segment-reduced, quantised view of the candidate's
  w-envelope: split the length axis into ``S`` segments (power of two,
  default 16; boundaries ``b[j] = j*L//S``, so ragged lengths are fine),
  take the per-segment mean of the upper/lower envelope, and quantise
  with *outward* rounding — ``ceil`` for the upper cells, ``floor`` for
  the lower — onto a shared symmetric int8 grid:

    sk_hi[n, j] = ceil(mean(upper[n, b[j]:b[j+1]]) / scale)   int8
    sk_lo[n, j] = floor(mean(lower[n, b[j]:b[j+1]]) / scale)  int8
    sk_scale    = max|segment cell| / 127 * (1 + 1e-6)        f32 scalar

  (the 1e-6 headroom keeps ``|cell/scale|`` strictly below 127, so the
  clip after ceil/floor can never round *inward* — quantisation only ever
  widens the envelope, which is what keeps the dequantised bound
  admissible; ``testing/faults.py::inward_quantiser`` proves the guard
  trips when this is violated).  The bound itself is the segment-reduced
  LB_Keogh (Cauchy-Schwarz over each segment):

    LB_sketch(q, n) = sum_j n_j * max(qbar_j - sk_hi[n,j]*scale,
                                      sk_lo[n,j]*scale - qbar_j, 0)^2
                    <= LB_Keogh(q, n) <= DTW_w(q, n)

  at 2*S = 32 bytes/candidate — a 10M-candidate sketch store is ~320 MB
  and stays on-chip where the raw series cannot.

Store-level candidate mask (``build_index(..., calibrate=cfg, mask=True)``):

  ``live[n]`` marks candidates some leave-one-out calibration query keeps
  below its measured seed threshold: after plan calibration, each sampled
  query's k seed distances give ``tau_i`` (an upper bound on its true
  k-th NN distance), and ``live[n] = any_i(LB_sketch(i, n) <= tau_i *
  mask_safety)``.  A committed plan threads ``live`` into the existing
  cross-block / pairwise liveness inputs (the kernels already take it),
  so dense-tier work on dead candidates becomes skipped tiles.  Exactness
  does not depend on the mask being right: masked tiers emit ``-inf``
  for dead candidates, whose *unmasked* cheap-tier bounds (sketch, Kim)
  stay in the running max — a dead candidate is still pruned by a valid
  bound or verified, never silently excluded.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.distances import znorm
from repro.kernels.ops import envelope_op

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DTWIndex:
    """Immutable candidate store + per-candidate precomputation.

    Attributes:
      series:  (N, L) candidate series (z-normalised if built with znorm).
      labels:  (N,) int labels (or -1s when unlabelled).
      upper:   (N, L) upper envelopes for window ``w``.
      lower:   (N, L) lower envelopes.
      kim:     (N, 4) [first, last, max, min] Kim features.
      kim_ok:  (N, 2) feature-admissibility flags [max interior, min interior].
      w:       static window the envelopes were built for.
      sk_lo:   (N, S) int8 outward-quantised lower envelope segment means
               (or None when built without a sketch).
      sk_hi:   (N, S) int8 upper counterpart.
      sk_scale: () f32 shared symmetric dequantisation scale.
      live:    (N,) bool store-level candidate mask (None = all live);
               see the module docstring for derivation and exactness.
    """

    series: Array
    labels: Array
    upper: Array
    lower: Array
    kim: Array
    kim_ok: Array
    w: int = dataclasses.field(metadata=dict(static=True))
    sk_lo: Array | None = None
    sk_hi: Array | None = None
    sk_scale: Array | None = None
    live: Array | None = None

    @property
    def n(self) -> int:
        return self.series.shape[0]

    @property
    def length(self) -> int:
        return self.series.shape[1]


def kim_features(x: Array) -> tuple[Array, Array]:
    """Per-series Kim features + interior-witness flags (see lb_kim)."""
    L = x.shape[-1]
    first = x[..., 0]
    last = x[..., -1]
    mx = jnp.max(x, -1)
    mn = jnp.min(x, -1)
    imax = jnp.argmax(x, -1)
    imin = jnp.argmin(x, -1)
    feats = jnp.stack([first, last, mx, mn], axis=-1)
    ok = jnp.stack(
        [(imax != 0) & (imax != L - 1), (imin != 0) & (imin != L - 1)],
        axis=-1,
    )
    return feats, ok


def sketch_segments(L: int, s: int) -> tuple[tuple[int, int], ...]:
    """Static segment boundaries ``b[j] = j*L//s`` as (start, stop) pairs.

    ``s`` is halved (power-of-two discipline) while it exceeds ``L`` so a
    short store never produces empty segments; ragged lengths (``L`` not
    divisible by ``s``) give segments differing by one step.
    """
    s = max(1, int(s))
    while s > L:
        s //= 2
    bounds = [j * L // s for j in range(s + 1)]
    return tuple((bounds[j], bounds[j + 1]) for j in range(s))


def sketch_segment_sizes(L: int, s: int) -> Array:
    """``(S,)`` f32 segment lengths ``n_j`` (the bound's per-segment
    Cauchy-Schwarz weights)."""
    return jnp.asarray(
        [b - a for a, b in sketch_segments(L, s)], jnp.float32
    )


def sketch_query_means(q: Array, s: int) -> Array:
    """Per-segment f32 means of a query batch: ``(..., L) -> (..., S)``.

    Query-side featurisation stays float (queries arrive at search time;
    only the *store* side is quantised, and only outward)."""
    segs = sketch_segments(q.shape[-1], s)
    return jnp.stack(
        [jnp.mean(q[..., a:b], axis=-1) for a, b in segs], axis=-1
    )


def sketch_features(
    upper: Array, lower: Array, s: int = 16
) -> tuple[Array, Array, Array]:
    """Quantise ``(N, L)`` w-envelopes into the int8 sketch store.

    Returns ``(sk_lo, sk_hi, sk_scale)`` — see the module docstring for
    the layout and the admissibility argument.  Outward rounding is the
    load-bearing invariant: ``sk_hi * scale >= mean(upper)`` and
    ``sk_lo * scale <= mean(lower)`` cell-wise, always.
    """
    segs = sketch_segments(upper.shape[-1], s)
    useg = jnp.stack(
        [jnp.mean(upper[..., a:b], axis=-1) for a, b in segs], axis=-1
    )
    lseg = jnp.stack(
        [jnp.mean(lower[..., a:b], axis=-1) for a, b in segs], axis=-1
    )
    maxabs = jnp.maximum(jnp.max(jnp.abs(useg)), jnp.max(jnp.abs(lseg)))
    # 1e-6 headroom: |cell/scale| < 127 strictly, so the clip below can
    # never pull a ceil'd/floor'd cell back inward
    scale = jnp.where(maxabs > 0.0, maxabs, 1.0) * ((1.0 + 1e-6) / 127.0)
    sk_hi = jnp.clip(jnp.ceil(useg / scale), -127, 127).astype(jnp.int8)
    sk_lo = jnp.clip(jnp.floor(lseg / scale), -127, 127).astype(jnp.int8)
    from repro.search import guards as _guards

    hook = _guards.fault_hook("sketch_feats")
    if hook is not None:
        sk_lo, sk_hi = hook(sk_lo, sk_hi)
    return sk_lo, sk_hi, scale.astype(jnp.float32)


def build_index(
    series: Array,
    w: int,
    labels: Array | None = None,
    *,
    normalize: bool = False,
    sanitize: bool = False,
    preflight: bool = False,
    calibrate: Any | None = None,
    calibrate_sample: int = 8,
    sketch: int | None = 16,
    mask: bool = False,
    mask_safety: float = 2.0,
) -> DTWIndex:
    """Build a ``DTWIndex`` for window ``w``.

    Input hygiene (concrete inputs; skipped under tracing like the
    calibration below): a store containing NaN/Inf raises — one poisoned
    value flows silently into envelopes, Kim features, and every bound
    otherwise — as does, with ``normalize=True``, a zero-variance series
    (z-norm maps it to all-zeros, which then matches every flat query at
    distance ~0).  ``sanitize=True`` masks bad values to the per-series
    finite mean, keeps flat series (znorm's epsilon maps them to zeros),
    and reports everything via a ``GuardWarning``
    (guards.validate_series).

    ``preflight`` runs ``guards.preflight_engine()`` — the single-device
    jitted-engine-vs-brute-force canary — before the store is returned,
    warning (once per process) if the compiled path is not exact on this
    jax install.

    ``calibrate`` (an ``EngineConfig`` or ``CascadeConfig``) runs store-
    level plan calibration at build time: a ``calibrate_sample``-series
    sample of the store itself is searched leave-one-out through the
    instrumented tier pipeline and the planner's optimised plan is
    committed for this store/config (search/planner.py), so repeated-
    query serving starts warm — the first real query batch runs the
    committed plan instead of paying a calibration block.  The LOO
    exclusion keeps the measured threshold honest (a self-match would
    collapse ``tau`` to zero, the same argument as
    ``choose_survivor_budget``), and a LOO-calibrated plan is
    conservative for plain queries, so the committed decision serves
    both.  Calibration requires concrete (host) inputs; it is skipped
    for unstaged cascades.

    ``sketch`` sets the segment count ``S`` of the int8 PAA sketch store
    (module docstring; ``None`` skips featurisation — the sketch tier
    then scores a trivial all-zero bound and the planner drops it as
    idle).  ``mask=True`` (requires ``calibrate`` and a sketch) derives
    the store-level ``live`` mask from LOO sketch mass *before* the plan
    is calibrated, so the committed plan prices the masked tiers;
    ``mask_safety`` scales the per-query seed threshold (squared-distance
    units) the mask admits candidates under — larger is more
    conservative (more candidates stay live).
    """
    series = jnp.asarray(series, jnp.float32)
    if not isinstance(series, jax.core.Tracer):
        from repro.search import guards as _guards

        series, _ = _guards.validate_series(
            series, name="series", sanitize=sanitize, check_flat=normalize,
        )
        if preflight:
            _guards.preflight_engine()
    if normalize:
        series = znorm(series)
    if labels is None:
        labels = jnp.full((series.shape[0],), -1, jnp.int32)
    u, lo = envelope_op(series, w)
    kim, kim_ok = kim_features(series)
    sk_lo = sk_hi = sk_scale = None
    if sketch is not None:
        sk_lo, sk_hi, sk_scale = sketch_features(u, lo, sketch)
    index = DTWIndex(
        series=series,
        labels=jnp.asarray(labels, jnp.int32),
        upper=u,
        lower=lo,
        kim=kim,
        kim_ok=kim_ok,
        w=w,
        sk_lo=sk_lo,
        sk_hi=sk_hi,
        sk_scale=sk_scale,
    )
    if calibrate is not None:
        from repro.search.planner import calibrate_plan, calibration_sample

        cascade = getattr(calibrate, "cascade", calibrate)
        k = int(getattr(calibrate, "k", 1))
        if cascade.staged and not isinstance(series, jax.core.Tracer):
            # strided store sample: class-ordered stores get every class
            # into the measurement (planner.calibration_sample)
            pick = calibration_sample(index.n, calibrate_sample)
            if mask and index.sk_lo is not None:
                index = _derive_live_mask(
                    index, cascade, k, pick, mask_safety
                )
            calibrate_plan(
                index.series[pick], index, cascade, k,
                exclude=jnp.asarray(pick, jnp.int32), sample=len(pick),
                pcfg=getattr(calibrate, "planner", None),
            )
    return index


def _derive_live_mask(index, cascade, k, pick, mask_safety):
    """LOO store-level mask: candidates no calibration query keeps.

    Runs the cascade once on the calibration sample (leave-one-out
    exclusion, like the plan calibration that follows) for the measured
    seed thresholds ``tau_i`` — each an *upper* bound on query ``i``'s
    true k-th NN distance, so thresholding the admissible sketch bound
    under ``tau_i * mask_safety`` only over-admits, never over-kills, on
    the calibration distribution.  Derived before ``calibrate_plan`` so
    the committed plan prices the masked tiers.
    """
    import dataclasses as _dc

    from repro.kernels.ref import sketch_bound_ref
    from repro.search.cascade import run_plan

    qs = index.series[pick]
    cres = run_plan(
        qs, index, cascade, k=k, exclude=jnp.asarray(pick, jnp.int32)
    )
    tau = jnp.max(
        jnp.where(jnp.isfinite(cres.seed_d), cres.seed_d, 0.0), axis=1
    )
    qbar = sketch_query_means(qs, index.sk_lo.shape[1])
    seg = sketch_segment_sizes(index.length, index.sk_lo.shape[1])
    sb = sketch_bound_ref(qbar, index.sk_lo, index.sk_hi,
                          index.sk_scale, seg)
    live = jnp.any(sb <= tau[:, None] * mask_safety + 1e-6, axis=0)
    return _dc.replace(index, live=live)
