"""Jitted public entry points for the Pallas kernels.

Dispatch policy:
  * on TPU backends the kernels run compiled (interpret=False);
  * on CPU they run in ``interpret=True`` mode, which executes the kernel
    bodies in Python for bit-faithful validation;
  * any other backend raises: there is no silent third path;
  * shapes outside kernel limits (an envelope longer than
    ``_ENVELOPE_MAX_L``, a DTW band whose state exceeds VMEM) fall back to
    the pure-jnp reference with a ``KernelFallbackWarning`` (once per
    kernel and shape) and the ``repro.obs`` counter ``kernel_fallbacks``,
    so the public API never fails on shape grounds and a fallback is
    never silent.  The bound kernels have no length limit: past
    ``tiling.LB_BLOCK_L`` columns they reduce over L blocks.

Every call of a bound op counts its grid (``bound_grid``: ``resident`` or
``l_blocked``) and every banded-DTW call its grid (``verify_grid``:
``resident`` or ``stream``) in ``repro.obs``.

All entry points accept/return plain arrays and are safe to ``jax.jit``
(and to call inside ``shard_map`` — see search/distributed.py).
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ref
from repro.kernels.dtw_band import dtw_band_pallas
from repro.kernels.envelope import envelope_pallas
from repro.kernels.lb_enhanced import lb_enhanced_pallas
from repro.kernels.lb_enhanced_pairwise import lb_enhanced_pairwise_pallas
from repro.kernels.lb_keogh import lb_keogh_pallas
from repro.kernels.mamba_scan import mamba_scan_pallas
from repro.kernels.sketch import sketch_bound_pallas
from repro.kernels.tiling import (
    apply_pair_perm,
    lb_block_l,
    stream_geometry,
    stream_vmem_budget,
)

Array = jax.Array

# The envelope kernel holds 3 blocks of (8, L) f32 rows, double-buffered:
# 6 MB at this length (kernels/envelope.py); longer series fall back.
_ENVELOPE_MAX_L = 65536
# Above this length the packed DTW operands stop being VMEM-resident and
# dtw_band_op switches to the streaming DMA-pipeline grid.  That grid has
# no length ceiling: only a band whose state alone exceeds the chip's
# streaming budget at the sublane floor falls back (w = L at L = 64k;
# w = L at L = 17984 fits a v5e, tiling.stream_vmem_budget).
_DTW_RESIDENT_MAX_L = 16384


class KernelFallbackWarning(UserWarning):
    """A kernel op served a shape through its jnp reference instead."""


def _interpret() -> bool:
    """Interpret the kernels on CPU, compile them on TPU, refuse the rest."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run compiled on tpu or interpreted on cpu; "
            f"the default backend is {backend!r}"
        )
    return backend == "cpu"


def _fallback(kernel: str, shape, why: str) -> None:
    """Warn that ``kernel`` serves ``shape`` through its jnp reference.

    The message names the kernel and the shape, so the warnings module's
    per-message registry shows it once per kernel and shape.
    """
    obs.count("kernel_fallbacks", kernel)
    warnings.warn(
        f"{kernel}: shape {tuple(shape)} served by the jnp reference ({why})",
        KernelFallbackWarning,
        stacklevel=3,
    )


def _count_grid(counter: str, grid: str, x) -> None:
    """Count one op call under its grid (``repro.obs``); a call traced
    into a program counts only where the program replays it."""
    obs.count(counter, grid, traced=isinstance(x, jax.core.Tracer))


def _bound_grid(q, bands_only: bool = False) -> None:
    blocked = not bands_only and lb_block_l(q.shape[-1]) < q.shape[-1]
    _count_grid("bound_grid", "l_blocked" if blocked else "resident", q)


def envelope_op(b: Array, w: int) -> tuple[Array, Array]:
    """Batched Sakoe-Chiba envelopes ``(N, L) -> (U, L)`` pair."""
    b = jnp.asarray(b)
    squeeze = b.ndim == 1
    if squeeze:
        b = b[None]
    if b.shape[-1] > _ENVELOPE_MAX_L:
        _fallback("envelope", b.shape, f"L > {_ENVELOPE_MAX_L}")
        u, lo = ref.envelope_ref(b, w)
    else:
        u, lo = envelope_pallas(b, w, interpret=_interpret())
    return (u[0], lo[0]) if squeeze else (u, lo)


def lb_keogh_op(q: Array, u: Array, lo: Array) -> Array:
    """``(Q, L) x (C, L) envelopes -> (Q, C)`` LB_KEOGH matrix."""
    _bound_grid(q)
    return lb_keogh_pallas(q, u, lo, interpret=_interpret())


def lb_enhanced_op(
    q: Array, c: Array, u: Array, lo: Array, w: int, v: int,
    *, live: Array | None = None, bands_only: bool = False,
) -> Array:
    """``(Q, L) x (C, L) -> (Q, C)`` fused LB_ENHANCED^V matrix.

    ``live`` (optional ``(C,)``) marks the candidates worth scoring: dead
    candidates return ``-inf`` for every query (the running-max identity)
    and fully-dead candidate tiles skip their compute — liveness parity
    with the pairwise kernel, so the planner can limit-mask a dense
    cross-block tier too (see kernels/lb_enhanced.py).
    """
    _bound_grid(q, bands_only)
    return lb_enhanced_pallas(
        q, c, u, lo, w, v, live=live, bands_only=bands_only,
        interpret=_interpret(),
    )


def lb_enhanced_pairwise_op(
    q: Array, c: Array, u: Array, lo: Array, w: int, v: int,
    *, live: Array | None = None, bands_only: bool = False,
) -> Array:
    """``(P, L) x (P, L) -> (P,)`` pairwise LB_ENHANCED^V bounds.

    The staged cascade's tier-2 shape: gather-compacted (query, candidate)
    survivor pairs, one bound per packed row (see
    kernels/lb_enhanced_pairwise.py vs the cross-block lb_enhanced.py).

    ``live`` (optional ``(P,)``) marks the slots the compaction policy
    allocated for refinement: dead slots return ``-inf`` and fully-dead
    pair tiles skip their compute — the global survivor budget's refine
    limits become skipped work, not masked outputs.
    """
    _bound_grid(q, bands_only)
    return lb_enhanced_pairwise_pallas(
        q, c, u, lo, w, v, live=live, bands_only=bands_only,
        interpret=_interpret(),
    )


def sketch_bound_op(
    qbar: Array, sk_lo: Array, sk_hi: Array, sk_scale: Array,
    seg_sizes: Array,
) -> Array:
    """``(Q, S) f32 x (N, S) int8 -> (Q, N)`` tier-(-1) sketch bounds.

    The quantised segment-reduced LB_Keogh over the int8 PAA sketch
    store (search/index.py documents the layout; kernels/sketch.py the
    kernel).  Host-side it rewrites the operands into the kernel's
    scaled-units form — the kernel never sees ``sk_scale``.
    """
    qbar = jnp.asarray(qbar, jnp.float32)
    scale = jnp.asarray(sk_scale, jnp.float32)
    qs = qbar / scale
    wseg = jnp.asarray(seg_sizes, jnp.float32) * scale * scale
    return sketch_bound_pallas(qs, sk_lo, sk_hi, wseg,
                               interpret=_interpret())


def dtw_band_op(
    a: Array, b: Array, w: int | None = None, cutoff: Array | None = None,
    *, early_exit: bool = True, perm: Array | None = None,
    tile_p: int | None = None,
) -> Array:
    """Pairwise banded DTW ``(P, L) x (P, L) -> (P,)``.

    ``cutoff`` (optional, per-pair) early-abandons lanes whose running
    frontier minimum proves the distance exceeds it (returns +inf there).
    With ``early_exit`` (default) the kernel runs the row-block grid that
    skips whole anti-diagonal blocks once every lane in a pair tile is
    abandoned; ``early_exit=False`` is PR 1's per-step lane-poisoning
    sweep, kept for the benchmark trajectory.

    ``perm`` (optional, a permutation of ``arange(P)``) is a *pair-packing
    gather*: operand rows are gathered by ``perm`` before the kernel and
    outputs scattered back (kernels/tiling.py), so the caller chooses
    which pairs share a pair tile — the engine's bound-ordered schedule
    clusters doomed pairs so the tile-level early exit fires per cluster —
    without the kernel, or the results, changing at all.

    ``tile_p`` (optional) caps the pair-tile size — the scheduler hook
    behind ``VerificationPlan.verify_tile_p``: bound-ordered rounds pick
    smaller tiles so the liveness exit fires on cluster boundaries (see
    tiling.sched_pair_tile).  ``None`` keeps the kernel default.  Packing
    geometry only; results are invariant under it.

    Length dispatch: series up to ``_DTW_RESIDENT_MAX_L`` run the
    VMEM-resident grid; longer series run the streaming DMA pipeline
    (operands in HBM, double-buffered per-block windows — no length
    ceiling), sized to the chip's streaming budget
    (``tiling.stream_vmem_budget``: 32 MiB of a v5e's 128 MiB VMEM, under
    an explicit 96 MiB ``vmem_limit_bytes``).  The streaming grid *is*
    the liveness grid, so past the crossover ``early_exit=False`` is
    ignored — the no-early-exit baseline is a VMEM-resident kernel by
    construction and only exists below the crossover (benchmark it
    there).  Only
    shapes whose *band state* exceeds that budget at the sublane floor
    (``stream_geometry`` returns None: w = L at L = 64k, where w = L at
    L = 17984 fits) fall back to the jnp reference, so the public API
    never fails on shape grounds.
    """
    if perm is not None:
        return apply_pair_perm(
            lambda x, y, c: dtw_band_op(x, y, w, c, early_exit=early_exit,
                                        tile_p=tile_p),
            perm, a, b, cutoff,
        )
    P, L = a.shape
    tp = 128 if tile_p is None else tile_p
    if L > _DTW_RESIDENT_MAX_L:
        wb = min(L if (w is None or w >= L) else w, L - 1)
        if stream_geometry(L, wb, tp, P, stream_vmem_budget()) is None:
            _fallback("dtw_band", a.shape,
                      f"band state of w={wb} exceeds VMEM")
            out = ref.dtw_band_ref(a, b, w, cutoff)
        else:
            _count_grid("verify_grid", "stream", a)
            out = dtw_band_pallas(
                a, b, w, cutoff, stream=True, tile_p=tp,
                interpret=_interpret(),
            )
    else:
        _count_grid("verify_grid", "resident", a)
        out = dtw_band_pallas(
            a, b, w, cutoff, early_exit=early_exit, tile_p=tp,
            interpret=_interpret(),
        )
    # fault seam (search/guards.py): the jnp reference mirrors do NOT
    # pass through here, so the guard subsystem's degradation rerun
    # (use_pallas=False) bypasses an injected kernel fault — the
    # property tests/test_guards.py relies on.  Imported lazily:
    # kernels must stay importable without the search package
    from repro.search.guards import fault_hook

    hook = fault_hook("dtw_out")
    return out if hook is None else hook(out)


# ---------------------------------------------------------------------------
# Fused Mamba selective scan (forward = Pallas kernel; backward recomputes
# through the differentiable chunked-scan reference — same recompute policy
# the remat'd scan path uses, so training numerics are identical).
# ---------------------------------------------------------------------------

@jax.custom_vjp
def mamba_scan_op(delta, u, A, Bmat, Cmat, h0):
    """Fused selective scan: (y (B,S,C), h_final (B,C,N))."""
    return mamba_scan_pallas(delta, u, A, Bmat, Cmat, h0,
                             interpret=_interpret())


def _mamba_fwd(delta, u, A, Bmat, Cmat, h0):
    out = mamba_scan_op(delta, u, A, Bmat, Cmat, h0)
    return out, (delta, u, A, Bmat, Cmat, h0)


def _mamba_bwd(res, cts):
    from repro.models.mamba import _chunked_selective_scan

    delta, u, A, Bmat, Cmat, h0 = res
    _, vjp = jax.vjp(
        lambda d, uu, a, bm, cm, h: _chunked_selective_scan(
            d, uu, a, bm, cm, h, chunk=256
        ),
        delta, u, A, Bmat, Cmat, h0,
    )
    return vjp(cts)


mamba_scan_op.defvjp(_mamba_fwd, _mamba_bwd)


# ---------------------------------------------------------------------------
# Fused flash attention (forward = Pallas kernel; backward recomputes
# through the chunked-jnp reference, matching the remat policy).
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_op(q, k, v, causal=True, window=None, score_cap=None):
    """Fused self-attention forward: (B, Sq, Hq, D) x (B, Skv, Hkv, D)."""
    from repro.kernels.flash_attention import flash_attention_pallas

    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, score_cap=score_cap,
        interpret=_interpret(),
    )


def _fa_fwd(q, k, v, causal, window, score_cap):
    return flash_attention_op(q, k, v, causal, window, score_cap), (q, k, v)


def _fa_bwd(causal, window, score_cap, res, ct):
    from repro.models.attention import flash_attention

    q, k, v = res
    B, Sq = q.shape[0], q.shape[1]
    Skv = k.shape[1]
    pos_q = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32)[None], (B, Sq))
    pos_k = jnp.broadcast_to(jnp.arange(Skv, dtype=jnp.int32)[None], (B, Skv))
    _, vjp = jax.vjp(
        lambda qq, kk, vv: flash_attention(
            qq, kk, vv, pos_q, pos_k, causal=causal, window=window,
            score_cap=score_cap,
        ),
        q, k, v,
    )
    return vjp(ct)


flash_attention_op.defvjp(_fa_fwd, _fa_bwd)
