"""Production mesh construction.

Pure functions only — importing this module must never touch jax device
state (the dry-run sets XLA_FLAGS for 512 host devices *before* any jax
import; smoke tests see the default single device).
"""

from __future__ import annotations

import math

import jax


def _auto_axes(n: int) -> tuple:
    """Every mesh axis ``Auto``: the compiler propagates the shardings."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """The target deployment mesh: one v5e-class 16x16 pod (256 chips), or
    two pods (512 chips) with a leading pure-DP ``pod`` axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devices)} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before importing jax for the dry-run)"
        )
    return jax.make_mesh(
        shape,
        axes,
        devices=devices,
        axis_types=_auto_axes(len(axes)),
    )


def make_host_mesh(
    shape: tuple[int, ...], axes: tuple[str, ...]
) -> jax.sharding.Mesh:
    """Small helper mesh for tests (uses however many devices exist)."""
    n = math.prod(shape)
    return jax.make_mesh(
        shape,
        axes,
        devices=jax.devices()[:n],
        axis_types=_auto_axes(len(axes)),
    )
