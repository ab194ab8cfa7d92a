"""Compile every served Pallas kernel for a v5e chip, at the paper's
deployment shapes (configs/paper_dtw.py: L=512, w=154, V=4) and at the
long-series deployment's (UEA EigenWorms: L=17984, w=L, through the
L-blocked bound kernels and the full-band streaming DTW grid), without a
chip: the TPU compiler is installed and compiles for a described
topology.  Interpret mode (the CPU suite) never checks Mosaic's layout
rules; these tests do, at about a second per kernel.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and a worker that imports this file
without running it must not take it.  Everything compiles in this
process, with the persistent compilation cache off (a compile for an
absent chip cannot be read back).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.paper_dtw import PAPER_SEARCH
from repro.kernels.dtw_band import dtw_band_pallas
from repro.kernels.envelope import envelope_pallas
from repro.kernels.lb_enhanced import lb_enhanced_pallas
from repro.kernels.lb_enhanced_pairwise import lb_enhanced_pairwise_pallas
from repro.kernels.lb_keogh import lb_keogh_pallas
from repro.kernels.sketch import sketch_bound_pallas

L, W, V = PAPER_SEARCH.length, PAPER_SEARCH.w, PAPER_SEARCH.v
LONG = 17984              # bench/configs/uea_eigenworms.json, w = L
MID = 8192
F32, I32, I8 = jnp.float32, jnp.int32, jnp.int8

# name -> (kernel call, argument shapes); every call compiles for the chip
CASES = {
    "dtw_band_resident_tile128": (
        lambda a, b, cut, **kw: dtw_band_pallas(a, b, W, cut, tile_p=128,
                                                **kw),
        [((1024, L), F32), ((1024, L), F32), ((1024,), F32)]),
    "dtw_band_resident_tile32": (
        lambda a, b, cut, **kw: dtw_band_pallas(a, b, W, cut, tile_p=32,
                                                **kw),
        [((512, L), F32), ((512, L), F32), ((512,), F32)]),
    "dtw_band_stream_L65536": (
        lambda a, b, cut, **kw: dtw_band_pallas(a, b, 655, cut,
                                                stream=True, **kw),
        [((64, 65536), F32), ((64, 65536), F32), ((64,), F32)]),
    "lb_enhanced": (
        functools.partial(lb_enhanced_pallas, w=W, v=V),
        [((64, L), F32)] + [((1024, L), F32)] * 3),
    "lb_enhanced_bands_live": (
        lambda q, c, u, lo, live, **kw: lb_enhanced_pallas(
            q, c, u, lo, W, V, live=live, bands_only=True, **kw),
        [((64, L), F32)] + [((1024, L), F32)] * 3 + [((1024,), I32)]),
    "lb_enhanced_pairwise_live": (
        lambda q, c, u, lo, live, **kw: lb_enhanced_pairwise_pallas(
            q, c, u, lo, W, V, live=live, **kw),
        [((1024, L), F32)] * 4 + [((1024,), I32)]),
    "sketch_Q2048": (
        sketch_bound_pallas,
        [((2048, 16), F32), ((8192, 16), I8), ((8192, 16), I8),
         ((16,), F32)]),
    "envelope": (
        functools.partial(envelope_pallas, w=W),
        [((1024, L), F32)]),
    "lb_keogh": (
        lb_keogh_pallas,
        [((64, L), F32), ((1024, L), F32), ((1024, L), F32)]),
    # one query against a 128-series store at full window: the rounds'
    # pair batch streams a 35968-lane band state; the bound kernels
    # reduce over nine L blocks
    "dtw_band_stream_full_band_L17984": (
        lambda a, b, cut, **kw: dtw_band_pallas(a, b, LONG, cut,
                                                stream=True, tile_p=32,
                                                **kw),
        [((32, LONG), F32), ((32, LONG), F32), ((32,), F32)]),
    "lb_enhanced_pairwise_live_L17984": (
        lambda q, c, u, lo, live, **kw: lb_enhanced_pairwise_pallas(
            q, c, u, lo, LONG, V, live=live, **kw),
        [((128, LONG), F32)] * 4 + [((128,), I32)]),
    "lb_enhanced_L17984": (
        functools.partial(lb_enhanced_pallas, w=LONG, v=V),
        [((8, LONG), F32)] + [((128, LONG), F32)] * 3),
    "lb_keogh_L17984": (
        lb_keogh_pallas,
        [((8, LONG), F32), ((128, LONG), F32), ((128, LONG), F32)]),
    "envelope_full_window_L17984": (
        functools.partial(envelope_pallas, w=LONG),
        [((128, LONG), F32)]),
    # a store of many tiles at L=8192: bound kernels that held whole
    # (tile, L) rows, double-buffered across grid steps, ran out of
    # Mosaic's 16 MiB scoped VMEM here (the pairwise kernel from L=4096)
    "lb_keogh_L8192_store1024": (
        lb_keogh_pallas,
        [((64, MID), F32), ((1024, MID), F32), ((1024, MID), F32)]),
    "lb_enhanced_bands_live_L8192_store1024": (
        lambda q, c, u, lo, live, **kw: lb_enhanced_pallas(
            q, c, u, lo, MID, V, live=live, bands_only=True, **kw),
        [((64, MID), F32)] + [((1024, MID), F32)] * 3 + [((1024,), I32)]),
    "lb_enhanced_L8192_store1024": (
        functools.partial(lb_enhanced_pallas, w=MID, v=V),
        [((64, MID), F32)] + [((1024, MID), F32)] * 3),
    "lb_enhanced_pairwise_L8192_P1024": (
        lambda q, c, u, lo, **kw: lb_enhanced_pairwise_pallas(
            q, c, u, lo, MID, V, **kw),
        [((1024, MID), F32)] * 4),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    kernel = functools.partial(fn, interpret=False)
    text = jax.jit(kernel).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
