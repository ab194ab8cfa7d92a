"""``chip_smoke.py`` at a tiny size on the CPU (kernels interpreted): its
phases must pass their own exactness checks, and its entry point must
refuse to report a result anywhere but on a TPU."""

from __future__ import annotations

import importlib.util
import os
import shutil
import subprocess
import sys
import warnings
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _loud(caught):
    from repro.kernels.ops import KernelFallbackWarning
    from repro.search import GuardWarning

    return [w for w in caught
            if issubclass(w.category, (GuardWarning, KernelFallbackWarning))]


def test_search_phase_tiny_is_exact(smoke):
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = smoke.search_phase(n=512, length=64, w=12, n_queries=8,
                                 n_check=4, candidate_chunk=128)
    assert not _loud(caught), [str(w.message) for w in _loud(caught)]
    assert out["steady_s"] > 0


def test_stream_phase_tiny_is_exact(smoke):
    from repro.kernels import ops

    out = smoke.stream_phase(length=ops._DTW_RESIDENT_MAX_L + 8, w=2,
                             n_pairs=2)
    assert out["stream_s"] > 0


def test_sharded_phase_on_the_devices_present_is_exact(smoke):
    out = smoke.sharded_phase(n=256, length=32, w=4, n_queries=4, n_check=4,
                              candidate_chunk=64)
    assert out["steady_s"] > 0


def test_served_kernels_reads_custom_call_names(smoke):
    hlo = "\n".join([
        '  %a = f32[8] custom-call(%x), custom_call_target="tpu_custom_call",'
        ' metadata={op_name="jit(step)/jit(dtw_band_pallas)/pallas_call"}',
        '  %b = f32[8] custom-call(%y), custom_call_target="tpu_custom_call",'
        ' metadata={op_name="jit(step)/jit(dtw_band_pallas)/pallas_call"}',
        '  %c = f32[8] custom-call(%z), custom_call_target="tpu_custom_call",'
        ' metadata={op_name="jit(step)/jit(sketch_bound_pallas)/pallas_call"}',
        '  %d = f32[8] add(%x, %y)',
    ])
    found = smoke.served_kernels(hlo)
    assert found == {"dtw_band_pallas": 2, "sketch_bound_pallas": 1}
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_kernels(hlo, {"dtw_band_pallas", "envelope_pallas"},
                            "test")


def test_main_refuses_without_a_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_main_refuses_outside_a_checkout(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(REPO / "chip_smoke.py", lone)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, str(lone)], cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_compile_cache_honours_env_else_checkout(monkeypatch):
    from repro.launch import cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", was)
        assert cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was   # set nothing
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = cache.enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
