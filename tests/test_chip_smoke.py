"""chip_smoke.py's phases at tiny sizes on the CPU, its refusal to run
without a TPU, and the compile-cache helper."""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.launch import cache

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_stencil_phases_tiny(smoke, capsys):
    first = smoke.stencil_kernel((40, 16, 32), blocks=(10, 8), sweeps=2,
                                 interpret=True)
    assert first.shape == (40, 16, 32)
    smoke.stencil_runtime(first)
    out = capsys.readouterr().out
    assert "sweep 1:" in out and "steals=" in out


def test_stencil_runtime_rejects_wrong_expectation(smoke):
    first = smoke.stencil_kernel((20, 8, 16), blocks=(10, 8), sweeps=1,
                                 interpret=True)
    with pytest.raises(RuntimeError, match="differs"):
        smoke.stencil_runtime(first + 1.0)


def test_serving_phase_reduced(smoke, capsys):
    smoke.serving(smoke=True, requests=4, replicas=2)
    out = capsys.readouterr().out
    assert "policy=locality served=4" in out
    assert "policy=single_queue served=4" in out


def test_four_chip_phase_on_one_device(smoke, capsys):
    smoke.four_chips((32, 8, 16), blocks_per_dev=4, n_dev=1)
    assert "scattered(blocks_per_dev=4)" in capsys.readouterr().out


def test_main_refuses_cpu(smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_helper_leaves_env_dir_alone(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_helper_fixed_checkout_dir(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = cache.use_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert cache.use_compile_cache() == path       # stable across calls
