"""compile_cache.enable: JAX_COMPILATION_CACHE_DIR wins, else the fixed
``<checkout>/.jax_cache``; never a temporary or per-process path."""
from pathlib import Path

import jax
import pytest

from repro import compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_env_dir_wins_and_config_is_left_alone(monkeypatch, tmp_path,
                                               cache_dir_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_the_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(CHECKOUT / ".jax_cache")
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert compile_cache.enable() == want          # same path every call


def test_importing_repro_sets_no_cache_dir():
    import subprocess
    import sys
    code = ("import jax, repro, repro.core.engine, repro.cluster; "
            "print(jax.config.jax_compilation_cache_dir)")
    env = {"PATH": "", "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(CHECKOUT / "src")}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "None"
