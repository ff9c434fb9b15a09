"""Placement of JAX's persistent compilation cache (repro.launch.compile_cache)."""

import os
import subprocess
import sys

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture
def cache_dir_config():
    """Restore the cache directory, and drop the cache JAX opened on it, so
    that later tests in this process write no entries there."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_default_is_the_fixed_repo_directory(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    assert REPO_CACHE_DIR.parent.joinpath("chip_smoke.py").is_file()


def test_environment_placement_is_left_alone(monkeypatch, tmp_path,
                                             cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_placed_cache_receives_the_entries(tmp_path):
    """A program that calls enable_compile_cache() under a placed cache
    writes its compiled entries there."""
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "enable_compile_cache()\n"
            "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64)))"
            ".block_until_ready()\n")
    env = dict(os.environ,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(ROOT, "src")]
                   + ([os.environ["PYTHONPATH"]]
                      if os.environ.get("PYTHONPATH") else [])))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert any(tmp_path.iterdir())
