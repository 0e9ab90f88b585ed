"""Where the persistent compilation cache goes."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.runtime.compile_cache import setup_compile_cache

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_left_to_jax(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_repo_jax_cache(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expect = str(REPO / ".jax_cache")
    assert setup_compile_cache() == expect
    assert jax.config.jax_compilation_cache_dir == expect
    # fixed path: a second call (another process, another day) agrees
    assert setup_compile_cache() == expect


def test_importing_sets_no_cache():
    """Only a call at start-up sets the cache, never an import: a fresh
    interpreter that imports the entry points still has no cache dir."""
    code = ("import chip_smoke, benchmarks.run, repro.launch.serve, jax; "
            "print(jax.config.jax_compilation_cache_dir)")
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=f"{REPO / 'src'}{os.pathsep}{REPO}", JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split() == ["None"]
