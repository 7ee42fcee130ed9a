"""Where the entry points keep JAX's persistent compilation cache."""

import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch.compile_cache import (CHECKOUT_CACHE, ENV_VAR,
                                        use_compile_cache)

ROOT = Path(__file__).resolve().parents[1]


def test_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_ignored_checkout_directory(monkeypatch):
    monkeypatch.delenv(ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == str(CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert CHECKOUT_CACHE == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def _child(script: str, tmp_path, **env_vars) -> None:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.pop(ENV_VAR, None)
    env.update(env_vars)
    subprocess.run([sys.executable, "-c", script], env=env, check=True,
                   cwd=tmp_path, timeout=120)


def test_import_repro_configures_no_cache(tmp_path):
    _child("import jax, repro, repro.launch.compile_cache\n"
           "assert not jax.config.jax_compilation_cache_dir\n", tmp_path)


def test_entries_land_in_the_env_directory(tmp_path):
    """A launcher-style process with the variable set writes its compiled
    programs there."""
    cache = tmp_path / "cache"
    _child("import jax, jax.numpy as jnp\n"
           "from repro.launch.compile_cache import use_compile_cache\n"
           "use_compile_cache()\n"
           "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
           "jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)\n"
           "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()\n",
           tmp_path, **{ENV_VAR: str(cache)})
    assert any(cache.iterdir())
