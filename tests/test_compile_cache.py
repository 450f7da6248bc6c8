"""repro.launch.compile_cache: JAX_COMPILATION_CACHE_DIR wins when set,
otherwise the cache sits at one fixed directory of the checkout."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache

_PROBE = """
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.arange(8.0)).block_until_ready()
"""


def test_env_dir_is_left_to_jax_and_receives_the_cache(tmp_path):
    cache = tmp_path / "cache"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache),
               PYTHONPATH=str(Path(compile_cache.__file__).parents[2]))
    out = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                         capture_output=True, text=True, check=True)
    assert out.stdout.split() == [str(cache), str(cache)]
    assert any(cache.iterdir())


def test_unset_env_uses_the_checkout_dir(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "CHECKOUT_CACHE", tmp_path / "c")
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == str(tmp_path / "c")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "c")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_checkout_dir_is_fixed_and_git_ignored():
    root = Path(compile_cache.__file__).resolve().parents[3]
    assert compile_cache.CHECKOUT_CACHE == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
