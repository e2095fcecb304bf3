"""Where the program's entry points keep JAX's persistent compile cache."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro import compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_default_is_the_fixed_in_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable() == REPO / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
        assert compile_cache.enable() == REPO / ".jax_cache"   # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_used_and_written(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, nothing else is configured and
    compiled programs land in that directory."""
    code = (
        "import os, jax\n"
        "from repro import compile_cache\n"
        "d = compile_cache.enable()\n"
        "assert str(d) == os.environ['JAX_COMPILATION_CACHE_DIR'], d\n"
        "assert jax.config.jax_compilation_cache_dir == str(d)\n"
        "jax.jit(lambda x: x * 2.0 + 1.0)(3.0).block_until_ready()\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=str(REPO / "src"))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    assert any(tmp_path.iterdir()), "nothing was written to the cache dir"
