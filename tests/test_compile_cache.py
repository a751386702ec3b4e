"""repro.launch.compile_cache: the persistent compile cache's directory."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_env_dir_is_used_and_written(tmp_path):
    script = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "print(enable_compile_cache())\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir())


def test_default_dir_is_the_repo_cache(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert REPO_CACHE_DIR == ROOT / ".jax_cache"
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
