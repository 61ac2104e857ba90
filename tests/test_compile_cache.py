"""Placement of the persistent compilation cache by the entry points.

Each case runs in a child process, so this test process never turns the
cache on (library code and tests leave it off).
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

PROBE = """
import jax, jax.numpy as jnp
from repro.launch.cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
"""


def probe(env_extra: dict, compile_: bool) -> list:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0", **env_extra)
    proc = subprocess.run(
        [sys.executable, "-c", PROBE.format(compile=compile_)], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.split()


def test_env_dir_is_used_and_nothing_else(tmp_path):
    cache = tmp_path / "cache"
    default = ROOT / ".jax_cache"
    before = sorted(default.iterdir()) if default.is_dir() else None
    helper, config = probe({"JAX_COMPILATION_CACHE_DIR": str(cache)}, True)
    assert helper == config == str(cache)
    assert any(cache.iterdir()), "no cache entry was written"
    after = sorted(default.iterdir()) if default.is_dir() else None
    assert after == before


def test_default_is_fixed_dir_in_checkout():
    helper, config = probe({}, False)
    assert helper == config == str(ROOT / ".jax_cache")
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
