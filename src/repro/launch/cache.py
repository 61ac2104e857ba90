"""Placement of JAX's persistent compilation cache, for entry points only.

Compiled kernels are cached across processes so a second run of the same
shapes skips compilation.  Where the cache lives is decided from outside:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing here
  overrides it.
* not set: ``<checkout>/.jax_cache`` -- a fixed path (no temporary name,
  process id or time in it), so every run of this checkout finds the
  entries the last one wrote.  Git ignores it.

Library modules and tests never call this: a compile made for a described
(unattached) chip is written to the cache but cannot be read back without
one, and would warn on every later read.
"""

from __future__ import annotations

import os
from pathlib import Path

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
