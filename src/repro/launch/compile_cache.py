"""Where JAX keeps its persistent compilation cache.

Every entry point (`launch/serve.py`, `launch/train.py`, the supervised
engine worker, `chip_smoke.py`) calls `init_compile_cache()` before its
first compile, so a second process with the same programs reads what the
first one wrote instead of compiling again.

The cache's location is part of what keys it, so it must not move between
processes: a directory set from outside (`JAX_COMPILATION_CACHE_DIR`, which
JAX reads itself) stands as it is, and otherwise the cache lives at the
fixed `<checkout>/.jax_cache/` (git-ignored).
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def init_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
