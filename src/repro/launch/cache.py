"""JAX's persistent compilation cache for the entry points.

Called from ``main()`` of each entry point (``launch/train.py``,
``launch/serve.py``, ``chip_smoke.py``) — never on import and never from
tests. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and no other directory is set here. Otherwise the cache lives at a fixed
path in the checkout, ``<repo>/.jax_cache``: the path is part of the
cache key, so a directory built from a temporary name would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def use_compile_cache(default_dir=DEFAULT_DIR) -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(default_dir))
    return str(default_dir)
