"""JAX's persistent compilation cache for the repo's entry points.

``chip_smoke.py``, ``examples/quickstart.py`` and ``benchmarks/run.py`` call
``setup_compile_cache()`` before they compile anything. Tests do not: a
compile for a described (unattached) chip is written to the cache but
cannot be read back, and each later read would warn.
"""
from __future__ import annotations

import os
from pathlib import Path

# <repo>/.jax_cache: a fixed path, because a cache entry is only found again
# from the directory it was written to (the path is git-ignored)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is left
    as it is; otherwise the cache goes to ``DEFAULT_CACHE_DIR``."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
