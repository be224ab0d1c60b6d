"""Where JAX's persistent compilation cache lives.

Entry-point scripts call :func:`use_compile_cache` once at start-up; the
library never sets a cache on import.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax


def use_compile_cache(repo_root) -> str:
    """Keep compiled programs across runs and return the cache directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is used as it is (JAX reads
    it itself) and nothing else is set.  Otherwise the cache goes to the
    fixed path ``<repo_root>/.jax_cache``: the path is part of the cache
    key, so it is never built from a temporary name, a pid or the time.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(repo_root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
