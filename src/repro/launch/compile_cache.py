"""Where entry points keep JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/serve_llm.py``,
``repro.launch.serve``, ``repro.launch.train``, ``benchmarks/run.py``)
call :func:`use_compile_cache` once before their first compile; the
library import and the tests never do.  The cache directory is part of
every entry's key, so it must not move between runs: a temp name, pid or
timestamp would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...);
# listed in .gitignore
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set here; otherwise the cache goes to the fixed
    in-checkout :data:`REPO_CACHE_DIR`."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
