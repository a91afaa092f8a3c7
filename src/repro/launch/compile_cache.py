"""Where JAX keeps its persistent compilation cache.

Entry points (`chip_smoke.py`, `serve.py`, `benchmarks/run.py`) call
`enable_compile_cache()` once, before they compile anything.  Importing
`repro` never turns the cache on: a test that compiles for a described
(not attached) TPU would otherwise write entries no later process can
read back.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it as the
cache directory and nothing here overrides it.  Otherwise the cache lives
at one fixed path inside the checkout (``<repo>/.jax_cache``, git-ignored):
the directory is part of each entry's key, so a path that moved between
runs would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: src/repro/launch/compile_cache.py -> the checkout root.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the cache uses: the environment's, else the fixed
    in-checkout default."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
