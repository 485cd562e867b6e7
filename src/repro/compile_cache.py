"""Where JAX keeps its persistent compilation cache.

Only the command-line entry points call :func:`use_compile_cache`
(``chip_smoke.py``, ``python -m repro.explore``, ``python -m repro.serve``
and ``benchmarks/run.py``); importing the package never changes JAX's
configuration.  The directory is fixed because the cache is keyed by it: a
path built from a temporary name, a PID or the time would never hit.
"""

from __future__ import annotations

import os
from pathlib import Path

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: ``.jax_cache/`` at the repository root (git-ignored)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` wins and is left alone (JAX reads
    it itself); otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
