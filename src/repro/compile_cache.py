"""Where JAX keeps its persistent compilation cache.

Entry points that compile for the chip (``chip_smoke.py``, the trace-query
service launcher) call :func:`use_compile_cache` before their first
compile, so a run starts from what an earlier run compiled.  Nothing else
in the package sets a cache directory.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_DIR", "use_compile_cache"]

# fixed and inside the checkout: the path is part of the cache key, so a
# directory that moved between runs would never hit (gitignored)
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the persistent cache directory JAX will use.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    changes nothing; otherwise the cache goes to :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
