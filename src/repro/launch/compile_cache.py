"""Where JAX keeps its persistent compilation cache.

Called from the ``main()`` of each launcher and from ``chip_smoke.py`` —
never at import time and never from tests.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (src/repro/launch/ -> three levels up)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this
    sets no other directory. Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` (a path is part of the cache key, so it is
    never built from a temporary name, a process id or the time), and a
    second run in the same checkout reuses what the first compiled.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every executable, not only the ones that took over a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
