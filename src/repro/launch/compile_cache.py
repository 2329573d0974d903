"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points call ``enable_compile_cache()`` first thing in ``main()`` —
never at import, so library users and tests keep JAX's default (no
persistent cache). The cache key includes the directory, so the default is
one fixed path inside the checkout: a path derived from a temp name, a pid
or the clock would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

#: ``<checkout>/.jax_cache`` (this file is ``src/repro/launch/...``).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, stays in force and nothing
    else is set (JAX reads it itself). Otherwise the cache goes to
    ``DEFAULT_DIR``. Returns None, setting nothing, when the cache is
    switched off (``JAX_ENABLE_COMPILATION_CACHE=false``).
    """
    import jax
    if not jax.config.jax_enable_compilation_cache:
        return None
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
