"""JAX's persistent compilation cache at one fixed place per checkout.

Entry points (``chip_smoke.py``, the examples, ``bench/run.py``) call
:func:`enable_compile_cache` first thing in ``main``; importing this module
changes nothing.  The next run of the same checkout must find what this one
compiled, so the directory never comes from a temporary name, a process id
or the clock.
"""

from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and this
    sets nothing else.  Otherwise the cache goes to ``<checkout>/.jax_cache``
    (listed in ``.gitignore``).
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
