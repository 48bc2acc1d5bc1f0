"""JAX's persistent compilation cache, at one fixed place per checkout.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and this
module sets nothing.  Otherwise the cache goes to ``<repo>/.jax_cache``: a
fixed path, so a later run finds what an earlier one cached, and one that
``.gitignore`` lists.  Launchers call
:func:`enable_compile_cache` before their first compile; importing this
module changes nothing, so the tests never write a cache.
"""

from __future__ import annotations

import os
import pathlib

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
