"""JAX's persistent compilation cache for the entry points.

Each entry point calls ``enable_compile_cache()`` from its ``main()``;
importing this module changes nothing, and tests never turn the cache on.
"""

from __future__ import annotations

import os

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads it
    itself). Otherwise the cache is ``<checkout>/.jax_cache``: a fixed path,
    because the path is part of each entry's key.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
