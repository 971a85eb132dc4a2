"""JAX's persistent compilation cache for the programs that run on the chip.

Call ``use_compile_cache()`` at the start of an entry point, before the
first compile; importing this module changes nothing.
"""

from __future__ import annotations

import os

#: The checkout's root (``src/repro/launch`` -> three levels up).
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn the cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and
    nothing else is set. Otherwise the cache is ``<checkout>/.jax_cache``,
    a fixed path, so that a later run finds what an earlier one stored."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
