"""Where this checkout keeps JAX's persistent compilation cache.

The cache key includes the directory's path, so a directory that moves
never hits: the location is either the one the environment names
(``JAX_COMPILATION_CACHE_DIR``, which jax reads by itself) or ONE fixed,
git-ignored directory next to the package.  Nothing here derives a
path from ``tempfile``, a pid or the clock.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout location used when the environment names none
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def ensure_compile_cache() -> str:
    """Place the compile cache and return the directory in use.  Call
    before the first compile (``chip_smoke.py`` and ``bench.py`` do).
    A set ``ENV_VAR`` is left
    alone — jax already honours it; otherwise
    ``jax_compilation_cache_dir`` is pointed at
    :data:`CHECKOUT_CACHE_DIR`."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
