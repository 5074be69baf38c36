"""Where JAX keeps its persistent compilation cache.

The entry points call ``setup_compile_cache`` at start-up, never on
import. A cache directory set from outside, through
``JAX_COMPILATION_CACHE_DIR``, is JAX's own setting and is left to it.
Otherwise the cache goes to one fixed directory in the checkout: a
name that changed from run to run (a temp dir, a pid, a time) would
never be found again by the next run.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def setup_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; return that
    directory."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
