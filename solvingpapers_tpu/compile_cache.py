"""Where this checkout keeps JAX's persistent compilation cache.

Entry points (`cli.main`, `chip_smoke.py`, `benchmarks/run.py`, the
`tools/` scripts) call `configure_compile_cache()` once, before their first
compilation. The directory is part of the cache key, so it must be the
same in every process: either the one `JAX_COMPILATION_CACHE_DIR`
names — JAX reads that variable itself, so nothing is set here — or
one fixed path inside the checkout.
"""

from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache (git-ignored): fixed, so a second process of the
# same checkout finds what the first one compiled
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def configure_compile_cache() -> str | None:
    """Point JAX's persistent compilation cache at `CACHE_DIR` unless
    `JAX_COMPILATION_CACHE_DIR` places it from outside. Returns the
    directory set in code, or None when the environment's is in force."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
