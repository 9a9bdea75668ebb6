"""JAX process set-up shared by every entry point (service, batch
pipeline, bench, graft entry, tools).

Platform selection is JAX's own: ``JAX_PLATFORMS`` in the environment, or
an explicit ``platforms`` argument for tools that default to the CPU.
The persistent compilation cache is placeable from outside: where
``JAX_COMPILATION_CACHE_DIR`` is set, the cache goes there and nowhere
else; otherwise the cache lives at the fixed
``<checkout>/.jax_cache`` (a fixed path, because the path is part of the
cache key, and inside the checkout, because nothing outside it survives
on the chip machine).
"""

from __future__ import annotations

import os
from typing import Optional

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — listed in .gitignore."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  A restart replays the warm set from disk
    instead of recompiling it (serve --warmup / batch --warmup)."""
    import jax

    cache_dir = os.environ.get(CACHE_ENV) or default_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    return cache_dir


def ensure_platform(platforms: Optional[str] = None) -> str:
    """Entry-point set-up: the compilation cache, plus ``platforms``
    (comma-separated, e.g. "cpu") when a caller pins one explicitly —
    otherwise JAX's own ``JAX_PLATFORMS`` handling applies.  Returns the
    effective platform setting ("" = JAX's default discovery)."""
    import jax

    enable_compilation_cache()
    if platforms:
        jax.config.update("jax_platforms", platforms)
        return platforms
    return os.environ.get("JAX_PLATFORMS", "")
