"""JAX persistent compilation-cache set-up.

The program runs on whatever devices JAX gives it and fails when JAX
fails: there is no platform override, no probe and no fallback here.

Where the cache lives is decided from outside: if
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no directory in code. Otherwise the cache sits at the fixed
path ``<checkout>/.jax_cache`` (the path is part of JAX's cache key, so
it is never temporary, pid- or time-derived).
"""

from __future__ import annotations

import os

_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")

_cache_enabled = False


def enable_compilation_cache() -> None:
    """Idempotent; called by every entry point before its first compile."""
    global _cache_enabled
    if _cache_enabled:
        return
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _cache_enabled = True


def compilation_cache_dir() -> str:
    """The directory JAX's persistent cache uses in this process."""
    import jax

    return jax.config.jax_compilation_cache_dir
