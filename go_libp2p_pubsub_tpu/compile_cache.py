"""Shared persistent-XLA-compile-cache policy: every entry point (tests,
gates, bench, sweep, profile, chip_smoke.py, the serve child) calls
:func:`enable_persistent_cache` before its first jit.

Where the cache lives is decided OUTSIDE the code when
``JAX_COMPILATION_CACHE_DIR`` is set (jax reads the variable itself; this
module then sets no directory). Otherwise it is the fixed
``<checkout>/.jax_cache`` — fixed because the path is part of the cache
key's environment: a directory that moves (temporary name, pid, time)
never hits. JAX_NO_TEST_CACHE=1 opts out everywhere (e.g. when bisecting
a suspected stale-cache issue).
"""

from __future__ import annotations

import os

#: the fixed fallback directory, git-ignored
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable_persistent_cache(cache_dir: str | None = None) -> bool:
    """Turn the persistent compile cache on unless the env opted out;
    returns whether it was enabled. ``cache_dir`` (default
    :data:`DEFAULT_CACHE_DIR`) is used only when
    ``JAX_COMPILATION_CACHE_DIR`` is unset."""
    if os.environ.get("JAX_NO_TEST_CACHE", "") == "1":
        return False
    import jax

    from .perf import spans

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          cache_dir or DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # every compile, cache hit and miss from here on is on record
    spans.watch_compiles()
    return True
