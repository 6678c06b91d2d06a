"""Programs WRITTEN to the persistent cache up to and including the
window's: jax's ``/jax/compilation_cache/cache_misses`` events
(``harness/setup.py``). 0 on a warm start; on a cold one equal to
``setup_programs_compiled``, since the command keeps every program in
the cache (``jax_persistent_cache_min_compile_time_secs`` 0). Nothing on
a commit without the recorder."""

from benchmark.harness import setup


def read(run: dict):
    return setup.read("setup_cache_misses")
