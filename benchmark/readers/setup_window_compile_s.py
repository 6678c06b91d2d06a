"""Seconds jax spent on the window's own program: its trace, its lowering
and ``compile_or_get_cached`` (the compile on a cold start, the cache
load on a warm one), the ``compile.trace`` / ``compile.lower`` /
``compile.backend`` spans with the window's ``fun_name`` up to its first
compile (``harness/setup.py``). Nothing on a commit without the
recorder."""

from benchmark.harness import setup


def read(run: dict):
    return setup.read("setup_window_compile_s")
