"""Seconds in ``compile_or_get_cached`` for every OTHER program up to the
window's compile: the eager programs of ``Net.build``, the step's build
and the state init, compiled (cold) or loaded from the persistent cache
(warm); summed ``compile.backend`` spans (``harness/setup.py``). Nothing
on a commit without the recorder."""

from benchmark.harness import setup


def read(run: dict):
    return setup.read("setup_small_programs_s")
