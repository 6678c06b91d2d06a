"""Programs that went through ``compile_or_get_cached`` up to and including
the window's: the ``compile.backend`` spans (``harness/setup.py``);
compiled or loaded, the count is the same on a cold and a warm start.
Nothing on a commit without the recorder."""

from benchmark.harness import setup


def read(run: dict):
    return setup.read("setup_programs_compiled")
