"""Host seconds inside ``make_gossipsub_phase_step``
(``models/gossipsub_phase.py``), summed over the ``setup.step_build``
spans that ended before the window was compiled (``harness/setup.py``):
the constants the step closes over and the eager programs that make them,
compiled or loaded; the largest part of a cold start. Nothing on a commit
without the recorder."""

from benchmark.harness import setup


def read(run: dict):
    return setup.read("setup_step_build_s")
