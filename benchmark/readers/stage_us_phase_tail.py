"""Device self time of the phase tail (``gs.phase_tail``: deferred clears,
gater, the state's replace), in microseconds per delivery round, over the
window's programs in the traced window (``harness/stages.py``)."""

from benchmark.harness import stages


def read(run: dict):
    return stages.stage_us_per_round(run, "phase_tail")
