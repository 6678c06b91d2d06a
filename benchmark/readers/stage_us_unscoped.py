"""Device self time of the ops under no ``gs.*`` scope, which XLA itself
puts into the window (carry copies, layout conversions, the ``while``), in
microseconds per delivery round, over the window's programs in the traced
window (``harness/stages.py``)."""

from benchmark.harness import stages


def read(run: dict):
    return stages.stage_us_per_round(run, "unscoped")
