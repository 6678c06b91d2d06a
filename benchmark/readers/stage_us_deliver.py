"""Device self time of the delivery commit (``gs.deliver``:
``finish_delivery``, ``merge_extra_tx``), in microseconds per delivery
round, over the window's programs in the traced window
(``harness/stages.py``)."""

from benchmark.harness import stages


def read(run: dict):
    return stages.stage_us_per_round(run, "deliver")
