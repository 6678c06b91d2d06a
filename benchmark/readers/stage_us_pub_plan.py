"""Device self time of publish allocation (``gs.pub_plan``:
``state.PhasePubPlan``, ``allocate_publishes``), in microseconds per
delivery round, over the window's programs in the traced window
(``harness/stages.py``)."""

from benchmark.harness import stages


def read(run: dict):
    return stages.stage_us_per_round(run, "pub_plan")
