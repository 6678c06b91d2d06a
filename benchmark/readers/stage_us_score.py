"""Device self time of scoring (``gs.score``: ``on_deliveries``,
``refresh_scores``, ``compute_scores``; 0 with scoring off), in microseconds
per delivery round, over the window's programs in the traced window
(``harness/stages.py``)."""

from benchmark.harness import stages


def read(run: dict):
    return stages.stage_us_per_round(run, "score")
