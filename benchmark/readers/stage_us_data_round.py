"""Device self time of the data sub-rounds' own work (``gs.data_round``:
transmit composition, trans gates, accumulator folds, mcache writes; the
gathers and the delivery commit are stages of their own), in microseconds
per delivery round, over the window's programs in the traced window
(``harness/stages.py``)."""

from benchmark.harness import stages


def read(run: dict):
    return stages.stage_us_per_round(run, "data_round")
