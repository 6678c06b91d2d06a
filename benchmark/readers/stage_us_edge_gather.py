"""Device self time of the neighbour gathers (``gs.edge_gather``:
``Net.edge_gather`` and ``Net.peer_gather``; rolls on a banded graph), in
microseconds per delivery round, over the window's programs in the traced
window (``harness/stages.py``)."""

from benchmark.harness import stages


def read(run: dict):
    return stages.stage_us_per_round(run, "edge_gather")
