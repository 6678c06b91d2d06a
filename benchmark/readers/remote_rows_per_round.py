"""Rows one chip receives from the others in the per-shard plan's exchange
per delivery round: the program's count of what the edge gathers of one
step call received (``TracedWindow.remote_rows_per_dispatch``, each
exchange's rows padded as they cross) over the rounds a step call runs.
What partitioning the graph would cut. ``None`` on one chip and on a
commit without the counter."""

from benchmark.harness import halo


def read(run: dict):
    window = halo.sharded_window(run)
    if window is None or not run.get("rounds_per_phase"):
        return None
    rows = getattr(window, "remote_rows_per_dispatch", None)
    return None if rows is None else rows / run["rounds_per_phase"]
