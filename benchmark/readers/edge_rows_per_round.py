"""Rows the engine's edge gathers address by index per delivery round:
the output rows of every ``Net.edge_gather`` gather plus the rows of its
scatters, 0 for rolls (a banded graph). The program counts them while it
traces the window (``ops/edges.tally_index_rows``) and keeps the sum of
one step call on the window's entry in ``perf.stages``; the general
gather pays per row, not per byte, so this is what
``stage_us_edge_gather`` follows. ``None`` on a commit without the
counter, and where no one traced window ran in the trace."""

from benchmark.harness import stages


def read(run: dict):
    if not run.get("rounds_per_phase"):
        return None
    window = stages.the_window(run.get("device_trace"),
                               stages.traced_windows())
    rows = getattr(window, "edge_rows_per_dispatch", None)
    return None if rows is None else rows / run["rounds_per_phase"]
