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
    windows = stages.traced_windows()
    tr = run.get("device_trace")
    if not windows or not tr or not run.get("rounds_per_phase"):
        return None
    ran = {stages.module_base(m[0]) for dev in tr["devices"].values()
           for m in dev["modules"]}
    ours = [w for w in windows if w.module_name in ran]
    if len(ours) != 1:
        return None
    rows = getattr(ours[0], "edge_rows_per_dispatch", None)
    return None if rows is None else rows / run["rounds_per_phase"]
