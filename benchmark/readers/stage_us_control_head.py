"""Device self time of the control head of the phase (``gs.control_head``:
peer transitions, accept gates, the control exchange, GRAFT/PRUNE, PX, IWANT
service, IHAVE ingest), in microseconds per delivery round, over the
window's programs in the traced window (``harness/stages.py``)."""

from benchmark.harness import stages


def read(run: dict):
    return stages.stage_us_per_round(run, "control_head")
