"""Device self time of the heartbeat (``gs.heartbeat``: mesh maintenance and
gossip emission, its score refresh apart), in microseconds per delivery
round, over the window's programs in the traced window
(``harness/stages.py``)."""

from benchmark.harness import stages


def read(run: dict):
    return stages.stage_us_per_round(run, "heartbeat")
