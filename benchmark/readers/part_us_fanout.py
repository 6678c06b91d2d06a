"""Device self time inside the fanout path (``gsx.fanout``: the fanout
slots' update on publish, the fanout carry of every sub-round, the
heartbeat's fanout maintenance and fanout gossip), in microseconds per
delivery round, over the window's programs in the traced window
(``harness/parts.py``). Its ops are part of ``stage_us_data_round`` and
``stage_us_heartbeat`` too."""

from benchmark.harness import parts


def read(run: dict):
    return parts.part_us_per_round(run, "fanout")
