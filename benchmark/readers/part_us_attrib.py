"""Device self time of what a v1.1 build pays to ATTRIBUTE deliveries
beyond P1 / P2 / P7 (``gsx.attrib``: the ``[N,K,W]`` ``trans`` and ``mcw``
planes' folds of every sub-round, the P3 window gate, the validation
queue's throttle, the static adversary's data-plane masks and the P3 /
P3b / P4 terms of the score engine), in microseconds per delivery round,
over the window's programs in the traced window (``harness/parts.py``).
Its ops are part of ``stage_us_data_round``, ``stage_us_control_head``
and ``stage_us_score`` too. 0.0 in an honest cell, whose program traces
none of it; nothing on a commit without the scope."""

from benchmark.harness import parts


def read(run: dict):
    return parts.part_us_per_round(run, "attrib")
