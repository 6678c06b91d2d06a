"""Device self time inside the peer gater (``gsx.gater``: the ``dup`` /
``rejw`` / ``ignw`` planes' composition and folds of every sub-round, the
phase tail's outcome counts, the accept draw of the control head and the
heartbeat's counter decay), in microseconds per delivery round, over the
window's programs in the traced window (``harness/parts.py``). Its ops are
part of ``stage_us_data_round``, ``stage_us_phase_tail``,
``stage_us_control_head`` and ``stage_us_heartbeat`` too. 0.0 in a cell
built without a gater; nothing on a commit without the scope."""

from benchmark.harness import parts


def read(run: dict):
    return parts.part_us_per_round(run, "gater")
