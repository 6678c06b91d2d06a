"""Device self time of what a ``dynamic_peers`` build pays at the phase
head beyond a static one (``gsx.churn``: ``apply_peer_transitions`` with
its dead-edge clears and the ONE crossing of the 2-bit liveness code
(bit 0 ``down_tr``, bit 1 the new ``up``) through the net's planned edge
gather, ``live_step_views``' traced arm, the publish gate on
``up[origin]``), in microseconds per delivery round, over the window's
programs in the traced window (``harness/parts.py``). Its ops are part of
``stage_us_control_head`` too, and the crossing of ``stage_us_edge_gather``
for as long as it is a gather of its own (until PR 38 it was two ``[N]``
-> ``[N,K]`` bool peer gathers). What the traced liveness mask costs where
the other stages read it in place of a constant is not in it. 0.0 in a
static cell, whose program traces none of it; nothing on a commit without
the scope."""

from benchmark.harness import parts


def read(run: dict):
    return parts.part_us_per_round(run, "churn")
