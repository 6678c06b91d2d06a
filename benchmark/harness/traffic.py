"""The one general generator of publish traffic.

A traffic mix is a data file of parameters (``benchmark/traffic/*.json``):

  driver           which file of ``benchmark/drivers/`` runs it
  pubs_per_round   publishes injected in every delivery round, each from a
                   peer and on a topic drawn uniformly, every one valid
  segment_phases   phases the host hands to the window in one dispatch
  unroll_phases    phases per iteration of the compiled scan
  ahead_segments   segments the client has sent beyond the one whose summary
                   it waits for (0: a closed loop), so that the chip is fed
                   while the host stalls: four seconds of device work, or
                   as many as the runtime takes without blocking the call

Every seed draws the same amount of work: ``[rounds, pubs_per_round]``
planes for each segment, from a stream keyed by (seed, segment index), so
a segment's schedule can be drawn again once the window has closed.
"""

from __future__ import annotations

import numpy as np


def check_mix(mix: dict) -> None:
    for key in ("pubs_per_round", "segment_phases", "unroll_phases"):
        if int(mix[key]) < 1:
            raise ValueError(f"{key} = {mix[key]}")
    if int(mix["ahead_segments"]) < 0:
        raise ValueError(f"ahead_segments = {mix['ahead_segments']}")


def segment_schedule(mix: dict, seed: int, segment: int, rounds: int,
                     n_peers: int, n_topics: int):
    """``(origin, topic, valid)`` numpy ``[rounds, pubs_per_round]`` planes
    of segment number ``segment`` (the warm-up segment is number 0)."""
    rng = np.random.default_rng([int(seed), int(segment)])
    shape = (rounds, int(mix["pubs_per_round"]))
    origin = rng.integers(0, n_peers, size=shape, dtype=np.int32)
    topic = rng.integers(0, n_topics, size=shape, dtype=np.int32)
    return origin, topic, np.ones(shape, bool)
