"""Who is up when: the one draw of peer churn the builder, the reference
and the tests share. Numpy from parameters and a seed, as
``harness/sybils.py`` makes its mask; nothing of the program.

A configuration's ``churn`` group gives

  start_round      no peer leaves before this round (the meshes are built)
  leave_prob       each heartbeat every up peer leaves with this probability
  down_heartbeats  ``[lo, hi]``: a peer that left stays down a whole number
                   of heartbeats drawn uniformly from lo..hi, then returns to
                   the same addresses with fresh soft state

One row a PHASE (transitions land at phase heads, one a heartbeat): row
``p`` is who is up through rounds ``p * r .. p * r + r - 1``. The process
is event-based: a row costs a copy of the last one and the handful of
peers that change, well under a millisecond at 100,000 peers. Row ``p``
draws from a stream keyed by (seed, p) and from the rows before it alone,
so the history is the same however many rows are asked for at a time: the
builder draws a segment's rows inside the timed call, the reference draws
the whole history again once the window has closed.
"""

from __future__ import annotations

import numpy as np

STREAM = 0xC4A2


def check(spec: dict) -> None:
    lo, hi = (int(x) for x in spec["down_heartbeats"])
    if not 1 <= lo <= hi:
        raise ValueError(f"down_heartbeats = {spec['down_heartbeats']}")
    if not 0.0 <= float(spec["leave_prob"]) < 1.0:
        raise ValueError(f"leave_prob = {spec['leave_prob']}")
    if int(spec["start_round"]) < 0:
        raise ValueError(f"start_round = {spec['start_round']}")


class Process:
    """The churn process of one run, a row at a time."""

    def __init__(self, seed: int, n_peers: int, spec: dict,
                 rounds_per_phase: int):
        check(spec)
        self.seed = int(seed)
        self.n = int(n_peers)
        self.prob = float(spec["leave_prob"])
        self.lo, self.hi = (int(x) for x in spec["down_heartbeats"])
        # the first row in which somebody may be down
        self.first = -(-int(spec["start_round"]) // int(rounds_per_phase))
        self.phase = 0
        self.up = np.ones(self.n, bool)
        self.returns = {}            # row -> peers that come back in it
        self.left = self.returned = 0

    def next_row(self) -> np.ndarray:
        """Row number ``self.phase``, and step on. The array is the
        process's own: copy it to keep it."""
        p = self.phase
        self.phase += 1
        back = self.returns.pop(p, None)
        if p >= self.first and self.prob > 0.0:
            rng = np.random.default_rng([self.seed, STREAM, p])
            # who leaves is drawn among the peers up in the row before, so
            # nobody returns and leaves at one head
            idx = np.flatnonzero(self.up)
            k = int(rng.binomial(idx.size, self.prob))
            gone = rng.choice(idx, size=k, replace=False)
            stay = rng.integers(self.lo, self.hi + 1, size=k)
            self.up[gone] = False
            self.left += k
            for d in np.unique(stay):
                self.returns.setdefault(p + int(d), []).append(gone[stay == d])
        if back is not None:
            back = np.concatenate(back)
            self.up[back] = True
            self.returned += back.size
        return self.up

    def rows(self, phases: int) -> np.ndarray:
        """The next ``phases`` rows, ``[phases, N]`` bool."""
        out = np.empty((int(phases), self.n), bool)
        for i in range(int(phases)):
            out[i] = self.next_row()
        return out


def liveness(seed: int, phases: int, n_peers: int, spec: dict,
             rounds_per_phase: int = 8) -> np.ndarray:
    """``[phases, N]`` bool: the whole history from row 0."""
    return Process(seed, n_peers, spec, rounds_per_phase).rows(phases)


def up_since(history: np.ndarray) -> np.ndarray:
    """``[N]`` int: the first row of each peer's LAST unbroken run of up
    rows, the run that reaches the history's end; ``phases`` (past the
    end) for a peer that is down in the last row."""
    phases, n = history.shape
    down = ~history
    last_down = np.where(down.any(axis=0),
                         phases - 1 - np.argmax(down[::-1], axis=0), -1)
    return last_down + 1


def stats(history: np.ndarray) -> dict:
    """What the rows did: departures, returns and the share down at the
    end (the run's printed line carries them)."""
    step = history[1:].astype(np.int8) - history[:-1].astype(np.int8)
    return {"peers_left": int((step < 0).sum()),
            "peers_returned": int((step > 0).sum()),
            "down_share_end": float(1.0 - history[-1].mean())}
