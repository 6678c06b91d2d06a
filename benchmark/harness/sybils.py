"""Who is a sybil: the one draw the builder, the reference and the tests
share. Numpy from parameters and a seed, as ``harness/graphs.py`` makes
its graphs; nothing of the program.

A configuration's ``sybils`` group gives ``fraction``, ``count`` (the
fraction of the file's ``n_peers``, exactly), ``seed`` and ``behaviour``.
The sybils are ordinary nodes of the graph, drawn WITHOUT replacement from
all peers, so every honest peer sees the fraction among its neighbours on
average. At another size (the tests' toy runs) the count follows the
fraction.
"""

from __future__ import annotations

import numpy as np

BEHAVIOURS = ("drop_forward",)


def draw(spec: dict, n: int, n_file: int | None = None) -> np.ndarray:
    """``[n]`` bool, exactly ``count`` True (``round(fraction * n)`` where
    ``n`` is not the file's size), a function of ``spec["seed"]`` alone."""
    if spec["behaviour"] not in BEHAVIOURS:
        raise ValueError(f"unknown sybil behaviour {spec['behaviour']!r}")
    count = (int(spec["count"]) if n_file is None or n == n_file
             else int(round(float(spec["fraction"]) * n)))
    if not 0 <= count < n:
        raise ValueError(f"{count} sybils among {n} peers")
    rng = np.random.default_rng([int(spec["seed"]), 0x5B11])
    mask = np.zeros(n, bool)
    mask[rng.choice(n, size=count, replace=False)] = True
    return mask


def honest_component(graph: dict, sybil: np.ndarray) -> np.ndarray:
    """``[n]`` bool: the honest peers reachable from the lowest-numbered
    honest peer over edges between honest peers (the graph the messages
    have to cross: a squatter forwards nothing)."""
    nbr, ok = graph["nbr"], graph["nbr_ok"]
    honest = ~sybil
    seen = np.zeros(honest.size, bool)
    if not honest.any():
        return seen
    frontier = np.zeros(honest.size, bool)
    frontier[np.flatnonzero(honest)[0]] = True
    while frontier.any():
        seen |= frontier
        rows = np.flatnonzero(frontier)
        reached = np.zeros(honest.size, bool)
        reached[nbr[rows][ok[rows]]] = True
        frontier = reached & honest & ~seen
    return seen
