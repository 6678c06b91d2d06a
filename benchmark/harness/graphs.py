"""The peer graphs and subscriptions the benchmark hands to the program
and to the reference alike: numpy arrays made from parameters and a seed.

  nbr[N, K]     neighbour per slot, -1 where the slot is empty
  rev[N, K]     slot of the reverse edge: nbr[nbr[n, k], rev[n, k]] == n
  outbound[N,K] True where n dialed the connection
"""

from __future__ import annotations

import numpy as np


def ring_lattice(n: int, d: int) -> dict:
    """Every peer dials its next ``d`` ring neighbours: slot k holds ring
    offset +1..+d, then -1..-d (K = 2d, every slot full)."""
    if n <= 2 * d:
        raise ValueError(f"ring lattice needs n > 2d, got n={n} d={d}")
    offs = np.concatenate([np.arange(1, d + 1), -np.arange(1, d + 1)])
    nbr = ((np.arange(n)[:, None] + offs[None, :]) % n).astype(np.int32)
    rev = np.tile(np.concatenate([np.arange(d, 2 * d), np.arange(d)])
                  .astype(np.int32)[None, :], (n, 1))
    outbound = np.zeros((n, 2 * d), bool)
    outbound[:, :d] = True
    return {"nbr": nbr, "rev": rev, "outbound": outbound}


def random_connect(n: int, d: int, seed: int) -> dict:
    """Every peer dials ``d`` random others (upstream's denseConnect at
    d=10); the symmetric closure is the graph. K is the largest degree
    of the draw."""
    rng = np.random.default_rng(int(seed))
    picks = rng.integers(0, n - 1, size=(n, d))
    picks = picks + (picks >= np.arange(n)[:, None])       # never oneself
    src = np.repeat(np.arange(n), d)
    dst = picks.reshape(-1)
    # undirected edges, once each; the lower-numbered dialer of a mutual
    # pair keeps the "outbound" mark
    lo, hi = np.minimum(src, dst), np.maximum(src, dst)
    key, first = np.unique(lo * n + hi, return_index=True)
    lo, hi = key // n, key % n
    dial_lo = src[first] == lo
    a = np.concatenate([lo, hi])
    b = np.concatenate([hi, lo])
    out = np.concatenate([dial_lo, ~dial_lo])
    order = np.lexsort((b, a))
    a, b, out = a[order], b[order], out[order]
    deg = np.bincount(a, minlength=n)
    start = np.concatenate([[0], np.cumsum(deg)[:-1]])
    slot = np.arange(a.size) - start[a]
    k = int(deg.max())
    nbr = np.full((n, k), -1, np.int32)
    rev = np.zeros((n, k), np.int32)
    outbound = np.zeros((n, k), bool)
    nbr[a, slot] = b
    outbound[a, slot] = out
    # the reverse edge (b, a) sits where its key falls in the sorted list
    keys = a.astype(np.int64) * n + b
    back = np.searchsorted(keys, b.astype(np.int64) * n + a)
    rev[a, slot] = slot[back]
    return {"nbr": nbr, "rev": rev, "outbound": outbound}


def build_graph(spec: dict, n: int) -> dict:
    """The graph a configuration's ``graph`` group describes."""
    if spec["kind"] == "ring_lattice":
        g = ring_lattice(n, int(spec["d"]))
    elif spec["kind"] == "random_connect":
        g = random_connect(n, int(spec["d"]), int(spec["seed"]))
    else:
        raise ValueError(f"unknown graph kind {spec['kind']!r}")
    g["nbr_ok"] = g["nbr"] >= 0
    return g


def subscribe_all(n: int, n_topics: int) -> dict:
    """Every peer subscribes every topic; topic t sits in slot t."""
    return {
        "subscribed": np.ones((n, n_topics), bool),
        "my_topics": np.tile(np.arange(n_topics, dtype=np.int32), (n, 1)),
        "slot_of": np.tile(np.arange(n_topics, dtype=np.int32), (n, 1)),
    }
