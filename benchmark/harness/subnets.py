"""Sparse subscriptions and the graph that goes with them, for
configurations in which a peer subscribes a few of many topics (the
Ethereum attestation subnets: 2 of 64). Numpy arrays made from parameters
and a seed, as ``harness/graphs.py`` makes them, handed to the program
and to the reference alike.

  subscribed[N, T]  bool, who subscribes what
  my_topics[N, S]   the topics a peer subscribes, ascending, -1 pad
  slot_of[N, T]     where a topic sits in ``my_topics``, -1 if not there

``subnet_connect`` is what a client's discovery leaves behind: every peer
dials ``d_any`` random others (upstream's ``denseConnect``) and, in each
subnet it subscribes, ``d_subnet`` random co-subscribers (found through
the ENR's ``attnets`` in a real network; drawn here). The symmetric
closure is the graph; K is the largest degree of the draw.
"""

from __future__ import annotations

import numpy as np


def subscribe_random(n: int, n_topics: int, per_peer: int, seed: int) -> dict:
    """Every peer subscribes ``per_peer`` distinct topics, uniformly."""
    per_peer = min(int(per_peer), int(n_topics))
    rng = np.random.default_rng([int(seed), 0x5B5])
    picks = np.argsort(rng.random((n, n_topics)), axis=1)[:, :per_peer]
    my_topics = np.sort(picks, axis=1).astype(np.int32)
    rows = np.arange(n)[:, None]
    subscribed = np.zeros((n, n_topics), bool)
    subscribed[rows, my_topics] = True
    slot_of = np.full((n, n_topics), -1, np.int32)
    slot_of[rows, my_topics] = np.arange(per_peer, dtype=np.int32)[None, :]
    return {"subscribed": subscribed, "my_topics": my_topics,
            "slot_of": slot_of}


def draw_others(rng, m: int, d: int) -> np.ndarray:
    """``[m, min(d, m-1)]``: for each of ``m`` members, that many DISTINCT
    others, as positions 0..m-1. Rows with a repeat are drawn again."""
    d = min(int(d), m - 1)
    if d <= 0:
        return np.zeros((m, 0), np.int64)
    me = np.arange(m)[:, None]
    if d == m - 1:                                   # everybody else
        others = np.tile(np.arange(m - 1), (m, 1))
        return others + (others >= me)
    picks = rng.integers(0, m - 1, size=(m, d))
    while True:
        srt = np.sort(picks, axis=1)
        again = np.flatnonzero((srt[:, 1:] == srt[:, :-1]).any(axis=1))
        if again.size == 0:
            return picks + (picks >= me)
        picks[again] = rng.integers(0, m - 1, size=(again.size, d))


def from_dials(n: int, src: np.ndarray, dst: np.ndarray) -> dict:
    """Padded arrays of the symmetric closure of the dials ``src -> dst``
    (left-packed, neighbours ascending). A connection dialed from both
    ends is one edge, ``outbound`` at its lower-numbered end."""
    lo, hi = np.minimum(src, dst), np.maximum(src, dst)
    key, first = np.unique(lo.astype(np.int64) * n + hi, return_index=True)
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
    k = max(1, int(deg.max())) if a.size else 1
    nbr = np.full((n, k), -1, np.int32)
    rev = np.zeros((n, k), np.int32)
    outbound = np.zeros((n, k), bool)
    nbr[a, slot] = b
    outbound[a, slot] = out
    # edge i of the first half and edge i of the second are each other's
    # reverse: where the sort put the one, the other's slot is read
    where = np.empty(order.size, np.int64)
    where[order] = np.arange(order.size)
    rev[a, slot] = slot[where[(order + lo.size) % order.size]]
    return {"nbr": nbr, "rev": rev, "outbound": outbound}


def subnet_connect(subscribed: np.ndarray, d_any: int, d_subnet: int,
                   seed: int) -> dict:
    """``d_any`` random dials a peer, plus ``d_subnet`` dials to distinct
    random co-subscribers in every topic it subscribes (all of them where
    the topic has no more; a topic of one member dials nobody)."""
    n, n_topics = subscribed.shape
    rng = np.random.default_rng(int(seed))
    picks = draw_others(rng, n, d_any)
    src = [np.repeat(np.arange(n), picks.shape[1])]
    dst = [picks.reshape(-1)]
    for t in range(n_topics):
        members = np.flatnonzero(subscribed[:, t])
        picks = draw_others(rng, members.size, d_subnet)
        src.append(np.repeat(members, picks.shape[1]))
        dst.append(members[picks].reshape(-1))
    return from_dials(n, np.concatenate(src), np.concatenate(dst))


def build(config: dict, n: int) -> tuple:
    """``(graph, subscriptions)`` of a configuration with a
    ``subnet_connect`` graph group; both follow the graph's seed."""
    spec = config["graph"]
    if spec["kind"] != "subnet_connect":
        raise ValueError(f"unknown graph kind {spec['kind']!r}")
    subs = subscribe_random(n, int(config["n_topics"]),
                            int(config["topics_per_peer"]), int(spec["seed"]))
    g = subnet_connect(subs["subscribed"], int(spec["d_any"]),
                       int(spec["d_subnet"]), int(spec["seed"]))
    g["nbr_ok"] = g["nbr"] >= 0
    return g, subs
