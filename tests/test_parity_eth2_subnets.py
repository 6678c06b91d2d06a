"""Phase engine (r = 8) against the heartbeat-cadence scalar oracle
(h = 8) at the attestation-subnet shape at its real topic count: 64
topics, 2 a peer, ``graph.subnet_connect``, fanout on (PARITY.md's
"eth2 subnets: 64 topics" row; the 8-topic row beside it runs the
per-round step on ``random_connect``).

Both sides publish the same schedule: origins uniform over the peers,
topics uniform over the 64, so 31 publishes in 32 come from outside
their topic and enter through fanout, and some 4 in 10 have no
neighbour in the topic and stay at their origin (no discovery service:
gossipsub.go:983-998). The CDF's population is the (subscriber, message)
pairs of ROUTABLE publishes, worked out from the graph alone; both sides
must cover all of it.

Measured (CPU, 8 worlds, each run by both sides, 64 messages a world): see
PARITY.md.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from go_libp2p_pubsub_tpu import graph
from go_libp2p_pubsub_tpu.config import (
    GossipSubParams,
    PeerScoreParams,
    PeerScoreThresholds,
    TopicScoreParams,
)
from go_libp2p_pubsub_tpu.models.gossipsub import (
    GossipSubConfig,
    GossipSubState,
)
from go_libp2p_pubsub_tpu.models.gossipsub_phase import make_gossipsub_phase_step
from go_libp2p_pubsub_tpu.oracle.gossipsub import OracleGossipSub
from go_libp2p_pubsub_tpu.state import Net, hops

N, T, H, M = 512, 64, 8, 64
WARMUP, PUB_ROUNDS, DRAIN, PUBS = 24, 16, 16, 4        # 56 rounds, 64 msgs
MAX_H = 16
#: each world (graph, subscriptions, schedule) is run by both sides, the
#: engine's PRNG seeded with it and the oracle's with it + 100
WORLDS = (3, 4, 5, 6, 7, 8, 9, 10)


def _world(seed):
    subs = graph.subscribe_random(N, T, 2, seed=seed)
    topo = graph.subnet_connect(subs, d_any=10, d_subnet=5, seed=seed)
    tp = TopicScoreParams(mesh_message_deliveries_weight=0.0,
                          mesh_failure_penalty_weight=0.0)
    sp = PeerScoreParams(topics={t: tp for t in range(T)},
                         skip_app_specific=True,
                         behaviour_penalty_weight=-1.0,
                         behaviour_penalty_threshold=1.0,
                         behaviour_penalty_decay=0.9)
    params = dataclasses.replace(
        GossipSubParams(), D=8, Dlo=6, Dhi=12, history_length=6,
        heartbeat_interval=0.7)
    cfg = GossipSubConfig.build(params, PeerScoreThresholds(),
                                score_enabled=True, heartbeat_every=H)
    assert cfg.fanout_slots == 2
    rng = np.random.default_rng(seed * 7 + 1)
    total = WARMUP + PUB_ROUNDS + DRAIN
    po = np.full((total, PUBS), -1, np.int32)
    pt = np.zeros((total, PUBS), np.int32)
    po[WARMUP:WARMUP + PUB_ROUNDS] = rng.integers(0, N, (PUB_ROUNDS, PUBS))
    pt[WARMUP:WARMUP + PUB_ROUNDS] = rng.integers(0, T, (PUB_ROUNDS, PUBS))
    return topo, subs, sp, cfg, po, pt


def routable_pairs(topo, subs, po, pt):
    """(subscriber, message) pairs of the publishes that have somewhere to
    go, and the share of publishes that do."""
    sub = subs.subscribed
    near = np.zeros(sub.shape, bool)
    for k in range(topo.max_degree):
        ok = topo.nbr_ok[:, k]
        near[ok] |= sub[topo.nbr[ok, k]]
    live = po >= 0
    goes = (sub | near)[po[live], pt[live]]
    return int(sub[:, pt[live][goes]].sum()), float(goes.mean())


def _subscribed_hops(h, msg_topic, sub):
    mask = (h >= 0) & sub[:, np.clip(msg_topic, 0, None)]
    return [int(x) for x in h[mask]]


def run_engine(seed):
    topo, subs, sp, cfg, po, pt = _world(seed)
    net = Net.build(topo, subs)
    st = GossipSubState.init(net, M, cfg, score_params=sp, seed=seed)
    phase = make_gossipsub_phase_step(cfg, net, H, score_params=sp,
                                      heartbeat_interval=0.7)
    for p in range(po.shape[0] // H):
        sl = slice(p * H, (p + 1) * H)
        st = phase(st, jnp.asarray(po[sl]), jnp.asarray(pt[sl]),
                   jnp.ones((H, PUBS), bool), do_heartbeat=True)
    got = _subscribed_hops(np.asarray(hops(st.core.msgs, st.core.dlv)),
                           np.asarray(st.core.msgs.topic), subs.subscribed)
    return got, routable_pairs(topo, subs, po, pt)


def run_oracle(seed):
    topo, subs, sp, cfg, po, pt = _world(seed)
    o = OracleGossipSub(topo, subs, cfg, msg_slots=M, seed=seed + 100,
                        score_params=sp)
    for i in range(po.shape[0]):
        o.step([(int(p), int(t), True) for p, t in zip(po[i], pt[i]) if p >= 0])
    got = [h for (i, slot), h in o.hops().items()
           if subs.subscribed[i, o.msgs[slot].topic]]
    return got, routable_pairs(topo, subs, po, pt)


def _cdf(per_seed, denom):
    hist = np.zeros(MAX_H + 1)
    for hs in per_seed:
        for h in hs:
            hist[min(h, MAX_H)] += 1
    return np.cumsum(hist) / denom


def measure(worlds=WORLDS):
    ev = [run_engine(s) for s in worlds]
    eo = [run_oracle(s) for s in worlds]
    cv = _cdf([h for h, _ in ev], sum(p for _, (p, _) in ev))
    co = _cdf([h for h, _ in eo], sum(p for _, (p, _) in eo))
    share = float(np.mean([s for _, (_, s) in ev + eo]))
    return float(np.max(np.abs(cv - co))), cv[-1], co[-1], share


@pytest.mark.slow
def test_phase_engine_vs_oracle_at_64_topics_within_2pct():
    sup, cov_v, cov_o, share = measure()
    print(f"PARITY[eth2-64]: sup={100 * sup:.2f}% cov {cov_v:.4f}/{cov_o:.4f} "
          f"routable {share:.3f}")
    assert sup <= 0.02
    assert cov_v > 0.995 and cov_o > 0.995


def test_one_seed_a_side_covers_every_routable_pair():
    """The quick tier's share of the row: one seed a side, every
    (subscriber, routable message) pair delivered on both, the two CDFs
    within a one-seed noise band."""
    sup, cov_v, cov_o, share = measure(WORLDS[:1])
    assert cov_v > 0.995 and cov_o > 0.995
    assert 0.4 < share < 0.8
    assert sup <= 0.06
