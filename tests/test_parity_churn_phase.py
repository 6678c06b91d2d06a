"""Phase engine (r = 8, ``dynamic_peers=True``) against the heartbeat-
cadence scalar oracle (h = 8) under peer churn: 512 peers on
``random_connect(d=10)``, the mesh and score parameters and the churn
process of ``benchmark/configs/churn-100k.json`` (PARITY.md's "churn,
phase r=8" row). Both sides take the same publish schedule, 4 a round from
round 0 on, origins uniform over ALL peers (a down origin publishes
nothing on either side), and the same liveness rows from
``benchmark/harness/churn.py``, applied at phase heads on both.

The CDF's population is the (peer, message) pairs of the messages born in
rounds ``BORN`` by an origin that was up and stayed, and of the peers
whose last unbroken run of up rows reaches back to the birth: whatever
else a peer held, its crash took. Both sides must cover all of it.

Catch-up: a peer that came back 3 phases ago holds, of the messages born
before its return that some neighbour first saw inside the 3 heartbeats
before it, what gossip has brought it (IHAVE at the heartbeat, IWANT, the
answer). The phase engine answers an IWANT a phase later than the oracle
does (PARITY.md, the deviation list), so the shares are read 3 phases
after the return, when both have had a whole cycle, after every phase.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import churn  # noqa: E402
from go_libp2p_pubsub_tpu import graph  # noqa: E402
from go_libp2p_pubsub_tpu.config import (  # noqa: E402
    GossipSubParams,
    PeerScoreParams,
    PeerScoreThresholds,
    TopicScoreParams,
)
from go_libp2p_pubsub_tpu.models.gossipsub import (  # noqa: E402
    GossipSubConfig,
    GossipSubState,
)
from go_libp2p_pubsub_tpu.models.gossipsub_phase import (  # noqa: E402
    make_gossipsub_phase_step,
)
from go_libp2p_pubsub_tpu.oracle.gossipsub import OracleGossipSub  # noqa: E402
from go_libp2p_pubsub_tpu.state import Net  # noqa: E402

FILE = os.path.join(ROOT, "benchmark", "configs", "churn-100k.json")
N, H, PUBS = 512, 8, 4
ROUNDS, BORN = 160, (120, 136)
MAX_H = 24
WORLDS = (3, 4)
BACK = 3            # phases after a return at which catch-up is read


def _world(seed):
    with open(FILE, encoding="utf-8") as f:
        c = json.load(f)
    sc, mp = c["score"], c["mesh_params"]
    topo = graph.random_connect(N, d=c["graph"]["d"], seed=seed)
    subs = graph.subscribe_all(N, 1)
    tp = TopicScoreParams(
        topic_weight=sc["topic_weight"],
        time_in_mesh_weight=sc["time_in_mesh_weight"],
        time_in_mesh_quantum=sc["time_in_mesh_quantum_s"],
        time_in_mesh_cap=sc["time_in_mesh_cap"],
        first_message_deliveries_weight=sc["first_message_deliveries_weight"],
        first_message_deliveries_decay=sc["first_message_deliveries_decay"],
        first_message_deliveries_cap=sc["first_message_deliveries_cap"],
        mesh_message_deliveries_weight=0.0, mesh_failure_penalty_weight=0.0,
        invalid_message_deliveries_weight=0.0)
    sp = PeerScoreParams(
        topics={0: tp}, skip_app_specific=True,
        behaviour_penalty_weight=sc["behaviour_penalty_weight"],
        behaviour_penalty_threshold=sc["behaviour_penalty_threshold"],
        behaviour_penalty_decay=sc["behaviour_penalty_decay"])
    params = dataclasses.replace(
        GossipSubParams(), D=mp["D"], Dlo=mp["D_lo"], Dhi=mp["D_hi"],
        Dscore=mp["D_score"], Dout=mp["D_out"], Dlazy=mp["D_lazy"],
        gossip_factor=mp["gossip_factor"],
        history_length=mp["history_length"],
        history_gossip=mp["history_gossip"], flood_publish=False)
    cfg = GossipSubConfig.build(params, PeerScoreThresholds(),
                                score_enabled=True, heartbeat_every=H)
    cfg = dataclasses.replace(cfg, count_events=False, fanout_slots=0)
    rng = np.random.default_rng(seed * 7 + 1)
    po = rng.integers(0, N, (ROUNDS, PUBS)).astype(np.int32)
    hist = churn.liveness(seed, ROUNDS // H, N, c["churn"], H)
    return topo, subs, sp, cfg, po, hist, int(c["msg_slots"])


def _judge(fr, birth, origin, hist, nbr, nbr_ok, phase):
    """After ``phase`` phases: ``(hops, pairs)`` of the CDF's population
    (only once the run is over) and ``(held, told)`` of the catch-up of the
    peers that returned ``BACK`` phases ago. ``fr`` is ``[N, M]`` first
    rounds (-1: none), ``birth`` / ``origin`` ``[M]`` (-1: empty slot)."""
    h = hist[:phase]
    since = churn.up_since(h) * H
    t_end = phase * H
    alive = birth >= 0
    o = np.clip(origin, 0, None)
    up_origin = alive & h[np.clip(birth, 0, None) // H, o] & (since[o] <= birth)
    hops, pairs = [], 0
    if t_end == ROUNDS:
        for s in np.flatnonzero(up_origin & (birth >= BORN[0]) & (birth < BORN[1])):
            through = since <= birth[s]
            pairs += int(through.sum())
            got = fr[through, s]
            hops += [int(x) - int(birth[s]) for x in got[got >= 0]]
    held = told = 0
    pr = phase - BACK
    if pr >= 1:
        who = np.flatnonzero(h[pr:].all(axis=0) & ~h[pr - 1])
        msgs = np.flatnonzero(up_origin & (birth < pr * H))
        if who.size and msgs.size:
            q = np.clip(nbr[who], 0, None)
            seen = fr[q][:, :, msgs]
            first = (pr + 1 - 3) * H
            could = ((seen >= first) & (seen < pr * H) & nbr_ok[who][:, :, None]
                     & (since[q] <= first)[:, :, None]).any(axis=1)
            told = int(could.sum())
            held = int((could & (fr[who][:, msgs] >= 0)).sum())
    return hops, pairs, held, told


def run_engine(seed):
    topo, subs, sp, cfg, po, hist, m = _world(seed)
    net = Net.build(topo, subs)
    st = GossipSubState.init(net, m, cfg, score_params=sp, seed=seed)
    phase = make_gossipsub_phase_step(cfg, net, H, score_params=sp,
                                      dynamic_peers=True)
    pt = jnp.zeros((H, PUBS), jnp.int32)
    held = told = 0
    for p in range(ROUNDS // H):
        st = phase(st, jnp.asarray(po[p * H:(p + 1) * H]), pt,
                   jnp.ones((H, PUBS), bool), jnp.asarray(hist[p]),
                   do_heartbeat=True)
        hops, pairs, a, b = _judge(
            np.asarray(st.core.dlv.first_round), np.asarray(st.core.msgs.birth),
            np.asarray(st.core.msgs.origin), hist, topo.nbr, topo.nbr_ok, p + 1)
        held, told = held + a, told + b
    assert np.array_equal(np.asarray(st.up), hist[-1])
    return hops, pairs, held, told


def run_oracle(seed):
    topo, subs, sp, cfg, po, hist, m = _world(seed)
    o = OracleGossipSub(topo, subs, cfg, msg_slots=m, seed=seed + 100,
                        score_params=sp)
    held = told = 0
    hops, pairs = [], 0
    for i in range(ROUNDS):
        o.step([(int(p), 0, True) for p in po[i]], up=hist[i // H])
        if i % H == H - 1:
            fr = np.full((N, m), -1, np.int64)
            for (peer, slot), rnd in o.first_round.items():
                fr[peer, slot] = rnd
            birth = np.full(m, -1, np.int64)
            origin = np.full(m, -1, np.int64)
            for slot, msg in o.msgs.items():
                birth[slot], origin[slot] = msg.birth, msg.origin
            hops, pairs, a, b = _judge(fr, birth, origin, hist, topo.nbr,
                                       topo.nbr_ok, (i + 1) // H)
            held, told = held + a, told + b
    assert o.up == hist[-1].tolist()
    return hops, pairs, held, told


def _cdf(hop_lists, denom):
    hist = np.zeros(MAX_H + 1)
    for hs in hop_lists:
        for h in hs:
            hist[min(h, MAX_H)] += 1
    return np.cumsum(hist) / denom


def test_phase_engine_vs_oracle_under_churn():
    """Two worlds (graph, schedule, liveness rows), each run by both
    sides. Bound 4 %, the sybil row's: the phase engine batches GRAFT /
    PRUNE / IWANT service to the phase head where the oracle handles them
    on arrival, and two worlds leave a noise band of about a point."""
    ev = [run_engine(s) for s in WORLDS]
    eo = [run_oracle(s) for s in WORLDS]
    cv = _cdf([e[0] for e in ev], sum(e[1] for e in ev))
    co = _cdf([e[0] for e in eo], sum(e[1] for e in eo))
    sup = float(np.max(np.abs(cv - co)))
    share_v = sum(e[2] for e in ev) / max(1, sum(e[3] for e in ev))
    share_o = sum(e[2] for e in eo) / max(1, sum(e[3] for e in eo))
    print(f"PARITY[churn-phase]: sup={100 * sup:.2f}% cov {cv[-1]:.4f}/"
          f"{co[-1]:.4f} pairs {sum(e[1] for e in ev)}/{sum(e[1] for e in eo)} "
          f"catchup {share_v:.4f}/{share_o:.4f} of "
          f"{sum(e[3] for e in ev)}/{sum(e[3] for e in eo)}")
    assert sup <= 0.04
    # every peer that stayed up gets every message of an origin that did
    assert cv[-1] == 1.0 and co[-1] == 1.0
    assert sum(e[1] for e in ev) > 20000
    # the returning peers caught up alike
    assert sum(e[3] for e in ev) > 200 and sum(e[3] for e in eo) > 200
    assert abs(share_v - share_o) <= 0.05
