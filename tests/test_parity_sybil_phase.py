"""Phase engine (r = 8) against the heartbeat-cadence scalar oracle
(h = 8) under attack: 512 peers on ``random_connect(d=10)``, 20 % sybil
squatters (control plane only, never a byte of message data), and the
score, threshold and gater parameters of ``benchmark/configs/
sybil-50k.json`` (PARITY.md's "sybil squatters, phase r=8" row; the row
above it runs the per-round step at 192 peers with honest origins).

Both sides publish the same schedule, 4 a round from round 0 on, origins
uniform over ALL peers as the cell's traffic has them: a fifth of the
publishes are a squatter's own and go nowhere on either side. The CDF's
population is the (honest peer, honest-origin message) pairs of the
messages born in rounds 96-111, once the deficit has had its say (P3
activates 80 rounds after a graft, counted in rounds on both sides); both
sides must cover all of it, and on both the squatters must be out of the
honest peers' meshes by then.

The oracle models neither the validation queue nor the gater. The engine
runs both, as the cell does: at 32 a peer a round no honest queue of
these worlds fills once the meshes stand, so they change nothing an
honest peer sees.
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np

from go_libp2p_pubsub_tpu import graph
from go_libp2p_pubsub_tpu.config import (
    GossipSubParams,
    PeerGaterParams,
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

FILE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmark", "configs", "sybil-50k.json")
N, H, PUBS = 512, 8, 4
ROUNDS, BORN = 128, (96, 112)
MAX_H = 16
WORLDS = (3, 4)


def _world(seed):
    with open(FILE, encoding="utf-8") as f:
        c = json.load(f)
    sc, th, gt = c["score"], c["score_thresholds"], c["gater"]
    topo = graph.random_connect(N, d=c["graph"]["d"], seed=seed)
    subs = graph.subscribe_all(N, 1)
    rng = np.random.default_rng(seed * 7 + 1)
    sybil = np.zeros(N, bool)
    sybil[rng.choice(N, size=round(c["sybils"]["fraction"] * N),
                     replace=False)] = True
    # the file's keys are the program's field names, durations with "_s"
    tp = TopicScoreParams(**{k.removesuffix("_s"): v for k, v in sc.items()
                             if not k.startswith("behaviour_penalty")})
    sp = PeerScoreParams(
        topics={0: tp}, skip_app_specific=True,
        behaviour_penalty_weight=sc["behaviour_penalty_weight"],
        behaviour_penalty_threshold=sc["behaviour_penalty_threshold"],
        behaviour_penalty_decay=sc["behaviour_penalty_decay"])
    thr = PeerScoreThresholds(
        gossip_threshold=th["gossip"], publish_threshold=th["publish"],
        graylist_threshold=th["graylist"], accept_px_threshold=th["accept_px"],
        opportunistic_graft_threshold=th["opportunistic_graft"])
    gater = PeerGaterParams(threshold=gt["threshold"], quiet=gt["quiet_s"],
                            duplicate_weight=gt["duplicate_weight"],
                            ignore_weight=gt["ignore_weight"],
                            reject_weight=gt["reject_weight"])
    params = dataclasses.replace(GossipSubParams(), flood_publish=False)
    cfg = GossipSubConfig.build(
        params, thr, score_enabled=True, heartbeat_every=H,
        gater_params=gater, validation_capacity=c["validation_capacity"])
    cfg = dataclasses.replace(cfg, count_events=False, fanout_slots=0)
    assert cfg.gater_quiet_rounds == c["timers"]["gater_quiet"]["rounds"]
    po = rng.integers(0, N, (ROUNDS, PUBS)).astype(np.int32)
    return topo, subs, sp, cfg, gater, sybil, po, int(c["msg_slots"])


def _judged(birth, origin, sybil):
    """Slots of honest-origin messages born inside ``BORN``."""
    return ((birth >= BORN[0]) & (birth < BORN[1])
            & ~sybil[np.clip(origin, 0, None)])


def run_engine(seed):
    topo, subs, sp, cfg, gater, sybil, po, m = _world(seed)
    net = Net.build(topo, subs)
    st = GossipSubState.init(net, m, cfg, score_params=sp, seed=seed)
    phase = make_gossipsub_phase_step(cfg, net, H, score_params=sp,
                                      gater_params=gater,
                                      adversary_no_forward=sybil)
    pt = jnp.zeros((H, PUBS), jnp.int32)
    for p in range(ROUNDS // H):
        st = phase(st, jnp.asarray(po[p * H:(p + 1) * H]), pt,
                   jnp.ones((H, PUBS), bool), do_heartbeat=True)
    h = np.asarray(hops(st.core.msgs, st.core.dlv))
    slots = _judged(np.asarray(st.core.msgs.birth),
                    np.asarray(st.core.msgs.origin), sybil)
    got = h[~sybil][:, slots]
    mesh = np.asarray(st.mesh)[:, 0]
    adv_nbr = sybil[np.clip(topo.nbr, 0, None)] & topo.nbr_ok
    share = (mesh & adv_nbr)[~sybil].sum() / max(1, mesh[~sybil].sum())
    throttled = int((np.asarray(st.gater.last_throttle)[~sybil] >= 32).sum())
    return ([int(x) for x in got[got >= 0]], int(slots.sum() * (~sybil).sum()),
            float(share), throttled)


def run_oracle(seed):
    topo, subs, sp, cfg, _, sybil, po, m = _world(seed)
    o = OracleGossipSub(topo, subs, cfg, msg_slots=m, seed=seed + 100,
                        score_params=sp,
                        adversary=set(np.flatnonzero(sybil).tolist()))
    for i in range(ROUNDS):
        o.step([(int(p), 0, True) for p in po[i]])
    ok = {slot for slot, msg in o.msgs.items()
          if BORN[0] <= msg.birth < BORN[1] and not sybil[msg.origin]}
    got = [h for (i, slot), h in o.hops().items()
           if slot in ok and not sybil[i]]
    syb = tot = 0
    for i in np.flatnonzero(~sybil):
        for k, s, _ in o._edges(int(i)):
            if k in o.mesh[i].get(0, set()):
                tot += 1
                syb += s in o.adversary
    return got, len(ok) * int((~sybil).sum()), syb / max(1, tot)


def _cdf(hop_lists, denom):
    hist = np.zeros(MAX_H + 1)
    for hs in hop_lists:
        for h in hs:
            hist[min(h, MAX_H)] += 1
    return np.cumsum(hist) / denom


def test_phase_engine_vs_oracle_under_squatters():
    """Two worlds (graph, sybil draw, schedule), each run by both sides.
    Bound 4 %: the phase engine batches GRAFT / PRUNE / IWANT service to
    the phase head where the oracle handles them on arrival (PARITY.md,
    the phase-vs-oracle rows), and two worlds of 64 messages leave a
    noise band of about a point; measured 1.1 % (PARITY.md)."""
    ev = [run_engine(s) for s in WORLDS]
    eo = [run_oracle(s) for s in WORLDS]
    cv = _cdf([e[0] for e in ev], sum(e[1] for e in ev))
    co = _cdf([e[0] for e in eo], sum(e[1] for e in eo))
    sup = float(np.max(np.abs(cv - co)))
    print(f"PARITY[sybil-phase]: sup={100 * sup:.2f}% cov {cv[-1]:.4f}/"
          f"{co[-1]:.4f} share {[round(e[2], 3) for e in ev]}/"
          f"{[round(e[2], 3) for e in eo]}")
    assert sup <= 0.04
    # every honest peer gets every honest message, on both sides
    assert cv[-1] > 0.999 and co[-1] > 0.999
    # the deficit has expelled most squatters from the honest meshes on
    # both sides (they start at the draw's 20 %), and no honest queue
    # overflowed once the meshes stood
    assert all(e[2] < 0.14 for e in ev) and all(e[2] < 0.14 for e in eo)
    assert all(e[3] == 0 for e in ev)
