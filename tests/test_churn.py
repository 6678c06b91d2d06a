"""Peer lifecycle: churn (dead/returning peers) + blacklist.

Reference behavior being modeled: notify.go:19-75 (connection events),
handleDeadPeers pubsub.go:648-689 (writer death => remove peer + router
RemovePeer), gossipsub.go:545-562 (RemovePeer drops mesh/fanout/gossip
state), score.go:604-637 (score retention across disconnect: negative
scores survive, non-negative stats are deleted), blacklist.go:12-64 +
pubsub.go:1048-1060,636-639 (blacklisted peers disconnected and ignored).
"""

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
    make_gossipsub_step,
    no_publish as nopub,
    set_blacklist,
)
from go_libp2p_pubsub_tpu.models.gossipsub_phase import make_gossipsub_phase_step
from go_libp2p_pubsub_tpu.ops import bitset
from go_libp2p_pubsub_tpu.state import Net
from go_libp2p_pubsub_tpu.trace.events import EV


def benign_score_params(n_topics=1):
    tp = TopicScoreParams(
        topic_weight=1.0,
        time_in_mesh_weight=0.0,
        first_message_deliveries_weight=1.0,
        first_message_deliveries_cap=50.0,
        first_message_deliveries_decay=0.9,
        mesh_message_deliveries_weight=0.0,
        mesh_failure_penalty_weight=0.0,
        invalid_message_deliveries_weight=-10.0,
        invalid_message_deliveries_decay=0.95,
    )
    return PeerScoreParams(
        topics={t: tp for t in range(n_topics)},
        skip_app_specific=True,
        behaviour_penalty_weight=-10.0,
        behaviour_penalty_threshold=0.0,
        behaviour_penalty_decay=0.9,
        ip_colocation_factor_weight=0.0,
    )


#: the lifecycle runs through both engines: the per-round step, and the
#: phase engine at r = 8 (transitions land once a phase, control acts once
#: a phase, IWANT answers ride one round). For the phase engine one "step"
#: of these tests is one PHASE: the publish goes into its first round and a
#: heartbeat closes it, so a count of steps is a count of heartbeats in both
ENGINES = ("round", "phase8")
PHASE_R = 8


def phase_as_step(pstep, r=PHASE_R):
    """``step(st, po, pt, pv, up)`` over a phase step: the ``[P]`` publish
    batch in the phase's first round, padding in the others."""
    def step(st, po, pt, pv, up):
        rows = lambda a, fill: jnp.concatenate(
            [a[None], jnp.full((r - 1,) + a.shape, fill, a.dtype)])
        return pstep(st, rows(po, -1), rows(pt, -1), rows(pv, False), up,
                     do_heartbeat=True)
    return step


def build(n=30, d=6, seed=0, score=False, msg_slots=32, engine="round"):
    topo = graph.random_connect(n, d, seed=seed)
    subs = graph.subscribe_all(n, 1)
    net = Net.build(topo, subs)
    params = dataclasses.replace(GossipSubParams(), flood_publish=False)
    sp = benign_score_params() if score else None
    thr = PeerScoreThresholds(
        gossip_threshold=-2.0,
        publish_threshold=-4.0,
        graylist_threshold=-8.0,
        accept_px_threshold=10.0,
        opportunistic_graft_threshold=1.0,
    )
    cfg = GossipSubConfig.build(
        params, thr, score_enabled=score,
        heartbeat_every=PHASE_R if engine == "phase8" else 1)
    st = GossipSubState.init(net, msg_slots, cfg, score_params=sp, seed=seed)
    if engine == "phase8":
        step = phase_as_step(make_gossipsub_phase_step(
            cfg, net, PHASE_R, score_params=sp, dynamic_peers=True))
    else:
        step = make_gossipsub_step(cfg, net, score_params=sp,
                                   dynamic_peers=True)
    return topo, net, cfg, st, step


def pub(o, t=0, valid=True, p=4):
    po = np.full(p, -1, np.int32)
    pt = np.full(p, -1, np.int32)
    pv = np.zeros(p, bool)
    po[0], pt[0], pv[0] = o, t, valid
    return jnp.asarray(po), jnp.asarray(pt), jnp.asarray(pv)


def run(step, st, up, k, publishes=()):
    pubs = dict(publishes)
    for i in range(k):
        po, pt, pv = pubs.get(i, nopub())
        st = step(st, po, pt, pv, up)
    return st


def received(st, peer):
    """Set of message slots `peer` has seen."""
    have = np.asarray(bitset.unpack(st.core.dlv.have, st.core.msgs.capacity))
    return set(np.nonzero(have[peer])[0])


def test_down_peer_stops_receiving_and_events_counted():
    topo, net, cfg, st, step = build()
    n = net.n_peers
    up = jnp.ones((n,), bool)

    # warm up the mesh, then take peer 0 down
    st = run(step, st, up, 5)
    down = up.at[0].set(False)
    ev_before = np.asarray(st.core.events)
    st = step(st, *nopub(), down)
    ev_after = np.asarray(st.core.events)
    assert ev_after[EV.REMOVE_PEER] - ev_before[EV.REMOVE_PEER] == 1

    # a message published elsewhere while 0 is down must not reach 0
    st = run(step, st, down, 8, publishes={0: pub(n - 1)})
    assert received(st, 0) == set()
    # but reaches everyone else
    for p in range(1, n):
        assert received(st, p) >= {0} or p == n - 1  # origin counts too

    # no live mesh edges point at peer 0
    mesh = np.asarray(st.mesh)
    nbr = np.asarray(net.nbr)
    for j in range(1, n):
        for k in range(net.max_degree):
            if nbr[j, k] == 0:
                assert not mesh[j, :, k].any()


@pytest.mark.parametrize("engine", ENGINES)
def test_mesh_heals_after_peer_death(engine):
    topo, net, cfg, st, step = build(n=40, d=8, engine=engine)
    n = net.n_peers
    up = jnp.ones((n,), bool)
    st = run(step, st, up, 5)
    down = np.ones(n, bool)
    down[:4] = False  # kill 4 peers at once
    down = jnp.asarray(down)
    st = run(step, st, down, 20)
    mesh = np.asarray(st.mesh)
    deg = mesh.sum(axis=(1, 2))
    # survivors regraft back into a healthy mesh
    alive_deg = deg[4:]
    assert (alive_deg >= cfg.Dlo).mean() > 0.9
    # the dead peers' own mesh state was cleared
    assert deg[:4].sum() == 0


@pytest.mark.parametrize("engine", ENGINES)
def test_returning_peer_rejoins_and_receives(engine):
    topo, net, cfg, st, step = build(engine=engine)
    n = net.n_peers
    up = jnp.ones((n,), bool)
    st = run(step, st, up, 5)
    down = up.at[0].set(False)
    st = run(step, st, down, 5)
    ev_before = np.asarray(st.core.events)
    st = step(st, *nopub(), up)  # peer 0 returns
    assert np.asarray(st.core.events)[EV.ADD_PEER] - ev_before[EV.ADD_PEER] == 1
    st = run(step, st, up, 10, publishes={2: pub(n - 1)})
    assert len(received(st, 0)) > 0
    # and it regrafted into someone's mesh
    mesh = np.asarray(st.mesh)
    deg0 = mesh[0].sum()
    assert deg0 > 0


def test_blacklisted_peer_fully_isolated():
    topo, net, cfg, st, step = build()
    n = net.n_peers
    up = jnp.ones((n,), bool)
    st = run(step, st, up, 5)
    bl = np.zeros(n, bool)
    bl[3] = True
    st = set_blacklist(st, bl)
    st = run(step, st, up, 10, publishes={1: pub(0), 3: pub(3)})
    # messages published by the blacklisted peer reach nobody
    got3 = [p for p in range(n) if p != 3 and 1 in received(st, p)]
    # slot 1 = second publish (peer 3's); slot 0 = peer 0's publish
    assert got3 == []
    # the network still works without it
    reached = sum(1 for p in range(n) if p != 3 and 0 in received(st, p))
    assert reached > n - 5
    # the blacklisted peer sees only its own local publish, nothing from
    # the network
    assert received(st, 3) <= {1}


def test_score_retention_negative_survives_reconnect():
    topo, net, cfg, st, step = build(score=True)
    n = net.n_peers
    up = jnp.ones((n,), bool)
    st = run(step, st, up, 5)

    # peer 7 spams invalid messages -> its neighbors score it negative (P4)
    for i in range(6):
        st = step(st, *pub(7, valid=False), up)
    nbr = np.asarray(net.nbr)
    scores = np.asarray(st.scores)
    viewers = [(j, k) for j in range(n) for k in range(net.max_degree) if nbr[j, k] == 7]
    neg_before = [scores[j, k] for j, k in viewers if scores[j, k] < 0]
    assert len(neg_before) > 0

    # bounce peer 7: negative opinions survive (retention)
    down = up.at[7].set(False)
    st = step(st, *nopub(), down)
    st = step(st, *nopub(), up)
    st = run(step, st, up, 2)
    scores_after = np.asarray(st.scores)
    still_neg = [scores_after[j, k] for j, k in viewers if scores_after[j, k] < 0]
    assert len(still_neg) >= len(neg_before) * 0.8  # decay may clear a few


def test_positive_stats_cleared_on_disconnect():
    topo, net, cfg, st, step = build(score=True)
    n = net.n_peers
    up = jnp.ones((n,), bool)
    st = run(step, st, up, 3)
    # peer 5 earns positive score via first deliveries
    for i in range(5):
        st = step(st, *pub(5, valid=True), up)
    st = run(step, st, up, 3)
    nbr = np.asarray(net.nbr)
    scores = np.asarray(st.scores)
    viewers = [(j, k) for j in range(n) for k in range(net.max_degree) if nbr[j, k] == 5]
    assert max(scores[j, k] for j, k in viewers) > 0

    down = up.at[5].set(False)
    st = step(st, *nopub(), down)
    # positive stats deleted immediately: fmd for those edges is zero
    fmd = np.asarray(st.score.fmd)
    for j, k in viewers:
        assert fmd[j, :, k].sum() == 0


def test_retained_deficit_converts_to_decaying_penalty():
    """removePeer (score.go:604-637): when a mesh peer with a negative
    (retained) score disconnects, its standing P3 deficit must convert to
    the decaying P3b penalty once and the activation latch must drop —
    not stay latched as a permanent deficit. With heartbeat_every=1 the
    heartbeat prunes negative-score mesh edges with the same memoized
    score snapshot the disconnect sees, so the window only opens in
    multi-round-heartbeat configs; this exercises the engine path the
    model's down-transition composes (on_prune + clear_mesh_status +
    clear_edges with a retention mask)."""
    from go_libp2p_pubsub_tpu.score.engine import (
        ScoreState,
        TopicParamsArrays,
        clear_edges,
        clear_mesh_status,
        compute_scores,
        on_prune,
        refresh_scores,
    )

    tp_params = TopicScoreParams(
        topic_weight=1.0,
        time_in_mesh_weight=0.0,
        first_message_deliveries_weight=0.0,
        mesh_message_deliveries_weight=-1.0,
        mesh_message_deliveries_decay=0.9,
        mesh_message_deliveries_cap=100.0,
        mesh_message_deliveries_threshold=10.0,
        mesh_message_deliveries_activation=1.0,
        mesh_failure_penalty_weight=-1.0,
        mesh_failure_penalty_decay=0.5,
        invalid_message_deliveries_weight=-10.0,
        invalid_message_deliveries_decay=0.95,
    )
    sp = PeerScoreParams(
        topics={0: tp_params},
        skip_app_specific=True,
        behaviour_penalty_weight=-10.0,
        behaviour_penalty_threshold=0.0,
        behaviour_penalty_decay=0.9,
        ip_colocation_factor_weight=0.0,
    )
    topo = graph.ring_lattice(6, d=2)
    net = Net.build(topo, graph.subscribe_all(6, 1))
    n, k, s = net.n_peers, net.max_degree, net.n_slots
    tpa = TopicParamsArrays.build(sp, 1, 1.0)
    tp = tpa.gather(net.my_topics)

    st = ScoreState.empty(n, s, k)
    # viewer 0 has neighbor slot 0 in mesh, activation latched, zero mmd
    # counter -> full deficit
    in_mesh = jnp.zeros((n, s, k), bool).at[0, 0, 0].set(True)
    st = st.replace(mmd_active=jnp.zeros((n, s, k), bool).at[0, 0, 0].set(True))

    # down-transition composition from make_gossipsub_step for a dead
    # neighbor with a retained (negative) score
    down_nbr = jnp.zeros((n, k), bool).at[0, 0].set(True)
    retained = jnp.zeros((n, k), bool)  # negative score -> NOT cleared
    st2 = on_prune(st, in_mesh & down_nbr[:, None, :], tp)
    st2 = clear_mesh_status(st2, down_nbr)
    st2 = clear_edges(st2, retained)

    thr = float(np.asarray(tp["thr3"])[0, 0])
    assert not bool(np.asarray(st2.mmd_active)[0, 0, 0])
    assert np.asarray(st2.mfp)[0, 0, 0] == pytest.approx(thr * thr)

    # scores after: P3 no longer applies (latch cleared), P3b does, and
    # decays away over refreshes
    no_mesh = jnp.zeros((n, s, k), bool)
    sc = np.asarray(compute_scores(st2, no_mesh, tp, sp, jnp.zeros((n, k)),
                                   jnp.zeros((n,)), net))
    assert sc[0, 0] == pytest.approx(-thr * thr)
    for t in range(20):
        st2 = refresh_scores(st2, no_mesh, t, tp, sp)
    sc_late = np.asarray(compute_scores(st2, no_mesh, tp, sp,
                                        jnp.zeros((n, k)), jnp.zeros((n,)), net))
    assert abs(sc_late[0, 0]) < 1e-3

    # contrast: without the status clear the deficit would be permanent
    st_bug = on_prune(st, in_mesh & down_nbr[:, None, :], tp)
    st_bug = clear_edges(st_bug, retained)
    for t in range(20):
        st_bug = refresh_scores(st_bug, no_mesh, t, tp, sp)
    sc_bug = np.asarray(compute_scores(st_bug, no_mesh, tp, sp,
                                       jnp.zeros((n, k)), jnp.zeros((n,)), net))
    assert sc_bug[0, 0] < -thr * thr / 2  # latched deficit never heals


@pytest.mark.parametrize("engine", ENGINES)
def test_restarting_peer_loses_soft_state(engine):
    """A crashing node restarts with an empty seen-cache/mcache (soft state
    is rebuilt from the network — survey §5 failure detection; the engine's
    down transition models the process dying)."""
    topo, net, cfg, st, step = build(engine=engine)
    n = net.n_peers
    up = jnp.ones((n,), bool)
    st = run(step, st, up, 5, publishes={0: pub(n - 1)})
    assert len(received(st, 0)) > 0

    down = up.at[0].set(False)
    st = step(st, *nopub(), down)
    # seen-cache wiped at the crash
    assert received(st, 0) == set()
    assert np.asarray(st.mcache)[0].sum() == 0

    # back up: re-receives traffic from scratch
    st = step(st, *nopub(), up)
    st = run(step, st, up, 10, publishes={2: pub(n - 1)})
    assert len(received(st, 0)) > 0


@pytest.mark.parametrize("engine", ENGINES)
def test_down_origin_publishes_nothing(engine):
    """A stopped process publishes nothing (upstream has no such event).
    The publish of an origin that is down takes its slot, as the ring
    allocator says, and nobody holds the message, the origin included:
    nothing of it enters seen-cache, forward set, mcache or first
    receipts, so the peer cannot serve it by gossip once it is back."""
    topo, net, cfg, st, step = build(engine=engine)
    n = net.n_peers
    up = jnp.ones((n,), bool)
    st = run(step, st, up, 5)
    down = up.at[0].set(False)
    st = step(st, *nopub(), down)
    ev_before = np.asarray(st.core.events)
    # peer 0 publishes while down (slot 0); a live origin beside it (slot 1)
    po, pt, pv = (jnp.asarray([0, n - 1, -1, -1], jnp.int32),
                  jnp.asarray([0, 0, -1, -1], jnp.int32),
                  jnp.asarray([True, True, False, False]))
    st = step(st, po, pt, pv, down)
    assert int(st.core.msgs.cursor) == 2
    assert np.asarray(st.core.msgs.origin)[:2].tolist() == [0, n - 1]
    assert (np.asarray(st.core.events)[EV.PUBLISH_MESSAGE]
            - ev_before[EV.PUBLISH_MESSAGE]) == 1
    st = run(step, st, down, 4)
    assert all(0 not in received(st, p) for p in range(n))
    assert sum(1 in received(st, p) for p in range(1, n)) == n - 1
    fr = np.asarray(st.core.dlv.first_round)
    assert (fr[:, 0] < 0).all() and fr[n - 1, 1] >= 0
    assert not (np.asarray(st.mcache)[0] != 0).any()
    assert not (np.asarray(st.core.dlv.fwd)[0] != 0).any()
    # back up: it gossips nothing of what it never published
    st = run(step, st, up, 6)
    assert all(0 not in received(st, p) for p in range(n))
