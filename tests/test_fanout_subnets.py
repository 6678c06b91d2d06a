"""The fanout path at the attestation-subnet shape (PR 31): the graph
that sparse subscriptions need, the phase engine's packed fanout form at
K > 32, the FanoutTTL on the clock of rounds (engine and oracle), one
fanout slot a topic, the ``gsx.fanout`` part scope, and the engine
against the scalar oracle at 64 topics, 2 a peer, a heartbeat every 8
rounds."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from go_libp2p_pubsub_tpu import api, driver, graph
from go_libp2p_pubsub_tpu.config import (
    GossipSubParams,
    PeerScoreParams,
    PeerScoreThresholds,
    TopicScoreParams,
)
from go_libp2p_pubsub_tpu.models.gossipsub import (
    GossipSubConfig,
    GossipSubState,
    fanout_carry_words,
    fanout_carry_words_packed,
    make_gossipsub_step,
    pack_fanout_peers,
    unpack_fanout_peers,
)
from go_libp2p_pubsub_tpu.models.gossipsub_phase import (
    make_gossipsub_phase_step,
)
from go_libp2p_pubsub_tpu.oracle.gossipsub import OracleGossipSub
from go_libp2p_pubsub_tpu.perf import stages
from go_libp2p_pubsub_tpu.state import Net

P = 4


def _involution(topo):
    n, k = topo.nbr.shape
    ok = topo.nbr_ok
    rows = np.arange(n)[:, None].repeat(k, 1)
    back = topo.nbr[np.clip(topo.nbr, 0, None), topo.rev]
    assert np.array_equal(back[ok], rows[ok])
    assert np.array_equal(
        topo.outbound[ok],
        ~topo.outbound[np.clip(topo.nbr, 0, None), topo.rev][ok])
    assert (topo.nbr[ok] != rows[ok]).all()
    assert np.array_equal(topo.degree, ok.sum(axis=1))
    # left-packed, ascending, no edge twice
    assert (ok[:, :-1] >= ok[:, 1:]).all()
    nb = np.where(ok, topo.nbr, np.iinfo(np.int32).max)
    assert (np.diff(nb, axis=1)[ok[:, 1:]] > 0).all()


def _co_subscribers(topo, subs):
    """``[N, S]`` neighbours that subscribe the topic of my slot s."""
    nbr = np.clip(topo.nbr, 0, None)
    out = np.zeros(subs.my_topics.shape, np.int64)
    for s in range(subs.my_topics.shape[1]):
        tp = np.clip(subs.my_topics[:, s], 0, None)
        out[:, s] = (subs.subscribed[nbr, tp[:, None]] & topo.nbr_ok).sum(1)
    return out


@pytest.mark.parametrize("n,n_topics", [(600, 64), (40, 64), (300, 8)])
def test_subnet_connect_is_a_symmetric_involution_with_subnet_peers(n, n_topics):
    subs = graph.subscribe_random(n, n_topics, 2, seed=4)
    topo = graph.subnet_connect(subs, d_any=10, d_subnet=5, seed=1)
    _involution(topo)
    again = graph.subnet_connect(subs, d_any=10, d_subnet=5, seed=1)
    assert np.array_equal(again.nbr, topo.nbr)
    other = graph.subnet_connect(subs, d_any=10, d_subnet=5, seed=2)
    assert not np.array_equal(other.nbr, topo.nbr)
    assert topo.degree.min() >= min(10, n - 1)
    assert topo.max_degree == topo.degree.max()
    # every peer reaches min(d_subnet, members - 1) co-subscribers in each
    # of its topics: what it dialed itself
    members = subs.subscribed.sum(axis=0)
    want = np.minimum(5, members[subs.my_topics] - 1)
    assert (_co_subscribers(topo, subs) >= want).all()
    # without the subnet dials a peer has under one a topic, and no mesh
    plain = graph.random_connect(n, d=10, seed=1)
    if n_topics == 64 and n == 600:
        assert _co_subscribers(plain, subs).mean() < 1.0
        assert _co_subscribers(topo, subs).mean() > 5.0


def test_subnet_connect_clamps_to_the_members_there_are():
    # topic 0 has one member, topic 1 two, topic 2 three, topic 3 the rest
    n = 30
    mask = np.zeros((n, 4), bool)
    mask[0, 0] = mask[1:3, 1] = mask[3:6, 2] = True
    mask[6:, 3] = True
    subs = graph.subscribe_mask(mask)
    src, dst = graph.subnet_dials(mask, d_any=0, d_subnet=5, seed=3)
    dials = set(zip(src.tolist(), dst.tolist()))
    assert not any(a == 0 or b == 0 for a, b in dials)     # nobody to dial
    assert {(1, 2), (2, 1)} <= dials
    assert {(a, b) for a in (3, 4, 5) for b in (3, 4, 5) if a != b} <= dials
    for a in range(6, n):                       # five distinct others each
        mine = [b for x, b in dials if x == a]
        assert len(mine) == len(set(mine)) == 5 and all(b >= 6 for b in mine)
    topo = graph.subnet_connect(subs, d_any=0, d_subnet=5, seed=3)
    _involution(topo)
    assert topo.degree[0] == 0 and topo.degree[1] == topo.degree[2] == 1
    assert (topo.degree[3:6] == 2).all()
    with pytest.raises(ValueError, match="exceeds K"):
        graph.subnet_connect(subs, d_any=0, d_subnet=5, seed=3, max_degree=3)


def test_api_network_subnet_connect_delivers_from_outside_the_topic():
    net = api.Network()
    nodes = net.add_nodes(40)
    inboxes = {}
    for i, nd in enumerate(nodes):
        for name in ("subnet%d" % (i % 8), "subnet%d" % ((i + 3) % 8)):
            inboxes[(i, name)] = nd.join(name).subscribe()
    net.subnet_connect(d_any=4, d_subnet=3, seed=2)
    net.start()
    net.run(4)
    # node 0 subscribes subnet0 and subnet3; it publishes to subnet5
    outsider = nodes[0].join("subnet5")
    outsider.publish(b"attestation")
    net.run(10)
    members = [i for i in range(40) if (i, "subnet5") in inboxes]
    assert len(members) == 10
    got = [inboxes[(i, "subnet5")].next() is not None for i in members]
    assert all(got), got


# ---------------------------------------------------------------------------
# the engine at the shape


def _shape(n, n_topics, seed, max_degree=None, hb=8, scored=True,
           interval=1.0, ttl=60.0):
    subs = graph.subscribe_random(n, n_topics, 2, seed=seed)
    topo = graph.subnet_connect(subs, d_any=6, d_subnet=4, seed=seed,
                                max_degree=max_degree)
    net = Net.build(topo, subs)
    sp = None
    if scored:
        tp = TopicScoreParams(mesh_message_deliveries_weight=0.0,
                              mesh_failure_penalty_weight=0.0,
                              invalid_message_deliveries_weight=0.0)
        sp = PeerScoreParams(topics={t: tp for t in range(n_topics)},
                             skip_app_specific=True)
    params = dataclasses.replace(
        GossipSubParams(), flood_publish=False, heartbeat_interval=interval,
        fanout_ttl=ttl)
    cfg = GossipSubConfig.build(params, PeerScoreThresholds(),
                                score_enabled=scored, heartbeat_every=hb)
    cfg = dataclasses.replace(cfg, count_events=False)
    assert cfg.fanout_slots == 2
    return topo, subs, net, cfg, sp


def _publishes(rounds, n, n_topics, seed):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, n, (rounds, P)).astype(np.int32)),
            jnp.asarray(rng.integers(0, n_topics, (rounds, P)).astype(np.int32)),
            jnp.ones((rounds, P), bool))


def _same_trees(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        if jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key):
            x, y = jax.random.key_data(x), jax.random.key_data(y)
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_phase_r1_equals_the_per_round_step_at_k_over_32_with_fanout():
    """K = 40 > 32: the phase loop carries the fanout peers packed in TWO
    words a (peer, slot). At r = 1 it is the per-round step bit for bit
    (which keeps the ``[N,F,K]`` bool plane), fanout slots filling on the
    way."""
    n, t = 96, 8
    _, _, net, cfg, sp = _shape(n, t, seed=5, max_degree=40, hb=1)
    assert net.max_degree == 40
    step = make_gossipsub_step(cfg, net, score_params=sp)
    # exact_counters: the phase engine's static weight elision leaves the
    # unread P3 counter unlike the per-round step's
    phase = make_gossipsub_phase_step(cfg, net, 1, score_params=sp,
                                      exact_counters=True)
    po, pt, pv = _publishes(20, n, t, seed=6)
    a = GossipSubState.init(net, 64, cfg, score_params=sp, seed=3)
    b = GossipSubState.init(net, 64, cfg, score_params=sp, seed=3)
    for i in range(20):
        a = step(a, po[i], pt[i], pv[i])
        b = phase(b, po[i:i + 1], pt[i:i + 1], pv[i:i + 1], do_heartbeat=True)
    _same_trees(a, b)
    live = np.asarray(a.fanout_topic) >= 0
    assert live.sum() > 20
    assert (np.asarray(a.fanout_peers).sum(axis=2)[live] > 0).mean() > 0.5


@pytest.mark.parametrize("k", [16, 32, 33, 65])
def test_fanout_peers_pack_into_as_many_words_as_the_degree_needs(k):
    rng = np.random.default_rng(k)
    peers = jnp.asarray(rng.random((20, 2, k)) < 0.2)
    packed = pack_fanout_peers(peers)
    assert packed.shape == (20, 2, -(-k // 32)) and packed.dtype == jnp.uint32
    assert np.array_equal(np.asarray(unpack_fanout_peers(packed, k)),
                          np.asarray(peers))
    topic = jnp.asarray(rng.integers(-1, 4, (20, 2)).astype(np.int32))
    msg_topic = jnp.asarray(rng.integers(-1, 4, (70,)).astype(np.int32))
    assert np.array_equal(
        np.asarray(fanout_carry_words_packed(packed, k, topic, msg_topic)),
        np.asarray(fanout_carry_words(peers, topic, msg_topic)))


@pytest.mark.parametrize("max_degree", [None, 40], ids=["one-word", "k40-two-words"])
def test_a_fanout_publish_reaches_its_topic_through_the_phase_engine(max_degree):
    n, t, r = 128, 8, 8
    topo, subs, net, cfg, sp = _shape(n, t, seed=9, max_degree=max_degree)
    phase = make_gossipsub_phase_step(cfg, net, r, score_params=sp)
    st = GossipSubState.init(net, 64, cfg, score_params=sp, seed=3)
    none = (jnp.full((r, P), -1, jnp.int32), jnp.zeros((r, P), jnp.int32),
            jnp.ones((r, P), bool))
    for _ in range(3):
        st = phase(st, *none, do_heartbeat=True)
    # one publish from a peer outside topic 0 that has a neighbour inside
    sub0 = subs.subscribed[:, 0]
    near = (sub0[np.clip(topo.nbr, 0, None)] & topo.nbr_ok).any(axis=1)
    origin = int(np.flatnonzero(~sub0 & near)[0])
    po = np.full((r, P), -1, np.int32)
    po[0, 0] = origin
    st = phase(st, jnp.asarray(po), none[1], none[2], do_heartbeat=True)
    st = phase(st, *none, do_heartbeat=True)
    fr = np.asarray(st.core.dlv.first_round)[:, 0]
    assert fr[origin] == 3 * r
    assert (fr[sub0] >= 0).all()                   # every subscriber
    assert (fr[~sub0 & (np.arange(n) != origin)] < 0).all()   # nobody else
    slot = int(np.flatnonzero(np.asarray(st.fanout_topic)[origin] == 0)[0])
    peers = topo.nbr[origin, np.asarray(st.fanout_peers)[origin, slot]]
    assert 0 < len(peers) <= cfg.D and sub0[peers].all()
    assert (fr[peers] == 3 * r + 1).all()          # pushed the round after


def test_same_round_publishes_to_one_topic_share_one_fanout_slot():
    n, t, r = 64, 8, 4
    topo, subs, net, cfg, sp = _shape(n, t, seed=11, scored=False, hb=4)
    outside = np.flatnonzero(~subs.subscribed[:, 2] & ~subs.subscribed[:, 5])
    origin = int(outside[0])
    phase = make_gossipsub_phase_step(cfg, net, r)
    st = GossipSubState.init(net, 64, cfg, seed=3)
    po = np.full((r, P), -1, np.int32)
    pt = np.zeros((r, P), np.int32)
    po[1, :3] = origin                # twice topic 2 and once topic 5
    pt[1, :3] = (2, 5, 2)
    st = phase(st, jnp.asarray(po), jnp.asarray(pt), jnp.ones((r, P), bool),
               do_heartbeat=True)
    assert sorted(np.asarray(st.fanout_topic)[origin].tolist()) == [2, 5]
    assert (np.asarray(st.fanout_lastpub)[origin] == 1).all()
    o = OracleGossipSub(topo, subs, cfg, msg_slots=64, seed=5)
    o.step()
    o.step([(origin, 2, True), (origin, 5, True), (origin, 2, True)])
    assert sorted(o.fanout[origin]) == [2, 5]


def test_a_fanout_slot_lives_60_s_of_heartbeats_not_of_rounds():
    """FanoutTTL 60 s at a 0.7 s heartbeat is 86 heartbeats; with a
    heartbeat every 8 rounds that is 688 rounds on the clock ``tick`` and
    ``lastpub`` count in. Engine and oracle agree: the slot outlives 86
    rounds and dies at the first heartbeat past round 8 + 688."""
    n, t, r = 48, 8, 8
    topo, subs, net, cfg, _ = _shape(n, t, seed=13, scored=False,
                                     interval=0.7, ttl=60.0)
    assert cfg.fanout_ttl_ticks == 86 and cfg.fanout_ttl_rounds == 688
    sub0 = subs.subscribed[:, 0]
    origin = int(np.flatnonzero(~sub0)[0])
    phase = make_gossipsub_phase_step(cfg, net, r)
    scan = driver.make_scan(phase, heartbeat_every=r, rounds_per_phase=r,
                            static_heartbeat=True)
    oracle = OracleGossipSub(topo, subs, cfg, msg_slots=64, seed=5)
    st = GossipSubState.init(net, 64, cfg, seed=3)

    def advance(st, rounds, publish_at=None):
        po = np.full((rounds, P), -1, np.int32)
        if publish_at is not None:
            po[publish_at, 0] = origin
        for i in range(rounds):
            oracle.step([(origin, 0, True)] if i == publish_at else ())
        return scan(st, jnp.asarray(po), jnp.zeros((rounds, P), jnp.int32),
                    jnp.ones((rounds, P), bool))

    def alive(st):
        return (0 in np.asarray(st.fanout_topic)[origin].tolist(),
                0 in oracle.fanout[origin])

    st = advance(st, 16, publish_at=8)             # published in round 8
    assert alive(st) == (True, True)
    st = advance(st, 96)                           # round 112: 86 rounds on
    assert int(st.core.tick) == 112 and alive(st) == (True, True)
    st = advance(st, 584)                          # heartbeat of round 695
    assert int(st.core.tick) == 696 and alive(st) == (True, True)
    st = advance(st, 8)                            # heartbeat of round 703
    assert alive(st) == (False, False)


# ---------------------------------------------------------------------------
# the part scope


@pytest.mark.parametrize("op_name,part", [
    ("jit(gs_window_v1)/while/body/closed_call/gs.data_round/gsx.fanout/or",
     "fanout"),
    ("jit(gs_window_v1)/gs.phase_tail/gs.heartbeat/gsx.fanout/"
     "gs.edge_gather/jit(_take)/gather", "fanout"),
    ("jit(gs_window_v1)/gs.data_round/and", None),
    ("jit(f)/gsx.not_a_part/add", None),
    ("", None),
])
def test_part_of_and_the_stages_do_not_see_each_other(op_name, part):
    assert stages.part_of(op_name) == part
    assert stages.stage_of("jit(f)/gs.heartbeat/gsx.fanout/add") == "heartbeat"
    assert stages.stage_of("jit(f)/gsx.fanout/add") == stages.UNSCOPED
    assert stages.PARTS == ("fanout", "attrib", "gater", "churn")
    with pytest.raises(ValueError, match="no part"):
        stages.part("gossip")


def test_instruction_parts_holds_only_what_is_inside_a_part():
    text = '''ENTRY %main (p: u32[8]) -> u32[8] {
  %p = u32[8]{0} parameter(0)
  %or.3 = u32[8]{0} or(%p, %p), metadata={op_name="jit(gs_window_v1)/gs.data_round/gsx.fanout/or"}
  %fusion.7 = u32[8]{0} fusion(%or.3), kind=kLoop, calls=%fc, metadata={op_name="jit(gs_window_v1)/gs.phase_tail/gs.heartbeat/gsx.fanout/select_n"}
  ROOT %and.9 = u32[8]{0} and(%fusion.7, %p), metadata={op_name="jit(gs_window_v1)/gs.data_round/and"}
}'''
    assert stages.instruction_parts(text) == {
        "or.3": "fanout", "fusion.7": "fanout"}
    assert stages.instruction_stages(text) == {
        "p": "unscoped", "or.3": "data_round", "fusion.7": "heartbeat",
        "and.9": "data_round"}


@pytest.mark.parametrize("fanout_slots,dynamic", [(2, False), (0, False),
                                                  (0, True)])
def test_a_window_with_fanout_has_the_part_and_one_without_has_none(
        fanout_slots, dynamic):
    """A static honest window carries no part at all (``fanout``,
    ``attrib``, ``gater``, ``churn``); a ``dynamic_peers`` build of the
    same window carries ``churn`` and nothing else."""
    n, t, r = 64, 8, 4
    _, _, net, cfg, sp = _shape(n, t, seed=15, hb=r)
    cfg = dataclasses.replace(cfg, fanout_slots=fanout_slots)
    phase = make_gossipsub_phase_step(cfg, net, r, score_params=sp,
                                      dynamic_peers=dynamic)
    scan = driver.make_scan(phase, heartbeat_every=r, rounds_per_phase=r,
                            static_heartbeat=True)
    st = GossipSubState.init(net, 64, cfg, score_params=sp, seed=3)
    xs = _publishes(2 * r, n, t, seed=1)
    if dynamic:
        xs += (jnp.ones((2 * r, n), bool).at[:, 5].set(False),)
    jax.block_until_ready(scan(st, *xs))
    (entry,) = [w for w in stages.traced_windows() if w.jitted is scan]
    stage_of, part_of = entry.stages(), entry.parts()
    assert entry.parts() is part_of                # one lowering for both
    assert set(stage_of.values()) == set(stages.STAGES) | {stages.UNSCOPED}
    assert set(part_of) <= set(stage_of)
    if fanout_slots:
        assert set(part_of.values()) == {"fanout"}
        # the part lies inside the stages its callers are
        assert {stage_of[i] for i in part_of} >= {"data_round", "heartbeat"}
        assert len(part_of) > 20
    elif dynamic:
        assert set(part_of.values()) == {"churn"}
        # the transitions, the views and the publish gate at the head,
        # the liveness code's edge gather under the gather's own stage
        assert {stage_of[i] for i in part_of} >= {
            "control_head", "edge_gather"}
    else:
        assert part_of == {}
