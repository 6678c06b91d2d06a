"""GossipSub protocol tests — tier-2 analogues of gossipsub_test.go
(mesh formation, propagation, gossip retrieval, backoff) on the vectorized
router."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from go_libp2p_pubsub_tpu import graph
from go_libp2p_pubsub_tpu.config import GossipSubParams
from go_libp2p_pubsub_tpu.models.gossipsub import (
    GossipSubConfig,
    GossipSubState,
    make_gossipsub_step,
    no_publish,
)
from go_libp2p_pubsub_tpu.ops import bitset
from go_libp2p_pubsub_tpu.state import Net
from go_libp2p_pubsub_tpu.trace.events import EV


def build(n=50, d=8, n_topics=1, msg_slots=32, seed=0, cfg=None, subs=None, **net_kw):
    topo = graph.random_connect(n, d, seed=seed)
    subs = subs or graph.subscribe_all(n, n_topics)
    net = Net.build(topo, subs, **net_kw)
    cfg = cfg or GossipSubConfig.build()
    st = GossipSubState.init(net, msg_slots, cfg, seed=seed)
    step = make_gossipsub_step(cfg, net)
    return topo, net, cfg, st, step


def pub(origins, topics, p=4):
    po = np.full(p, -1, np.int32)
    pt = np.full(p, -1, np.int32)
    pv = np.zeros(p, bool)
    for i, (o, t) in enumerate(zip(origins, topics)):
        po[i], pt[i], pv[i] = o, t, True
    return jnp.asarray(po), jnp.asarray(pt), jnp.asarray(pv)


def run(step, st, n, args=None):
    a = args or no_publish()
    for _ in range(n):
        st = step(st, *a)
    return st


def test_mesh_forms_and_stays_bounded():
    topo, net, cfg, st, step = build(n=60, d=10, seed=3)
    st = run(step, st, 30)
    deg = np.asarray(st.mesh.sum(axis=(1, 2)))
    assert (deg >= 1).all()
    assert (deg <= cfg.Dhi).all()
    # most peers should sit in the healthy band
    assert deg.mean() >= cfg.Dlo


def test_mesh_links_become_mutual():
    topo, net, cfg, st, step = build(n=40, d=8, seed=5)
    st = run(step, st, 20)
    mesh = np.asarray(st.mesh[:, 0, :])
    total = mutual = 0
    for j in range(40):
        for k in range(topo.max_degree):
            if topo.nbr_ok[j, k] and mesh[j, k]:
                total += 1
                mutual += bool(mesh[topo.nbr[j, k], topo.rev[j, k]])
    assert total > 0
    assert mutual / total > 0.95


def test_propagation_all_peers():
    # multihop propagation through the mesh (gossipsub_test.go dense harness)
    topo, net, cfg, st, step = build(n=100, d=10, seed=7)
    st = run(step, st, 10)  # mesh warmup
    st = step(st, *pub([3], [0]))
    st = run(step, st, 10)
    have = np.asarray(bitset.unpack(st.core.dlv.have, 32))[:, 0]
    assert have.all()
    ev = np.asarray(st.core.events)
    assert ev[EV.DELIVER_MESSAGE] == 99


def test_multi_topic_slot_compression():
    # peers subscribe 2 of 8 topics; messages stay within their topic's
    # subscriber set and reach all of it
    n = 120
    topo = graph.random_connect(n, 12, seed=9)
    subs = graph.subscribe_random(n, n_topics=8, topics_per_peer=2, seed=9)
    net = Net.build(topo, subs)
    cfg = GossipSubConfig.build()
    st = GossipSubState.init(net, 32, cfg, seed=0)
    step = make_gossipsub_step(cfg, net)
    st = run(step, st, 15)
    origin = int(np.nonzero(subs.subscribed[:, 3])[0][0])
    st = step(st, *pub([origin], [3]))
    st = run(step, st, 15)
    have = np.asarray(bitset.unpack(st.core.dlv.have, 32))[:, 0]
    subscribers = subs.subscribed[:, 3]
    # no leakage outside the topic
    assert not have[~subscribers].any()
    # gossipsub may need the subnet to be connected *within* subscribers via
    # the union graph; require strong majority coverage
    assert have[subscribers].mean() > 0.9


def test_gossip_ihave_iwant_path():
    # a peer that cannot mesh (permanent backoff both ways) still receives
    # messages via IHAVE -> IWANT -> retransmission (the lazy gossip pull,
    # gossipsub.go:615-716)
    topo, net, cfg, st, step = build(n=30, d=6, seed=11)
    FAR = 2**30
    leech = 0
    # backoff presence blocks heartbeat grafting in both directions
    bp = np.zeros(st.backoff_present.shape, bool)
    be = np.zeros(st.backoff_expire.shape, np.int32)
    bp[leech, :, :] = True
    be[leech, :, :] = FAR
    for k in range(topo.max_degree):
        if topo.nbr_ok[leech, k]:
            j, r = topo.nbr[leech, k], topo.rev[leech, k]
            bp[j, :, r] = True
            be[j, :, r] = FAR
    st = st.replace(
        backoff_present=jnp.asarray(bp), backoff_expire=jnp.asarray(be)
    )
    st = run(step, st, 10)
    assert int(st.mesh[leech].sum()) == 0, "leech must stay out of the mesh"

    st = step(st, *pub([7], [0]))
    st = run(step, st, 12)
    have = np.asarray(bitset.unpack(st.core.dlv.have, 32))
    assert have[leech, 0], "gossip pull must deliver to the meshless peer"


def test_backoff_blocks_regraft():
    topo, net, cfg, st, step = build(n=20, d=6, seed=13)
    st = run(step, st, 10)
    # force-prune everything from peer 0's view with a long backoff
    bp = np.array(st.backoff_present)
    be = np.array(st.backoff_expire)
    bp[0, :, :] = True
    be[0, :, :] = int(st.core.tick) + 50
    mesh = np.array(st.mesh)
    mesh[0, :, :] = False
    st = st.replace(
        backoff_present=jnp.asarray(bp),
        backoff_expire=jnp.asarray(be),
        mesh=jnp.asarray(mesh),
    )
    st2 = run(step, st, 5)
    # peer 0 must not graft anyone while backoff presence holds
    assert int(st2.mesh[0].sum()) == 0


def test_backoff_expiry_allows_regraft():
    topo, net, cfg, st, step = build(n=20, d=6, seed=13)
    st = run(step, st, 10)
    bp = np.array(st.backoff_present)
    be = np.array(st.backoff_expire)
    bp[0, :, :] = True
    be[0, :, :] = int(st.core.tick) + 3
    mesh = np.array(st.mesh)
    mesh[0, :, :] = False
    st = st.replace(
        backoff_present=jnp.asarray(bp),
        backoff_expire=jnp.asarray(be),
        mesh=jnp.asarray(mesh),
    )
    # run past expiry + clear cadence (15) + slack
    st2 = run(step, st, 25)
    assert int(st2.mesh[0].sum()) >= cfg.Dlo


def test_mcache_window_shift():
    topo, net, cfg, st, step = build(n=20, d=6, seed=15)
    st = run(step, st, 5)
    st = step(st, *pub([1], [0]))
    st = run(step, st, 2)
    # the message sits in some window of its receivers
    mc = np.asarray(st.mcache)
    assert (mc != 0).any()
    # after > history_length heartbeats with no traffic, windows drain
    st = run(step, st, cfg.history_length + 1)
    mc = np.asarray(st.mcache)
    assert (mc == 0).all()


def test_ihave_respects_joined_topics():
    # messages of topics a peer didn't join are never requested
    n = 40
    topo = graph.random_connect(n, 8, seed=17)
    subs = graph.subscribe_random(n, n_topics=2, topics_per_peer=1, seed=17)
    net = Net.build(topo, subs)
    cfg = GossipSubConfig.build()
    st = GossipSubState.init(net, 32, cfg, seed=0)
    step = make_gossipsub_step(cfg, net)
    st = run(step, st, 10)
    origin = int(np.nonzero(subs.subscribed[:, 0])[0][0])
    st = step(st, *pub([origin], [0]))
    st = run(step, st, 15)
    have = np.asarray(bitset.unpack(st.core.dlv.have, 32))[:, 0]
    assert not have[~subs.subscribed[:, 0]].any()


def test_graft_prune_events_traced():
    topo, net, cfg, st, step = build(n=30, d=8, seed=19)
    st = run(step, st, 10)
    ev = np.asarray(st.core.events)
    assert ev[EV.GRAFT] > 0
    # over-subscription pruning should have fired somewhere
    deg = np.asarray(st.mesh.sum(axis=(1, 2)))
    assert (deg <= cfg.Dhi).all()


@pytest.mark.slow
def test_count_events_off_identical_protocol_state():
    """Tracer-detached mode (count_events=False) must change nothing but
    the aggregate counters — every protocol-visible array stays identical
    (tracing is opt-in in the reference: WithEventTracer, pubsub.go)."""
    import jax

    cfg_on = GossipSubConfig.build()
    cfg_off = dataclasses.replace(cfg_on, count_events=False)
    topo = graph.random_connect(40, 8, seed=9)
    subs = graph.subscribe_all(40, 1)
    net = Net.build(topo, subs)
    states = {}
    for name, cfg in [("on", cfg_on), ("off", cfg_off)]:
        st = GossipSubState.init(net, 32, cfg, seed=1)
        step = make_gossipsub_step(cfg, net)
        for r in range(12):
            st = step(st, *pub([r % 40], [0]))
        states[name] = st
    a, b = states["on"], states["off"]
    la_all = dict(
        (jax.tree_util.keystr(p), l) for p, l in jax.tree_util.tree_leaves_with_path(a)
    )
    lb_all = dict(
        (jax.tree_util.keystr(p), l) for p, l in jax.tree_util.tree_leaves_with_path(b)
    )
    assert la_all.keys() == lb_all.keys()
    for name in la_all:
        if "events" in name or "key" in name:
            continue
        assert (np.asarray(la_all[name]) == np.asarray(lb_all[name])).all(), name
    # counters-off leaves the event array untouched
    assert (np.asarray(b.core.events) == 0).all()
    assert int(np.asarray(a.core.events)[EV.DELIVER_MESSAGE]) > 0


def test_static_heartbeat_matches_cond():
    """make_gossipsub_step(static_heartbeat=True) is bit-identical to the
    lax.cond cadence when driven with do_heartbeat == (tick % he == 0).
    (The static form exists because the cond's branch-materialization
    copies measured 407 -> 113 ticks/s on the bench — BASELINE.md r3.)"""
    import jax

    he = 3
    cfg = dataclasses.replace(GossipSubConfig.build(), heartbeat_every=he)
    topo = graph.random_connect(48, 8, seed=5)
    net = Net.build(topo, graph.subscribe_all(48, 1))
    st0 = GossipSubState.init(net, 32, cfg, seed=1)
    step_c = make_gossipsub_step(cfg, net)
    step_s = make_gossipsub_step(cfg, net, static_heartbeat=True)

    sa = jax.tree.map(jnp.copy, st0)
    sb = st0
    rng = np.random.default_rng(7)
    for t in range(2 * he + 1):
        args = pub([int(rng.integers(0, 48))], [0])
        sa = step_c(sa, *args)
        sb = step_s(sb, *args, do_heartbeat=(t % he == 0))
    la = jax.tree.leaves(sa)
    lb = jax.tree.leaves(sb)
    for a, b in zip(la, lb):
        if jnp.issubdtype(a.dtype, jax.dtypes.prng_key):
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        assert (np.asarray(a) == np.asarray(b)).all()


@pytest.mark.parametrize("cap", [0, 1, 2, 3, 5])
def test_served_capped(cap):
    """The IWANT retransmission cap on the 2-bit served counter
    (served_hi:served_lo, saturating at 3): a slot is capped once its
    count reaches gossip_retransmission, and a cap beyond the counter's
    range clamps to 3."""
    from go_libp2p_pubsub_tpu.models.gossipsub import _served_capped

    rng = np.random.default_rng(cap)
    lo = rng.integers(0, 1 << 32, size=(5, 3, 2), dtype=np.uint32)
    hi = rng.integers(0, 1 << 32, size=(5, 3, 2), dtype=np.uint32)
    cfg = dataclasses.replace(GossipSubConfig.build(),
                              gossip_retransmission=cap)
    got = bitset.unpack(
        _served_capped(cfg, jnp.asarray(lo), jnp.asarray(hi)), 64)
    bit = lambda w: (w[..., None] >> np.arange(32, dtype=np.uint32)) & 1
    count = (bit(lo) + 2 * bit(hi)).reshape(5, 3, 64)
    assert np.array_equal(np.asarray(got), count >= min(cap, 3))
    assert {0, 1, 2, 3} == set(np.unique(count))
