"""Host API tests — the reference's tier-2 integration style
(floodsub_test.go getNetHosts/connect/assertReceive) driven through the
Network/Node/Topic/Subscription surface."""

import numpy as np
import pytest

from go_libp2p_pubsub_tpu import api
from go_libp2p_pubsub_tpu.config import default_peer_score_params
from go_libp2p_pubsub_tpu.subscription_filter import AllowlistSubscriptionFilter


def _basic_net(router="gossipsub", n=10, **kw):
    net = api.Network(router=router, **kw)
    nodes = net.add_nodes(n)
    net.dense_connect(d=5, seed=1)
    return net, nodes


def test_basic_delivery_gossipsub():
    net, nodes = _basic_net()
    topics = [nd.join("news") for nd in nodes]
    subs = [t.subscribe() for t in topics]
    net.start()
    mid = topics[0].publish(b"msg-0")
    assert isinstance(mid, bytes) and len(mid) > 8
    net.run(6)  # mesh forms at tick0 heartbeat; then propagation
    got = [s.next() for s in subs]
    # everyone (publisher included) got exactly the published message
    assert all(m is not None and m.data == b"msg-0" for m in got)
    assert all(m.topic == "news" for m in got)
    assert all(s.next() is None for s in subs)
    # signature travels with the message
    assert got[1].HasField("signature")
    assert getattr(got[1], "from") == nodes[0].peer_id


def test_basic_delivery_floodsub():
    net, nodes = _basic_net(router="floodsub")
    subs = [nd.join("t").subscribe() for nd in nodes]
    net.start()
    nodes[3].topics["t"].publish(b"flood")
    net.run(5)
    assert all(s.next() is not None for s in subs)


def test_basic_delivery_randomsub():
    net, nodes = _basic_net(router="randomsub", n=12)
    subs = [nd.join("t").subscribe() for nd in nodes]
    net.start()
    nodes[0].topics["t"].publish(b"rnd")
    net.run(6)
    delivered = sum(1 for s in subs if s.next() is not None)
    assert delivered >= 10  # sqrt-fanout flood reaches (nearly) everyone


def test_multi_topic_isolation():
    net = api.Network()
    nodes = net.add_nodes(8)
    net.connect_all()
    t_a = [nd.join("a") for nd in nodes[:4]]
    t_b = [nd.join("b") for nd in nodes[4:]]
    sub_a = [t.subscribe() for t in t_a]
    sub_b = [t.subscribe() for t in t_b]
    net.start()
    t_a[0].publish(b"for-a")
    net.run(5)
    assert all(s.next().data == b"for-a" for s in sub_a)
    assert all(s.next() is None for s in sub_b)


def test_validator_rejects_propagation():
    net, nodes = _basic_net(n=8)
    subs = [nd.join("t").subscribe() for nd in nodes]
    nodes[0].register_topic_validator(
        "t", lambda pid, m: not m.data.startswith(b"spam"), inline=True
    )
    net.start()
    with pytest.raises(api.ValidationError):
        nodes[1].topics["t"].publish(b"spam-1")  # local reject errors out
    net.run(4)
    assert all(s.next() is None for s in subs[2:])


def test_validator_throttle():
    net, nodes = _basic_net(n=4, validate_throttle=2)
    t = [nd.join("t") for nd in nodes]
    nodes[0].register_topic_validator("t", lambda pid, m: True)  # async
    net.start()
    t[0].publish(b"a")
    t[0].publish(b"b")
    with pytest.raises(api.ValidationError):
        t[0].publish(b"c")  # global throttle exhausted
    net.run(1)  # budget resets per run
    t[0].publish(b"d")


def test_subscription_filter_blocks_join():
    net = api.Network()
    a = net.add_node(sub_filter=AllowlistSubscriptionFilter(["ok"]))
    a.join("ok")
    with pytest.raises(api.APIError):
        a.join("not-ok")


def test_relay_forwards_without_delivery():
    # line: 0 -1- 2, node 1 relays but doesn't subscribe
    net = api.Network()
    nodes = net.add_nodes(3)
    net.connect(nodes[0], nodes[1])
    net.connect(nodes[1], nodes[2])
    t0 = nodes[0].join("t")
    t1 = nodes[1].join("t")
    t2 = nodes[2].join("t")
    cancel = t1.relay()
    sub2 = t2.subscribe()
    net.start()
    t0.publish(b"through")
    net.run(4)
    assert sub2.next().data == b"through"
    cancel()
    assert t1._relays == 0


def test_event_handler_churn():
    net, nodes = _basic_net(n=6)
    topics = [nd.join("t") for nd in nodes]
    h = topics[0].event_handler()
    net.start()
    # initial membership replay: everyone else is already joined
    seen = set()
    while (ev := h.next_event()) is not None:
        kind, pid = ev
        assert kind == api.PEER_JOIN
        seen.add(pid)
    assert seen == {nd.peer_id for nd in nodes[1:]}
    nodes[3].disconnect()
    net.run(1)
    assert h.next_event() == (api.PEER_LEAVE, nodes[3].peer_id)
    nodes[3].reconnect()
    net.run(1)
    assert h.next_event() == (api.PEER_JOIN, nodes[3].peer_id)


def test_a_disconnected_node_publishes_nothing():
    """A stopped process publishes nothing: ``publish`` on a node that is
    down raises, and a publish queued before its node went down is dropped
    before the engine sees it (nobody gets it, the node itself included,
    and it cannot gossip it once it is back)."""
    net, nodes = _basic_net(n=8)
    topics = [nd.join("t") for nd in nodes]
    subs = [t.subscribe() for t in topics]
    net.start()
    net.run(2)
    nodes[3].disconnect()
    with pytest.raises(api.APIError, match="disconnected"):
        topics[3].publish(b"from the dead")
    nodes[3].reconnect()
    topics[3].publish(b"queued, then down")    # local delivery at publish
    assert subs[3].next().data == b"queued, then down"
    nodes[3].disconnect()
    net.run(2)
    nodes[3].reconnect()
    topics[0].publish(b"alive")
    net.run(6)
    for i, sub in enumerate(subs):
        got = [m.data for m in iter(sub.next, None)]
        assert got == [b"alive"], (i, got)


def test_blacklist_disconnects():
    net, nodes = _basic_net(n=6)
    subs = [nd.join("t").subscribe() for nd in nodes]
    net.start()
    net.run(2)  # let the mesh form
    nodes[0].blacklist_peer(nodes[5].peer_id)
    net.run(1)
    nodes[5].topics["t"].publish(b"from-banned")
    net.run(4)
    # the blacklisted peer is cut off: nobody else receives its message
    assert all(subs[i].next() is None for i in range(5))


def test_subscription_buffer_drops():
    net = api.Network(max_publishes_per_round=64)
    nodes = net.add_nodes(2)
    net.connect(nodes[0], nodes[1])
    t0 = nodes[0].join("t")
    sub = nodes[1].join("t").subscribe(buffer=4)
    net.start()
    for i in range(10):
        t0.publish(b"m%d" % i)
    net.run(4)
    assert len(sub._q) == 4
    assert sub.dropped == 6


def test_peer_scores_surface():
    sp = default_peer_score_params(1)
    net, nodes = _basic_net(n=6, score_params=sp)
    [nd.join("t") for nd in nodes]
    net.start()
    net.run(3)
    scores = nodes[0].peer_scores()
    assert scores  # neighbors present
    assert all(isinstance(k, bytes) for k in scores)


def test_traced_network(tmp_path):
    from go_libp2p_pubsub_tpu.trace import sinks

    path = str(tmp_path / "api.json")
    net = api.Network(trace_sinks=[sinks.JSONTracer(path)])
    nodes = net.add_nodes(5)
    net.connect_all()
    subs = [nd.join("t").subscribe() for nd in nodes]
    net.start()
    nodes[0].topics["t"].publish(b"x")
    net.run(4)
    net.stop()
    evs = list(sinks.read_json_trace(path))
    kinds = {e.type for e in evs}
    from go_libp2p_pubsub_tpu.pb import trace_pb2

    assert trace_pb2.TraceEvent.PUBLISH_MESSAGE in kinds
    assert trace_pb2.TraceEvent.DELIVER_MESSAGE in kinds


def test_peer_score_snapshots_detailed():
    # WithPeerScoreInspectDetailed parity: per-topic counters behind the score
    from go_libp2p_pubsub_tpu import api

    net = api.Network(score_params=default_peer_score_params(1))
    nodes = net.add_nodes(10)
    subs = [nd.join("t").subscribe() for nd in nodes]
    net.dense_connect(d=4, seed=1)
    net.start()
    nodes[0].topics["t"].publish(b"x")
    net.run(6)
    snaps = nodes[1].peer_score_snapshots()
    assert snaps, "expected neighbor snapshots"
    for pid, snap in snaps.items():
        assert isinstance(snap.score, float)
        assert "t" in snap.topics
        ts = snap.topics["t"]
        assert ts.time_in_mesh >= 0
        assert ts.first_message_deliveries >= 0.0
        assert snap.ip_colocation_factor >= 0.0
    # somewhere in the network a first delivery must have been credited
    all_snaps = [s for nd in nodes for s in nd.peer_score_snapshots().values()]
    assert any(s.topics["t"].first_message_deliveries > 0 for s in all_snaps)
    # scores agree with the simple inspection map
    simple = nodes[1].peer_scores()
    for pid, snap in snaps.items():
        assert abs(simple[pid] - snap.score) < 1e-6


def test_slow_heartbeat_warning(caplog):
    # gossipsub.go:1305-1312: warn when a tick's wall time exceeds 10% of
    # the heartbeat interval — force it with a tiny interval
    import dataclasses
    import logging

    from go_libp2p_pubsub_tpu import api
    from go_libp2p_pubsub_tpu.config import GossipSubParams

    params = dataclasses.replace(GossipSubParams(), heartbeat_interval=1e-4)
    net = api.Network(params=params)
    nodes = net.add_nodes(4)
    for nd in nodes:
        nd.join("t")
    net.connect_all()
    net.start()
    net.run(1)  # first round is exempt (jit compile)
    with caplog.at_level(logging.WARNING, logger="go_libp2p_pubsub_tpu"):
        net.run(1)
    assert any("slow heartbeat" in r.message for r in caplog.records)


def test_network_rounds_per_phase():
    """The phase engine through the L6 API: publishes land per sub-round,
    deliveries drain at phase boundaries, full coverage."""
    from go_libp2p_pubsub_tpu import api as api_mod

    net = api_mod.Network(rounds_per_phase=4)
    nodes = net.add_nodes(24)
    net.dense_connect(d=6, seed=5)
    subs = [nd.join("x").subscribe() for nd in nodes]
    net.start()
    net.run(8)  # 2 phases of mesh formation
    for i in range(5):
        nodes[i].topics["x"].publish(b"p%d" % i)
    net.run(12)
    got = [sum(1 for _ in s) for s in subs]
    assert all(g == 5 for g in got), got
    import pytest as _pytest

    with _pytest.raises(api_mod.APIError, match="multiple of the phase"):
        net.run(3)


def test_network_phase_mode_no_delivery_loss_under_slot_pressure():
    """Publish far more messages than msg_slots through a long phase: the
    per-phase admission cap must prevent within-phase recycling from
    wiping receipts before the boundary drain (round-4 review repro:
    128 pubs at r=16 delivered only 32 without the cap)."""
    from go_libp2p_pubsub_tpu import api as api_mod

    net = api_mod.Network(rounds_per_phase=16, msg_slots=64)
    nodes = net.add_nodes(24)
    net.dense_connect(d=6, seed=7)
    subs = [nd.join("x").subscribe(buffer=256) for nd in nodes]
    net.start()
    net.run(16)
    for i in range(128):
        nodes[i % 24].topics["x"].publish(b"m%d" % i)
    net.run(16 * 8)
    got = [sum(1 for _ in s) for s in subs]
    assert all(g == 128 for g in got), got


def test_network_phase_mode_runtime_leave():
    """Runtime leave() in phase mode drives the transition through a full
    publish-free phase (round-4 review repro: TypeError before)."""
    from go_libp2p_pubsub_tpu import api as api_mod

    net = api_mod.Network(rounds_per_phase=4)
    nodes = net.add_nodes(16)
    net.dense_connect(d=5, seed=9)
    topics = [nd.join("x") for nd in nodes]
    net.start()
    net.run(8)
    topics[0].close()  # leave
    net.run(8)
    subs = [nd.topics["x"].subscribe() for nd in nodes[1:]]
    nodes[1].topics["x"].publish(b"after-leave")
    net.run(12)
    assert all(sum(1 for _ in s) == 1 for s in subs)


def test_network_phase_cold_start_publish():
    """Publishing immediately after start() in phase mode delivers to the
    whole network: start() runs a formation prelude (one publish-free
    phase) so the first user phase sees a formed mesh — the reference's
    immediate-Join behavior (gossipsub.go:1015-1064), with no warmup
    contract pushed onto the caller (round-4 review missing item 3)."""
    from go_libp2p_pubsub_tpu import api as api_mod

    net = api_mod.Network(rounds_per_phase=8)
    nodes = net.add_nodes(24)
    net.dense_connect(d=6, seed=5)
    subs = [nd.join("x").subscribe() for nd in nodes]
    net.start()
    for i in range(3):
        nodes[i].topics["x"].publish(b"cold%d" % i)
    net.run(8)  # ONE phase, no warmup
    got = [sum(1 for _ in s) for s in subs]
    assert all(g == 3 for g in got), got


def test_run_periodic_checkpoint_resume_exact(tmp_path):
    """run(checkpoint_every=k, checkpoint_path=p) auto-snapshots the
    device state; an identically-built Network that load_checkpoint()s
    the snapshot and runs the remaining rounds lands on EXACTLY the
    uninterrupted run's device state — the PRNG key and tick ride the
    snapshot, so the continued random (and chaos-fault) stream is the
    uninterrupted one."""
    import jax
    import jax.numpy as jnp

    path = str(tmp_path / "auto.npz")

    def build():
        net = api.Network(router="gossipsub", seed=11)
        nodes = net.add_nodes(10)
        net.dense_connect(d=5, seed=2)
        topics = [nd.join("t") for nd in nodes]
        net.start()
        return net, topics

    # uninterrupted: 10 rounds (publish up front), snapshots every 4
    net1, topics1 = build()
    topics1[0].publish(b"payload")
    net1.run(4, checkpoint_every=4, checkpoint_path=path)
    mid_tick = int(net1.state.core.tick)
    net1.run(6)
    final1 = net1.state

    # crashed host: fresh identically-built network resumes the snapshot
    net2, _ = build()
    net2.load_checkpoint(path)
    assert int(net2.state.core.tick) == mid_tick
    net2.run(6)
    final2 = net2.state

    la = jax.tree_util.tree_leaves(final1)
    lb = jax.tree_util.tree_leaves(final2)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        if jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
            x, y = jax.random.key_data(x), jax.random.key_data(y)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_run_checkpoint_arg_validation(tmp_path):
    net, _ = _basic_net(n=4)
    net.start()
    with pytest.raises(api.APIError):
        net.run(1, checkpoint_every=2)  # path missing
    with pytest.raises(api.APIError):
        net.run(1, checkpoint_every=0, checkpoint_path=str(tmp_path / "x"))


def test_run_checkpoint_retention_store_resume(tmp_path):
    """run(keep_last=, keep_every=) grows the single-path overwrite into
    the supervised loop's rolling store: multiple retained snapshots
    under a manifest, corrupted-latest fallback, and load_checkpoint()
    accepting the store DIRECTORY — resuming bit-exact."""
    import jax
    import jax.numpy as jnp

    from go_libp2p_pubsub_tpu.serve import CheckpointStore, truncate_file

    store_dir = str(tmp_path / "store")

    def build():
        net = api.Network(router="gossipsub", seed=13)
        nodes = net.add_nodes(10)
        net.dense_connect(d=5, seed=3)
        topics = [nd.join("t") for nd in nodes]
        net.start()
        return net, topics

    net1, topics1 = build()
    topics1[0].publish(b"payload")
    net1.run(8, checkpoint_every=2, checkpoint_path=store_dir,
             keep_last=2, keep_every=2)
    entries = CheckpointStore(store_dir).entries()
    assert len(entries) >= 2  # a rolling store, not one overwritten file
    ticks = [e["tick"] for e in entries]
    assert ticks == sorted(ticks)
    mid_tick = ticks[-1]
    net1.run(4)
    final1 = net1.state

    net2, _ = build()
    net2.load_checkpoint(store_dir)
    assert int(net2.state.core.tick) == mid_tick
    net2.run(4 + 8 - mid_tick)
    la = jax.tree_util.tree_leaves(final1)
    lb = jax.tree_util.tree_leaves(net2.state)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        if jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
            x, y = jax.random.key_data(x), jax.random.key_data(y)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    # damaged latest: load_checkpoint falls back to the previous entry
    latest = CheckpointStore(store_dir).latest()
    truncate_file(str(tmp_path / "store" / latest["file"]))
    net3, _ = build()
    net3.load_checkpoint(store_dir)
    assert int(net3.state.core.tick) < mid_tick


def test_run_checkpoint_retention_validation(tmp_path):
    net, _ = _basic_net(n=4)
    net.start()
    with pytest.raises(api.APIError):
        net.run(1, checkpoint_every=1,
                checkpoint_path=str(tmp_path / "s"), keep_last=0)
    with pytest.raises(api.APIError):
        net.run(1, checkpoint_every=1,
                checkpoint_path=str(tmp_path / "s"), keep_every=-1)
