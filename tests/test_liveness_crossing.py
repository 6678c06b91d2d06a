"""The liveness plane crosses the edges once a phase (PR 38).

``apply_peer_transitions`` builds one ``[N]`` code (bit 0 ``down_tr``, bit 1
the new ``up``), broadcasts it along K and sends it through
``Net.edge_gather``: for a plane constant along K the edge involution IS the
neighbour view, so both views the function needs come out of ONE gather,
through whatever the net's layout gives an edge gather (the tiered plan,
rolls, the flat involution). The parent's form, two ``Net.peer_gather``
calls on ``[N]`` bool planes, lives on here alone, as the plain reference:
the state tree and ``live`` must come out bit for bit on every layout.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from go_libp2p_pubsub_tpu import driver, graph
from go_libp2p_pubsub_tpu.config import (
    GossipSubParams,
    PeerScoreParams,
    PeerScoreThresholds,
    TopicScoreParams,
)
from go_libp2p_pubsub_tpu.models.gossipsub import (
    GossipSubConfig,
    GossipSubState,
    apply_peer_transitions,
    live_step_views,
    make_gossipsub_step,
    prepare_step_consts,
    px_connect,
    set_blacklist,
)
from go_libp2p_pubsub_tpu.models.gossipsub_phase import (
    make_gossipsub_phase_step,
)
from go_libp2p_pubsub_tpu.ops import edges
from go_libp2p_pubsub_tpu.perf import stages
from go_libp2p_pubsub_tpu.state import Net

N, D, M, P = 97, 10, 32, 4
WARM, HEAL = 10, 4


def score_params():
    """P1 / P2 / P3 / P3b live, so a departing mesh peer leaves counters to
    clear, a deficit to convert and a retained (negative) neighbour."""
    tp = TopicScoreParams(
        topic_weight=1.0,
        time_in_mesh_weight=0.01, time_in_mesh_cap=10.0,
        first_message_deliveries_weight=1.0,
        first_message_deliveries_cap=50.0,
        first_message_deliveries_decay=0.9,
        mesh_message_deliveries_weight=-0.02,
        mesh_message_deliveries_threshold=2.0,
        mesh_message_deliveries_activation=2.0,
        mesh_message_deliveries_decay=0.9,
        mesh_failure_penalty_weight=-0.02,
        mesh_failure_penalty_decay=0.9,
    )
    return PeerScoreParams(
        topics={0: tp}, skip_app_specific=True,
        behaviour_penalty_weight=-10.0, behaviour_penalty_threshold=0.0,
        behaviour_penalty_decay=0.9, ip_colocation_factor_weight=0.0)


def config(do_px=False, **kw):
    params = dataclasses.replace(GossipSubParams(), flood_publish=False,
                                 do_px=do_px)
    return GossipSubConfig.build(params, PeerScoreThresholds(
        gossip_threshold=-2.0, publish_threshold=-4.0,
        graylist_threshold=-8.0, accept_px_threshold=0.0,
        opportunistic_graft_threshold=1.0), score_enabled=True, **kw)


def topology(kind):
    return (graph.ring_lattice(N - 1, d=4) if kind == "banded"
            else graph.random_connect(N, D, seed=3))


@functools.lru_cache(maxsize=None)
def warmed(kind, do_px=False):
    """``(topo, state, row)``: a state a dense net of the topology
    ran ``WARM`` rounds of publishes into (meshes, mcache, seen-caches,
    counters and scores all non-zero), then lost six peers and one
    blacklisted one for ``HEAL`` rounds; and the liveness row the tests
    apply to it: three of the six come back, four others leave, the
    blacklisted one is marked up and must stay down."""
    topo = topology(kind)
    n = topo.n_peers
    net = Net.build(topo, graph.subscribe_all(n, 1))
    cfg, sp = config(do_px), score_params()
    dormant = graph.dormant_edges(topo, 0.4, seed=4) if do_px else None
    st = GossipSubState.init(net, M, cfg, score_params=sp, seed=5,
                             dormant=dormant)
    step = make_gossipsub_step(cfg, net, score_params=sp, dynamic_peers=True)
    rng = np.random.default_rng(7)
    gone = rng.choice(n, 11, replace=False)
    black, first, later = gone[0], gone[1:7], gone[7:]
    up = np.ones(n, bool)
    for i in range(WARM + HEAL):
        if i == WARM:
            st = set_blacklist(st, np.arange(n) == black)
            up[first] = False
        po = jnp.asarray(rng.integers(0, n, P).astype(np.int32))
        st = step(st, po, jnp.zeros(P, jnp.int32), jnp.ones(P, bool),
                  jnp.asarray(up))
    row = up.copy()
    row[first[:3]] = True
    row[later] = False
    assert not np.asarray(st.up)[black] and row[black]
    return topo, st, row


def tiered(net, topo, compact):
    """The net with a plan its size would not earn it: K0 < K."""
    k0 = topo.max_degree // 2
    assert 0 < int(topo.nbr_ok[:, k0:].sum()) < topo.nbr_ok[:, k0:].size
    return net.replace(tiers=edges.plan_tiers(
        np.asarray(net.edge_perm), topo.nbr_ok, k0=k0, compact=compact))


def build_net(layout, topo):
    subs = graph.subscribe_all(topo.n_peers, 1)
    if layout.startswith("csr"):
        return Net.build(topo, subs, edge_layout="csr",
                         dynamic=layout == "csr-dynamic")
    net = Net.build(topo, subs)
    if layout == "banded":
        assert net.band_off is not None
    elif layout == "dense":
        net = net.replace(tiers=None)
    else:
        net = tiered(net, topo, compact=layout == "tiered-compact")
        assert net.tiers.compact == (layout == "tiered-compact")
    return net


class TwoPeerGathers:
    """The parent's crossing, the plain reference: a net that answers the
    code's edge gather with two peer gathers of ``[N]`` bool planes,
    ``down_tr`` and the new ``up``, as ``apply_peer_transitions`` made them
    before PR 38. Everything else is the net's own."""

    def __init__(self, net):
        self.net = net
        self.calls = 0

    def __getattr__(self, name):
        return getattr(self.net, name)

    def edge_gather(self, x):
        self.calls += 1
        code = x[:, 0, 0]
        down_nbr = self.net.peer_gather((code & 1) != 0)
        up_nbr = self.net.peer_gather((code & 2) != 0)
        return (down_nbr.astype(jnp.uint32)
                | (up_nbr.astype(jnp.uint32) << 1))[..., None]


def step_consts(cfg, net):
    return prepare_step_consts(cfg, net, score_params(), 1.0, None, None,
                               None)


def assert_trees_equal(got, want):
    paths = jax.tree_util.tree_flatten_with_path(got)[0]
    leaves = jax.tree_util.tree_leaves(want)
    assert len(paths) == len(leaves)
    for (path, a), b in zip(paths, leaves):
        if jnp.issubdtype(a.dtype, jax.dtypes.prng_key):
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("layout", ["tiered-compact", "tiered-full", "dense",
                                    "banded", "csr", "csr-dynamic"])
def test_one_code_crossing_equals_the_two_peer_gathers(layout):
    topo, st, row = warmed("banded" if layout == "banded" else "random")
    net = build_net(layout, topo)
    cfg = dataclasses.replace(config(), edge_layout=net.edge_layout)
    tp, up_next = step_consts(cfg, net).tp, jnp.asarray(row)
    # the row does all four things, on a state that has something to lose
    eff = row & ~np.asarray(st.blacklist)
    was = np.asarray(st.up)
    assert (was & ~eff).sum() == 4 and (~was & eff).sum() == 3
    assert (~was & ~eff).sum() == 4 and np.asarray(st.blacklist).sum() == 1
    assert np.asarray(st.score.fmd).any() and np.asarray(st.scores).any()
    assert np.asarray(st.mesh)[was & ~eff].any()

    sets: list = []
    with edges.tally_halo_gathers(sets):
        got_st, got_live = jax.jit(
            lambda s, u: apply_peer_transitions(cfg, net, s, u, tp))(
                st, up_next)
    assert sets == ["edge"]                 # ONE crossing, and no peer gather
    ref = TwoPeerGathers(net)
    want_st, want_live = jax.jit(
        lambda s, u: apply_peer_transitions(cfg, ref, s, u, tp))(st, up_next)
    assert ref.calls == 1
    assert_trees_equal(got_st, want_st)
    np.testing.assert_array_equal(np.asarray(got_live), np.asarray(want_live))
    # the views themselves, as the parent wrote them
    down_nbr = net.peer_gather(jnp.asarray(was & ~eff)) & net.nbr_ok
    live = net.nbr_ok & eff[:, None] & net.peer_gather(jnp.asarray(eff))
    np.testing.assert_array_equal(np.asarray(got_live), np.asarray(live))
    assert np.asarray(live).any() and not np.asarray(live).all()
    dead = np.asarray(down_nbr) | ((was & ~eff)[:, None]
                                   & np.asarray(net.nbr_ok))
    assert dead.any() and (np.asarray(st.mesh) & dead[:, None]).any()
    np.testing.assert_array_equal(np.asarray(got_st.mesh),
                                  np.asarray(st.mesh) & ~dead[:, None])
    np.testing.assert_array_equal(np.asarray(got_st.up), eff)
    # a retained neighbour and a deleted one both lose their P2 credit
    assert (np.asarray(got_st.score.fmd) != np.asarray(st.score.fmd)).any()


def test_px_connect_takes_the_returned_plane_for_the_third_gather():
    """``do_px``: dormant edges wake only between two up peers. The parent
    asked ``up[:, None] & peer_gather(up)`` a third time; ``px_connect``
    now takes the ``live`` plane the transitions returned."""
    topo, st, row = warmed("random", do_px=True)
    net = tiered(Net.build(topo, graph.subscribe_all(N, 1)), topo, True)
    cfg = config(do_px=True)
    consts = step_consts(cfg, net)
    st2, live = apply_peer_transitions(cfg, net, st, jnp.asarray(row),
                                       consts.tp)
    net_l = live_step_views(cfg, net, st2, live, consts)[0]
    assert not np.array_equal(np.asarray(net_l.nbr_ok), np.asarray(live))
    # every pruned peer is offered PX by every pruner
    px_ok = jnp.asarray(net.nbr_ok)
    sets: list = []
    with edges.tally_halo_gathers(sets):
        got = px_connect(cfg, net, net_l, st2, px_ok, live)
    # the suggestions' peer gather and the symmetrising edge gather stay
    assert sets == ["peer", "edge"]
    live_ref = (net.nbr_ok & st2.up[:, None] & net.peer_gather(st2.up))
    want = px_connect(cfg, net, net_l, st2, px_ok, live_ref)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    woke = np.asarray(got) & ~np.asarray(st2.edge_live)
    asleep = np.asarray(px_connect(cfg, net, net_l, st2, px_ok, None))
    assert woke.any() and not (woke & ~np.asarray(live)).any()
    # a static build wakes edges of down peers too: the mask matters here
    assert (asleep & ~np.asarray(got)).any()


def _phase_pair(net, r=4):
    # no fanout, as the churn cell: its maintenance peer-gathers a constant
    cfg, sp = dataclasses.replace(
        config(heartbeat_every=r), edge_layout=net.edge_layout,
        fanout_slots=0), score_params()
    st = GossipSubState.init(net, M, cfg, score_params=sp, seed=5)
    xs = (jnp.full((r, P), -1, jnp.int32), jnp.zeros((r, P), jnp.int32),
          jnp.ones((r, P), bool))
    build = lambda dyn: make_gossipsub_phase_step(
        cfg, net, r, score_params=sp, dynamic_peers=dyn)
    return st, xs, build(False), build(True)


@pytest.mark.parametrize("layout", ["tiered-compact", "banded", "csr"])
def test_a_dynamic_phase_has_one_more_edge_set_and_no_more_peer_sets(layout):
    topo = topology("banded" if layout == "banded" else "random")
    net = build_net(layout, topo)
    st, xs, static, dynamic = _phase_pair(net)
    up = jnp.ones(topo.n_peers, bool)
    fold = lambda step, args: edges.fold_tally(edges.tally_step(
        step, st, args, {"do_heartbeat": True}))
    a, b = fold(static, xs), fold(dynamic, xs + (up,))
    assert b["edge"] == a["edge"] + 1
    assert b.get("peer", 0) == a.get("peer", 0)
    assert b["total"] == a["total"] + 1


def test_a_dynamic_window_counts_no_peer_rows_and_one_more_planned_call():
    """The counter that says the crossing engaged:
    ``TracedWindow.peer_rows_per_dispatch`` is 0 for a dynamic phase window
    (2 * N * K a phase at the parent) and its ``edge_rows_per_dispatch`` is
    the static window's plus the plan's rows."""
    topo = topology("random")
    net = build_net("tiered-compact", topo)
    st, xs, static, dynamic = _phase_pair(net)
    segs = 2
    window = lambda step: driver.make_scan(
        step, heartbeat_every=4, rounds_per_phase=4, static_heartbeat=True)
    rows = lambda a: jnp.stack([a] * segs).reshape((segs * 4,) + a.shape[1:])
    entries = []
    for step, more in ((static, ()),
                       (dynamic, (jnp.ones((segs * 4, N), bool),))):
        scan = window(step)
        jax.eval_shape(scan, st, *(tuple(rows(a) for a in xs) + more))
        (entry,) = [w for w in stages.traced_windows() if w.jitted is scan]
        entries.append(entry)
    fixed, moving = entries
    assert fixed.peer_rows_per_dispatch == moving.peer_rows_per_dispatch == 0.0
    # (a toy plan's rows are no fewer than the flat gather's, only other)
    assert net.tiers.rows != N * topo.max_degree
    assert (moving.edge_rows_per_dispatch
            == fixed.edge_rows_per_dispatch + net.tiers.rows)
    assert (moving.edge_sliced_calls_per_dispatch
            == fixed.edge_sliced_calls_per_dispatch)
    assert moving.edge_table_rows == fixed.edge_table_rows
    # a peer gather in a window is counted: N * K rows a call
    win = driver.make_window(lambda s, x: net.peer_gather(s[:, 0]) ^ x,
                             donate=False)
    x = jnp.zeros(topo.nbr.shape, jnp.uint32)
    jax.eval_shape(win, x, (jnp.stack([x, x]),))
    (entry,) = [w for w in stages.traced_windows() if w.jitted is win]
    assert entry.peer_rows_per_dispatch == x.size
    assert entry.edge_rows_per_dispatch == 0.0
