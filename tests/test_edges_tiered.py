"""The tiered edge gather (``ops/edges.plan_tiers`` /
``edge_permute_tiered``, planned by ``Net.build``): bit for bit the one
full gather ``edge_permute(x, edge_perm)`` on every slot, absent ones
included, on UNMASKED planes; planned only where the code can see that it
pays; counted by the rows it addresses."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from go_libp2p_pubsub_tpu import graph
from go_libp2p_pubsub_tpu.ops import edges
from go_libp2p_pubsub_tpu.state import Net, TopoState


def isolated(n=40):
    """A random graph with peer 0 cut off (its row is all absent)."""
    full = graph.random_connect(n, d=3, seed=4)
    pairs = {(min(a, int(b)), max(a, int(b)))
             for a in range(1, n) for b, ok in zip(full.nbr[a], full.nbr_ok[a])
             if ok and b != 0}
    return graph.from_edges(n, sorted(pairs))


TOPOS = {
    "random-64-d3": lambda: graph.random_connect(64, d=3, seed=1),
    "random-300-d4": lambda: graph.random_connect(300, d=4, seed=2),
    "random-97-d10": lambda: graph.random_connect(97, d=10, seed=3),
    "star": lambda: graph.star(33),
    "isolated-peer": isolated,
    "tree": lambda: graph.tree(50, branching=3),
}


def planes(shape, seed=0):
    """Unmasked random planes over ``[N, K]``: u32 words, bools, f32."""
    rng = np.random.default_rng(seed)
    return {
        "u32[N,K,5]": jnp.asarray(
            rng.integers(0, 2**32, size=shape + (5,), dtype=np.uint32)),
        "bool[N,K]": jnp.asarray(rng.random(shape) < 0.5),
        "f32[N,K]": jnp.asarray(rng.standard_normal(shape), jnp.float32),
        "u32[N,K,2,3]": jnp.asarray(
            rng.integers(0, 2**32, size=shape + (2, 3), dtype=np.uint32)),
    }


def every_k0(k):
    return sorted({0, 1, k // 2, k - 1})


@pytest.mark.parametrize("name", sorted(TOPOS))
def test_tiered_gather_equals_the_full_gather_on_every_slot(name):
    topo = TOPOS[name]()
    perm = edges.build_edge_perm(topo.nbr, topo.rev, topo.nbr_ok)
    n, k = perm.shape
    if name == "isolated-peer":
        assert not topo.nbr_ok[0].any()
    for k0 in every_k0(k):
        tiers = edges.plan_tiers(perm, topo.nbr_ok, k0)
        assert tiers.head.shape == (n, k0)
        tail = int(topo.nbr_ok[:, k0:].sum())
        assert tiers.rows == n * k0 + 2 * tail
        dst = np.asarray(tiers.tail_dst)
        assert (np.diff(dst) > 0).all()         # sorted, unique
        for what, x in planes((n, k), seed=k0).items():
            want = edges.edge_permute(x, jnp.asarray(perm))
            got = jax.jit(edges.edge_permute_tiered)(x, tiers)
            assert got.dtype == want.dtype and got.shape == want.shape
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want), err_msg=f"{what} K0={k0}")


def test_built_net_gathers_through_its_plan():
    """``Net.edge_gather`` of a net that ``build`` tiered, against the
    full gather through its ``edge_perm`` (which stays a field)."""
    topo = graph.random_connect(3000, d=4, seed=5)
    net = Net.build(topo, graph.subscribe_all(3000, 1))
    assert net.tiers is not None
    k0 = net.tiers.head.shape[1]
    assert 0 < k0 < net.max_degree
    assert k0 == edges.pick_k0(topo.nbr_ok.sum(axis=0), 3000)
    for what, x in planes(topo.nbr.shape).items():
        np.testing.assert_array_equal(
            np.asarray(net.edge_gather(x)),
            np.asarray(edges.edge_permute(x, net.edge_perm)), err_msg=what)
    # under vmap (the ensemble's exchange)
    xs = jnp.stack([planes(topo.nbr.shape, s)["u32[N,K,5]"] for s in (1, 2)])
    np.testing.assert_array_equal(
        np.asarray(jax.vmap(net.edge_gather)(xs)),
        np.asarray(jax.vmap(lambda x: edges.edge_permute(x, net.edge_perm))(xs)))


def test_full_columns_plan_nothing_and_trace_to_the_full_gather():
    """K0 = K IS the one full gather: a ring with a chord (every column
    full but not banded) carries no plan, and its jaxpr is
    ``edge_permute``'s."""
    n = 48
    ring = [(i, (i + 1) % n) for i in range(n)]
    chords = [(i, (i + n // 2) % n) for i in range(n // 2)]
    topo = graph.from_edges(n, ring + chords)
    assert topo.nbr_ok.all()
    net = Net.build(topo, graph.subscribe_all(n, 1))
    assert net.band_off is None and net.tiers is None
    x = planes(topo.nbr.shape)["u32[N,K,5]"]
    assert str(jax.make_jaxpr(net.edge_gather)(x)) == str(jax.make_jaxpr(
        lambda x: edges.edge_permute(x, net.edge_perm))(x))


@pytest.mark.parametrize("kw,why", [
    (dict(dynamic=True), "edge_perm is traced there"),
    (dict(edge_layout="csr"), "its own path"),
    (dict(edge_layout="csr", dynamic=True), "its own path"),
])
def test_no_plan_where_the_gather_is_not_the_static_dense_one(kw, why):
    topo = graph.random_connect(3000, d=4, seed=5)
    subs = graph.subscribe_all(3000, 1)
    assert Net.build(topo, subs).tiers is not None
    assert Net.build(topo, subs, **kw).tiers is None, why


def test_banded_net_has_no_plan():
    net = Net.build(graph.ring_lattice(64, d=4), graph.subscribe_all(64, 1))
    assert net.band_off is not None and net.tiers is None


def test_with_overlay_refuses_a_planned_net():
    topo = graph.random_connect(3000, d=4, seed=5)
    subs = graph.subscribe_all(3000, 1)
    net = Net.build(topo, subs)
    with pytest.raises(ValueError, match="tiered"):
        net.with_overlay(TopoState.from_net(net))
    dyn = Net.build(topo, subs, dynamic=True)
    assert dyn.with_overlay(TopoState.from_net(dyn)).tiers is None


def test_tally_records_the_plan_rows():
    topo = graph.random_connect(3000, d=4, seed=5)
    net = Net.build(topo, graph.subscribe_all(3000, 1))
    n, k = topo.nbr.shape
    x = planes((n, k))["u32[N,K,5]"]
    rows: list = []
    sets: list = []
    with edges.tally_index_rows(rows), edges.tally_halo_gathers(sets):
        jax.eval_shape(net.edge_gather, x)
        jax.eval_shape(net.replace(tiers=None).edge_gather, x)
        jax.eval_shape(net.peer_gather, x[:, 0])
    k0 = net.tiers.head.shape[1]
    tail = int(topo.nbr_ok[:, k0:].sum())
    assert rows == [("edge", n * k0 + 2 * tail), ("edge", n * k),
                    ("peer", n * k)]
    assert net.tiers.rows == n * k0 + 2 * tail < n * k
    # a tiered gather is still ONE gather set (hlo-audit, cost model)
    assert sets == ["edge", "edge", "peer"]
    banded = Net.build(graph.ring_lattice(64, d=4), graph.subscribe_all(64, 1))
    rows.clear()
    with edges.tally_index_rows(rows):
        jax.eval_shape(banded.edge_gather, jnp.zeros((64, 8, 2), jnp.uint32))
        jax.eval_shape(banded.peer_gather, jnp.zeros((64,), jnp.uint32))
    assert rows == [("edge", 0), ("peer", 0)]   # rolls address no row


def cost(col_fill, n, k0):
    """The cost rule, spelt out: head rows, tail rows, the fixed cost."""
    tail = int(np.sum(col_fill[k0:]))
    fixed = edges.TIER_FIXED_NS if k0 < len(col_fill) else 0.0
    return n * k0 * edges.HEAD_ROW_NS + tail * edges.TAIL_ROW_NS + fixed


@pytest.mark.parametrize("col_fill,n,why", [
    ([1000] * 8, 1000, "every column full: K0 = K"),
    ([1000] * 8 + [999], 1000, "one slot short of full: K0 = K"),
    ([1000, 1000, 1000, 600, 200, 40, 3, 1], 1000, "a thinning tail"),
    ([100_000] * 10 + [99_000, 90_000, 60_000, 30_000, 9_000, 2_000, 300,
                       40, 5, 1], 100_000, "random_connect's shape"),
    ([32, 1, 1, 1, 1, 1, 1, 1], 33, "a toy star: the fixed cost decides"),
    ([20_000] + [1] * 63, 20_000, "a star's hub"),
    ([0, 0, 0], 10, "no edge at all"),
])
def test_pick_k0_is_the_least_cost_on_a_hand_made_histogram(col_fill, n, why):
    k = len(col_fill)
    k0 = edges.pick_k0(col_fill, n)
    costs = [cost(col_fill, n, j) for j in range(k + 1)]
    assert costs[k0] == min(costs), why
    if costs[k] == min(costs):
        assert k0 == k, "K0 = K wins ties"
    perm = np.arange(n * k, dtype=np.int32).reshape(n, k)
    ok = np.arange(n)[:, None] < np.asarray(col_fill)[None, :]
    plan = edges.plan_tiers(perm, ok)
    assert (plan is None) == (k0 == k)
    if plan is not None:
        assert plan.head.shape[1] == k0
