"""The tiered edge gather (``ops/edges.plan_tiers`` /
``edge_permute_tiered``, planned by ``Net.build``): bit for bit the one
full gather ``edge_permute(x, edge_perm)`` on every slot, absent ones
included, on UNMASKED planes, through one gather out of a compact table
(the head columns plus the tail's present rows), addressed K-major over
the lane-padded peer axis (row ``k*Np + n``); planned only where the code
can see that it pays; a plane wider than one sublane tile of words in
tile-wide column slices where only the slices' tables lie under the cliff
(``word_slices``); counted by the rows it addresses, the rows and
tile-rows of the table it reads and the calls that were sliced."""

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
    # the peer axis against the lanes: no pad, one peer over, two tiles
    "random-256-d4": lambda: graph.random_connect(256, d=4, seed=6),
    "random-129-d3": lambda: graph.random_connect(129, d=3, seed=7),
    "star": lambda: graph.star(33),
    "isolated-peer": isolated,
    "tree": lambda: graph.tree(50, branching=3),
}


def planes(shape, seed=0):
    """Unmasked random planes over ``[N, K]``: u32 words (the data
    round's 5, the control head's 14), bools, f32."""
    rng = np.random.default_rng(seed)
    return {
        "u32[N,K,5]": jnp.asarray(
            rng.integers(0, 2**32, size=shape + (5,), dtype=np.uint32)),
        "bool[N,K]": jnp.asarray(rng.random(shape) < 0.5),
        "f32[N,K]": jnp.asarray(rng.standard_normal(shape), jnp.float32),
        "u32[N,K,2,3]": jnp.asarray(
            rng.integers(0, 2**32, size=shape + (2, 3), dtype=np.uint32)),
        "u32[N,K,14]": jnp.asarray(
            rng.integers(0, 2**32, size=shape + (14,), dtype=np.uint32)),
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
        for compact in (True, False):
            tiers = edges.plan_tiers(perm, topo.nbr_ok, k0, compact=compact)
            assert tiers.head.shape == (k0, edges.lane_padded(n))
            assert tiers.compact is compact
            for what, x in planes((n, k), seed=k0).items():
                want = edges.edge_permute(x, jnp.asarray(perm))
                got = jax.jit(edges.edge_permute_tiered)(x, tiers)
                assert got.dtype == want.dtype and got.shape == want.shape
                np.testing.assert_array_equal(
                    np.asarray(got), np.asarray(want),
                    err_msg=f"{what} K0={k0} compact={compact}")


def sliced(tiers, k):
    """The same plan with the cliff at its table's own size: every plane
    wider than a tile crosses in slices."""
    return tiers.replace(cliff=tiers.table_rows(k))


@pytest.mark.parametrize("compact", [True, False])
@pytest.mark.parametrize("w,slices", [(8, 1), (9, 2), (13, 2), (17, 3)])
def test_sliced_gather_equals_the_full_gather_on_every_slot(w, slices,
                                                            compact):
    for name in ("random-97-d10", "random-129-d3", "isolated-peer"):
        topo = TOPOS[name]()
        perm = edges.build_edge_perm(topo.nbr, topo.rev, topo.nbr_ok)
        n, k = perm.shape
        rng = np.random.default_rng(w)
        x = jnp.asarray(
            rng.integers(0, 2**32, size=(n, k, w), dtype=np.uint32))
        want = edges.edge_permute(x, jnp.asarray(perm))
        for k0 in every_k0(k):
            tiers = edges.plan_tiers(perm, topo.nbr_ok, k0, compact=compact)
            rows = tiers.table_rows(k)
            assert len(edges.word_slices(rows, w, tiers.cliff)) == 1
            tiers = sliced(tiers, k)
            assert len(edges.word_slices(rows, w, tiers.cliff)) == slices
            got = jax.jit(edges.edge_permute_tiered)(x, tiers)
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(want),
                err_msg=f"{name} K0={k0} compact={compact}")


WHOLE = lambda w: [(0, w)]
TILES = lambda w: [(lo, min(lo + 8, w)) for lo in range(0, w, 8)]


@pytest.mark.parametrize("rows,w,want,why", [
    (2_512_481, 13, TILES, "random-100k's control head out of the compact "
     "table: 5.02 M tile-rows, a slice 2.51 M"),
    (2_512_481, 5, WHOLE, "its sub-round gathers: one tile"),
    (1_751_680, 13, TILES, "sybil-50k's control head out of the full "
     "table: 3,503,360 tile-rows, a slice 1.75 M"),
    (1_751_680, 5, WHOLE, "its sub-round gathers"),
    (6_506_240, 17, WHOLE, "eth2-100k's control head: a slice of the full "
     "table is itself beyond the cliff"),
    (6_506_240, 6, WHOLE, "its sub-round gathers"),
    (364_032, 6, WHOLE, "random-10k-t8's control head: one tile"),
    (364_032, 13, WHOLE, "a small table read two tiles wide: 0.73 M "
     "tile-rows, under the cliff"),
    (1_750_000, 16, WHOLE, "two tiles to the tile-row under the cliff"),
    (1_750_001, 16, TILES, "and one tile-row over it"),
    (3_500_000, 9, TILES, "a slice's table on the cliff itself"),
    (3_500_001, 9, WHOLE, "and one row beyond it"),
    (10**9, 8, WHOLE, "up to one tile of words nothing splits"),
    (10**9, 1, WHOLE, "a plane of single words"),
])
def test_word_slices_by_hand(rows, w, want, why):
    assert edges.TILE_WORDS == 8 and edges.TABLE_CLIFF_ROWS == 3_500_000
    assert edges.word_slices(rows, w) == want(w), why
    assert edges.tile_rows(rows, w) == rows * -(-w // 8)
    # the cliff is an argument for the tests: none, and at the table's size
    assert edges.word_slices(rows, w, cliff=0) == WHOLE(w)
    assert edges.word_slices(rows, w, cliff=rows) == TILES(w)


@pytest.mark.parametrize("compact", [True, False])
def test_a_wide_plane_crosses_in_two_gathers_and_one_scatter(compact):
    """The jaxpr of a planned net's gather: a 13-word plane beyond the
    cliff crosses in two big gathers of at most a tile of words, each out
    of a table of its own through the same indices, and ONE scatter of
    all 13; a 5-word plane, and a 13-word one under the cliff, in one."""
    topo = graph.random_connect(3000, d=4, seed=5)
    net = Net.build(topo, graph.subscribe_all(3000, 1))
    n, k = topo.nbr.shape
    tiers = edges.plan_tiers(np.asarray(net.edge_perm), topo.nbr_ok,
                             compact=compact)
    k0, n_pad = tiers.head.shape
    tail = int(topo.nbr_ok[:, k0:].sum())
    big_rows = n_pad * k0 + (tail if compact else 0)

    def big(w, tiers):
        x = jnp.zeros((n, k, w), jnp.uint32)
        eqns = jax.make_jaxpr(net.replace(tiers=tiers).edge_gather)(x).eqns
        (scatter,) = [e for e in eqns if e.primitive.name == "scatter"]
        assert scatter.invars[2].aval.shape == (tail, w)
        return [(e.invars[0].aval.shape, e.outvars[0].aval.shape[1])
                for e in eqns if e.primitive.name == "gather"
                and e.outvars[0].aval.shape[0] == big_rows]

    table = (tiers.table_rows(k),)
    assert big(5, tiers) == big(5, sliced(tiers, k)) == [(table + (5,), 5)]
    assert big(13, tiers) == [(table + (13,), 13)]
    assert big(13, sliced(tiers, k)) == [(table + (8,), 8), (table + (5,), 5)]


@pytest.mark.parametrize("name", sorted(TOPOS))
def test_plan_lives_in_the_compact_table(name):

    """Every index of the plan lies inside ``[0, K0*Np + T)``; the rows the
    table appends are exactly the present tail slots, each once, K-major;
    each index is the compact address of the slot's partner in
    ``edge_perm``; and a pad slot points at row 0."""
    topo = TOPOS[name]()
    perm = edges.build_edge_perm(topo.nbr, topo.rev, topo.nbr_ok)
    n, k = perm.shape
    n_pad = edges.lane_padded(n)
    assert n_pad % 128 == 0 and n <= n_pad < n + 128
    for k0 in every_k0(k):
        tiers = edges.plan_tiers(perm, topo.nbr_ok, k0, compact=True)
        dst = np.asarray(tiers.tail_dst)
        t = dst.size
        assert tiers.table_rows(k) == n_pad * k0 + t
        assert (np.diff(dst) > 0).all()         # sorted, unique
        present = np.zeros((k - k0) * n_pad, bool)
        present[dst] = True
        np.testing.assert_array_equal(
            present.reshape(k - k0, n_pad)[:, :n], topo.nbr_ok[:, k0:].T)
        assert not present.reshape(k - k0, n_pad)[:, n:].any()
        head = np.asarray(tiers.head)
        assert not head[:, n:].any()            # the pad slots: row 0
        src = np.concatenate(
            [head[:, :n].reshape(-1), np.asarray(tiers.tail_src)])
        assert src.min(initial=0) >= 0 and src.max(initial=0) < n_pad * k0 + t
        # the compact table's rows, named by the full-space slot (n*K + k)
        # they hold; a pad row holds none
        slot_of_row = np.full(n_pad * k0 + t, -1)
        slot_of_row[(np.arange(k0)[:, None] * n_pad
                     + np.arange(n)[None, :]).reshape(-1)] = (
            np.arange(n)[None, :] * k + np.arange(k0)[:, None]).reshape(-1)
        slot_of_row[n_pad * k0:] = (dst % n_pad) * k + k0 + dst // n_pad
        asked = np.concatenate([perm[:, :k0].T.reshape(-1),
                                perm.reshape(-1)[slot_of_row[n_pad * k0:]]])
        np.testing.assert_array_equal(slot_of_row[src], asked)


@pytest.mark.parametrize("compact", [True, False])
@pytest.mark.parametrize("name", sorted(TOPOS))
def test_rows_are_what_the_program_addresses(name, compact):
    """``Tiers.rows`` and ``table_rows`` against the traced program: the
    gathers' output rows plus the scatter's update rows, and the operand
    of the big gather. The full-table form keeps ``edge_perm``'s indices,
    re-addressed K-major."""
    topo = TOPOS[name]()
    perm = edges.build_edge_perm(topo.nbr, topo.rev, topo.nbr_ok)
    n, k = perm.shape
    n_pad = edges.lane_padded(n)
    x = planes((n, k))["u32[N,K,5]"]
    for k0 in every_k0(k):
        tiers = edges.plan_tiers(perm, topo.nbr_ok, k0, compact=compact)
        tail = int(topo.nbr_ok[:, k0:].sum())
        assert tiers.rows == n_pad * k0 + (3 if compact else 2) * tail
        jaxpr = jax.make_jaxpr(edges.edge_permute_tiered)(x, tiers)
        gathers = [e for e in jaxpr.eqns if e.primitive.name == "gather"]
        scatters = [e for e in jaxpr.eqns if e.primitive.name == "scatter"]
        assert len(scatters) == 1
        addressed = (sum(e.outvars[0].aval.shape[0] for e in gathers)
                     + sum(e.invars[2].aval.shape[0] for e in scatters))
        assert addressed == tiers.rows
        if compact:     # the table's appended rows first, then ONE gather
            short, big = gathers    # that moves the head and the tail
            assert big.outvars[0].aval.shape[0] == n_pad * k0 + tail
            want = n_pad * k0 + tail
        else:           # the tail's, then the head's, out of one table
            # (jax drops the head gather of no rows at K0 = 0)
            assert len(gathers) == 2 - (k0 == 0)
            short, big = gathers[0], gathers[-1]
            assert k0 == 0 or big.outvars[0].aval.shape[0] == n_pad * k0
            assert short.invars[0].aval.shape[0] == n_pad * k
            np.testing.assert_array_equal(
                np.asarray(tiers.head)[:, :n].T,
                (perm % k * n_pad + perm // k)[:, :k0])
            want = n_pad * k
        assert short.outvars[0].aval.shape[0] == tail
        assert big.invars[0].aval.shape[0] == tiers.table_rows(k) == want


def test_built_net_gathers_through_its_plan():
    """``Net.edge_gather`` of a net that ``build`` tiered, against the
    full gather through its ``edge_perm`` (which stays a field)."""
    topo = graph.random_connect(3000, d=4, seed=5)
    net = Net.build(topo, graph.subscribe_all(3000, 1))
    assert net.tiers is not None
    k0 = net.tiers.head.shape[0]
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
    k0, n_pad = net.tiers.head.shape
    assert n_pad == edges.lane_padded(n) > n
    tail = int(topo.nbr_ok[:, k0:].sum())
    assert not net.tiers.compact        # a table of 3000 * K rows is small
    compact = net.replace(tiers=edges.plan_tiers(
        np.asarray(net.edge_perm), topo.nbr_ok, compact=True))
    with edges.tally_index_rows(rows):
        jax.eval_shape(compact.edge_gather, x)
    # five words are one tile: a table's tile-rows are its rows
    assert rows == [("edge", n_pad * k0 + 2 * tail), ("table", n_pad * k),
                    ("tile_rows", n_pad * k),
                    ("edge", n * k), ("table", n * k), ("tile_rows", n * k),
                    ("peer", n * k),
                    ("edge", n_pad * k0 + 3 * tail),
                    ("table", n_pad * k0 + tail),
                    ("tile_rows", n_pad * k0 + tail)]
    assert net.tiers.rows == n_pad * k0 + 2 * tail < n * k
    assert edges.edge_table_rows(rows) == n_pad * k
    assert edges.edge_table_rows(rows[-3:]) == n_pad * k0 + tail < n * k
    assert edges.edge_table_rows(rows, "tile_rows") == n_pad * k
    # 13 words are two tiles, whichever gather reads them; sliced, every
    # slice gathers (and appends its rows), the scatter stays one, and
    # the tables are a tile wide
    wide = jnp.zeros((n, k, 13), jnp.uint32)
    rows.clear()
    with edges.tally_index_rows(rows):
        edges.mark_dispatch()
        jax.eval_shape(net.edge_gather, wide)
        jax.eval_shape(net.replace(tiers=None).edge_gather, wide)
        edges.mark_dispatch()
        for plan in (net.tiers, compact.tiers):
            jax.eval_shape(net.replace(tiers=sliced(plan, k)).edge_gather,
                           wide)
    assert rows == [
        ("dispatch", None),
        ("edge", n_pad * k0 + 2 * tail), ("table", n_pad * k),
        ("tile_rows", 2 * n_pad * k),
        ("edge", n * k), ("table", n * k), ("tile_rows", 2 * n * k),
        ("dispatch", None),
        ("edge", 2 * (n_pad * k0 + tail) + tail), ("table", n_pad * k),
        ("tile_rows", n_pad * k), ("sliced", 1),
        ("edge", 2 * (n_pad * k0 + 2 * tail) + tail),
        ("table", n_pad * k0 + tail), ("tile_rows", n_pad * k0 + tail),
        ("sliced", 1)]
    assert edges.edge_table_rows(rows, "tile_rows") == 2 * n_pad * k
    assert edges.edge_rows_per_dispatch(rows[:7], "sliced") == 0.0
    assert edges.edge_rows_per_dispatch(rows[7:], "sliced") == 2.0
    # a tiered gather is still ONE gather set (hlo-audit, cost model)
    assert sets == ["edge", "edge", "peer"]
    banded = Net.build(graph.ring_lattice(64, d=4), graph.subscribe_all(64, 1))
    rows.clear()
    with edges.tally_index_rows(rows):
        jax.eval_shape(banded.edge_gather, jnp.zeros((64, 8, 2), jnp.uint32))
        jax.eval_shape(banded.peer_gather, jnp.zeros((64,), jnp.uint32))
    assert rows == [("edge", 0), ("peer", 0)]   # rolls address no row
    assert edges.edge_table_rows(rows) is None  # and read no table
    assert edges.edge_table_rows(rows, "tile_rows") is None


def compact_pays(col_fill, n, k0):
    """The table rule, spelt out: the full table lies beyond the cliff,
    the compact one this side of it, both at their padded size."""
    n_pad = edges.lane_padded(n)
    return (n_pad * k0 + int(np.sum(col_fill[k0:]))
            <= edges.TABLE_CLIFF_ROWS < n_pad * len(col_fill))


def cost(col_fill, n, k0):
    """The cost rule, spelt out: head rows at their table's price, tail
    rows, the fixed cost."""
    tail = int(np.sum(col_fill[k0:]))
    fixed = edges.TIER_FIXED_NS if k0 < len(col_fill) else 0.0
    head = (edges.COMPACT_HEAD_ROW_NS if compact_pays(col_fill, n, k0)
            else edges.HEAD_ROW_NS)
    return n * k0 * head + tail * edges.TAIL_ROW_NS + fixed


@pytest.mark.parametrize("col_fill,n,why", [
    ([1000] * 8, 1000, "every column full: K0 = K"),
    ([1000] * 8 + [999], 1000, "one slot short of full: K0 = K"),
    ([1000, 1000, 1000, 600, 200, 40, 3, 1], 1000, "a thinning tail"),
    ([100_000] * 10 + [99_000, 90_000, 60_000, 30_000, 9_000, 2_000, 300,
                       40, 5, 1], 100_000, "random_connect's shape"),
    ([32, 1, 1, 1, 1, 1, 1, 1], 33, "a toy star: the fixed cost decides"),
    ([20_000] + [1] * 63, 20_000, "a star's hub"),
    ([0, 0, 0], 10, "no edge at all"),
    ([100_000] * 10 + [99_000, 90_000, 60_000, 30_000, 9_000, 2_000, 300,
                       40, 5, 1] + [0] * 21, 100_000,
     "random-100k's K = 41: a large table, the compact form"),
    ([100_000] * 36 + [90_000, 50_000, 9_000, 300, 5] + [0] * 24, 100_000,
     "eth2-100k's shape: the compact table would lie beyond the cliff"),
    ([10_000] * 10 + [9_900, 9_000, 6_000, 3_000, 900, 200, 30, 4] + [0] * 18,
     10_000, "random-10k's shape: a small table, the full form"),
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
        assert plan.head.shape == (k0, edges.lane_padded(n))
        assert plan.compact == compact_pays(col_fill, n, k0)


@pytest.mark.parametrize("form", ["compact", "tiered", "full", "rolls",
                                  "sliced", "sliced-compact"])
def test_a_traced_window_notes_the_table_its_gathers_read(form):
    """``driver``'s windows keep ``edge_table_rows`` beside the rows
    addressed, how a reader of a traced run sees that the compact table
    engaged, and the same in tile-rows beside the sliced calls of a step:
    how it sees that a wide plane crossed in slices (the window's plane
    is 9 words, two tiles)."""
    from go_libp2p_pubsub_tpu import driver
    from go_libp2p_pubsub_tpu.perf import stages

    tiles, sliced_calls = 2, 0.0
    if form == "rolls":
        topo = graph.ring_lattice(64, d=4)
        net = Net.build(topo, graph.subscribe_all(64, 1))
        want = None
    else:
        topo = graph.random_connect(3000, d=4, seed=5)
        net = Net.build(topo, graph.subscribe_all(3000, 1))
        n, k = topo.nbr.shape
        tiers = edges.plan_tiers(np.asarray(net.edge_perm), topo.nbr_ok,
                                 compact=True)
        k0, n_pad = tiers.head.shape
        assert tiers.table_rows(k) == int(
            n_pad * k0 + topo.nbr_ok[:, k0:].sum()) < n * k
        want = {"compact": tiers.table_rows(k), "tiered": n_pad * k,
                "full": n * k, "sliced": n_pad * k,
                "sliced-compact": tiers.table_rows(k)}[form]
        net = net.replace(tiers={
            "compact": tiers, "tiered": net.tiers, "full": None,
            "sliced": sliced(net.tiers, k),
            "sliced-compact": sliced(tiers, k)}[form])
        if form.startswith("sliced"):
            tiles, sliced_calls = 1, 1.0

    def step(st, x):
        return net.edge_gather(st) ^ x

    win = driver.make_window(step, donate=False)
    x = jnp.zeros(topo.nbr.shape + (9,), jnp.uint32)
    jax.eval_shape(win, x, (jnp.stack([x, x]),))
    (entry,) = [w for w in stages.traced_windows() if w.jitted is win]
    assert entry.edge_table_rows == want
    assert entry.edge_table_tile_rows == (want and tiles * want)
    assert entry.edge_sliced_calls_per_dispatch == sliced_calls
