"""The tiered edge gather planned per shard (``ops/edges.plan_shards``) of a
``random_connect`` net whose peer axis is split over a 1-D ``peers`` mesh:
each shard's tiered gather reads its own table with the rows the other
shards send it appended, all of them in ONE exchange (S - 1 permutes by
shard offset), and the result is ``edge_permute(x, edge_perm)`` bit for bit
on every slot. On 2 and 4 of the suite's host devices; counts, no device
metric."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from go_libp2p_pubsub_tpu import graph
from go_libp2p_pubsub_tpu.ops import edges
from go_libp2p_pubsub_tpu.parallel import collective_profile, make_mesh
from go_libp2p_pubsub_tpu.state import Net

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs the virtual-device CPU harness")


def _graph(n: int):
    topo = graph.random_connect(n, d=6, seed=3)
    return topo, edges.build_edge_perm(topo.nbr, topo.rev, topo.nbr_ok)


def _plane(shape):
    return np.random.default_rng(45).integers(0, 2**32, shape,
                                              dtype=np.uint32)


#: (peers, shards, K0, compact, trailing axes): the full and the compact
#: table, K0 < K and K0 = K ("K"), a one-word, a 5-word, a 13-word and an
#: [N, K, 2] plane, shards of 64, 128, 250 (not a whole number of lanes),
#: 384 and 512 peers
CASES = {
    "auto_full_5w": (256, 2, None, None, (5,)),
    "compact_5w": (512, 4, 3, True, (5,)),
    "full_1w_250_a_shard": (1000, 4, 3, False, ()),
    "k0_is_k_nk2": (2048, 4, "K", None, (2,)),
    "compact_13w_2_shards": (768, 2, 2, True, (13,)),
}


@pytest.mark.parametrize("case", CASES)
def test_the_per_shard_gather_equals_edge_permute_bit_for_bit(case):
    n, shards, k0, compact, trail = CASES[case]
    topo, perm = _graph(n)
    k = perm.shape[1]
    plan = edges.plan_shards(perm, topo.nbr_ok, shards,
                             k0=k if k0 == "K" else k0, compact=compact)
    assert isinstance(plan, edges.ShardTiers) and plan.shards == shards
    want_k0 = {"K": k, None: plan.local().head.shape[0]}.get(k0, k0)
    assert plan.local().head.shape == (want_k0,
                                       edges.lane_padded(n // shards))
    if compact is not None:
        assert plan.compact == compact
    x = _plane((n, k) + trail)
    want = np.asarray(edges.edge_permute(jnp.asarray(x), jnp.asarray(perm)))
    placed = jax.device_put(jnp.asarray(x), NamedSharding(
        make_mesh(shards), PartitionSpec("peers")))
    fn = jax.jit(lambda a: edges.edge_permute_sharded(
        a, plan, edges.peer_mesh(a)))
    assert np.array_equal(np.asarray(fn(placed)), want)
    # one exchange: S - 1 permutes, and no table crosses whole
    prof = collective_profile(fn.lower(placed).compile().as_text())
    assert prof["collective-permute"] == shards - 1, prof
    assert sum(prof.values()) == shards - 1, prof


def test_a_wide_plane_slices_under_a_lowered_cliff():
    """A 13-word plane crosses in tile-wide slices where its table lies
    beyond the cliff and a slice's does not (``word_slices``), each slice's
    table with the received rows appended, and stays exact."""
    n, shards = 768, 4
    topo, perm = _graph(n)
    k = perm.shape[1]
    plain = edges.plan_shards(perm, topo.nbr_ok, shards, k0=3,
                              compact=False)
    table = (plain.local().table_rows(k) + (shards - 1) * plain.pad)
    plan = edges.plan_shards(perm, topo.nbr_ok, shards, k0=3, compact=False,
                             cliff=table)
    x = _plane((n, k, 13))
    placed = jax.device_put(jnp.asarray(x), NamedSharding(
        make_mesh(shards), PartitionSpec("peers")))
    rows: list = []
    with edges.tally_index_rows(rows):
        got = jax.jit(lambda a: edges.edge_permute_sharded(
            a, plan, edges.peer_mesh(a)))(placed)
    assert ("sliced", 1) in rows and ("halo", 1) in rows
    assert ("remote", (shards - 1) * plan.pad) in rows
    assert ("table", table) in rows
    want = edges.edge_permute(jnp.asarray(x), jnp.asarray(perm))
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [256, 2048])
def test_one_shard_is_plan_tiers(n):
    """With one shard the plan is today's ``Tiers`` (``None`` where K0 = K
    wins), leaf for leaf."""
    topo, perm = _graph(n)
    for k0 in (None, 3):
        got = edges.plan_shards(perm, topo.nbr_ok, 1, k0=k0)
        want = edges.plan_tiers(perm, topo.nbr_ok, k0=k0)
        assert (got is None) == (want is None)
        if want is not None:
            assert got.compact == want.compact
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
                assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_send_lists_are_the_remote_partners():
    """Every present slot whose partner lies on another shard is sent once,
    by the row of its shard's K-major table, in the block of the shard
    offset; the lists are padded to the longest."""
    n, shards = 1000, 4
    topo, perm = _graph(n)
    k, ns = perm.shape[1], n // shards
    plan = edges.plan_shards(perm, topo.nbr_ok, shards)
    n_pad = edges.lane_padded(ns)
    send = np.asarray(plan.send).reshape(shards, shards - 1, plan.pad)
    there = perm // k // ns
    for i in range(shards):
        for d in range(1, shards):
            j = (i + d) % shards
            rows = np.nonzero(topo.nbr_ok[i * ns:(i + 1) * ns]
                              & (there[i * ns:(i + 1) * ns] == j))
            want = np.sort(rows[1] * n_pad + rows[0])
            assert np.array_equal(send[i, d - 1, :want.size], want)
            assert not send[i, d - 1, want.size:].any()
    assert plan.pad == max(
        int((topo.nbr_ok[i * ns:(i + 1) * ns]
             & (there[i * ns:(i + 1) * ns] == j)).sum())
        for i in range(shards) for j in range(shards) if i != j)


def test_net_takes_the_per_shard_path_from_the_argument():
    """``Net.edge_gather`` plans per shard for the shard count its
    argument arrives split into, once, and keeps one device's ``Tiers``
    where it is not split; a dynamic net, whose ``edge_perm`` is traced,
    has no plan."""
    n = 512
    topo, perm = _graph(n)
    net = Net.build(topo, graph.subscribe_all(n, 1))
    k = perm.shape[1]
    x = _plane((n, k, 3))
    want = np.asarray(edges.edge_permute(jnp.asarray(x), jnp.asarray(perm)))
    fn = jax.jit(net.edge_gather)
    for shards in (2, 4):
        placed = jax.device_put(jnp.asarray(x), NamedSharding(
            make_mesh(shards), PartitionSpec("peers")))
        assert "sdy.manual_computation" in fn.lower(placed).as_text()
        assert np.array_equal(np.asarray(fn(placed)), want)
        assert net.shard_plans(shards) is net.shard_plans(shards)
    assert "sdy.manual_computation" not in fn.lower(jnp.asarray(x)).as_text()
    assert np.array_equal(np.asarray(fn(jnp.asarray(x))), want)
    dynamic = Net.build(topo, graph.subscribe_all(n, 1), dynamic=True)
    assert dynamic.shard_plans is None and net.shard_plans is not None
