"""The fused composites (round 21, docs/DESIGN.md §21): the XLA forms
behind ``cfg.fused`` / ``Net.fused``.

Pins the §21 contracts:

  * what ``models/common.delivery_round`` commits on a CSR-resident
    ``fused=True`` net equals a piecewise reference of the flat chain
    (peer/edge/owner gathers + ops/csr.segment_or_scan + the commit
    algebra) on ragged, banded and power-law topologies, link-deny
    masks on and off;
  * the fused composite pieces are exact recompositions: the
    capacity-bounded segmented scan equals the log2(E)
    associative_scan form on random ragged segments, and the
    sort-composite rank equals the pairwise count — ties, signed
    zeros, masks, keyed and unkeyed;
  * fused-vs-unfused FULL STATE TREES are bit-exact for all four
    engines (gossipsub, gossipsub_phase r∈{1,8}, floodsub, randomsub).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

from go_libp2p_pubsub_tpu import graph, topo
from go_libp2p_pubsub_tpu.models import common
from go_libp2p_pubsub_tpu.models.floodsub import floodsub_step
from go_libp2p_pubsub_tpu.models.randomsub import make_randomsub_step
from go_libp2p_pubsub_tpu.ops import bitset
from go_libp2p_pubsub_tpu.ops import csr as csrops
from go_libp2p_pubsub_tpu.ops import select
from go_libp2p_pubsub_tpu.state import Delivery, MsgTable, Net, SimState

M = 32
W = bitset.n_words(M)


# ---------------------------------------------------------------------------
# topologies: ragged (uneven real degrees), banded, power-law


def _net(kind: str) -> Net:
    if kind == "ragged":
        t = graph.random_connect(96, d=4, seed=2)
        subs = graph.subscribe_all(96, 1)
        return Net.build(t, subs, edge_layout="csr", fused=True)
    if kind == "banded":
        t = graph.ring_lattice(64, d=8)
        subs = graph.subscribe_all(64, 1)
        return Net.build(t, subs, edge_layout="csr", fused=True)
    if kind == "powerlaw":
        el = topo.powerlaw(128, exponent=2.2, d_min=2, max_degree=16,
                           seed=0)
        subs = graph.subscribe_all(128, 1)
        _t, _net_d, net_c = topo.build_nets(el, subs, max_degree=16)
        return Net.build(_t, subs, edge_layout="csr", fused=True)
    raise ValueError(kind)


def _rand_planes(net: Net, rng):
    """Arbitrary word planes — the commit is pure bit algebra, so it
    must hold for ANY inputs, not just reachable states."""
    n, k = net.nbr.shape
    e = net.n_edges
    u32 = lambda shape: jnp.asarray(
        rng.integers(0, 1 << 32, size=shape, dtype=np.uint32))
    return {
        "fwd": u32((n, W)),
        "fe_e": u32((e, W)),
        "edge_mask": u32((n, k, W)),
        "not_mine": u32((n, W)),
        "have": u32((n, W)),
        "first_round": jnp.asarray(
            rng.integers(-1, 50, size=(n, M)), jnp.int32),
        "valid": jnp.asarray(rng.random(M) < 0.8),
    }


def _composite_reference(net: Net, p: dict, tick, link_ok_e=None):
    """The flat chain, piecewise (the ops models/common.delivery_round
    + finish_delivery_flat compose)."""
    fwd_e = net.peer_gather_flat(p["fwd"])
    echo_e = net.edge_gather_flat(p["fe_e"])
    mask_e = net.pack_edges(p["edge_mask"])
    nm_e = net.owner_gather(p["not_mine"])
    trans_e = fwd_e & ~echo_e & mask_e & nm_e
    if link_ok_e is not None:
        trans_e = trans_e & jnp.where(
            link_ok_e[:, None], jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    inc, exc = csrops.segment_or_scan(trans_e, net.csr_seg_start,
                                      cap=net.max_degree)
    recv = jnp.where(net.csr_row_nonempty[:, None],
                     inc[jnp.clip(net.csr_row_last, 0)], jnp.uint32(0))
    new = recv & ~p["have"]
    new_e = net.owner_gather(new)
    fa_e = trans_e & ~exc & new_e
    valid_words = bitset.pack(p["valid"])
    first_round = jnp.where(bitset.unpack(new, M), tick,
                            p["first_round"])
    return {
        "trans_e": trans_e,
        "recv": recv,
        "new": new,
        "have": p["have"] | new,
        "fwd": new & valid_words[None, :],
        "first_round": first_round,
        "fe": (p["fe_e"] & ~new_e) | fa_e,
        "fa_e": fa_e,
    }


@pytest.mark.parametrize("kind", ["ragged", "banded", "powerlaw"])
@pytest.mark.parametrize("chaos", [False, True])
def test_csr_delivery_commit_matches_reference(kind, chaos):
    net = _net(kind)
    n, k = net.nbr.shape
    rng = np.random.default_rng(
        {"ragged": 1, "banded": 2, "powerlaw": 3}[kind] * 2 + int(chaos))
    for trial in range(2):
        p = _rand_planes(net, rng)
        # not-mine is the one plane delivery_round derives itself: one
        # origin (or none) a slot, read back through the same helper
        origin = jnp.asarray(rng.integers(-1, n, size=(M,)), jnp.int32)
        msgs = MsgTable.empty(M).replace(origin=origin, valid=p["valid"])
        p["not_mine"] = ~common.origin_msg_words(net, msgs)
        edge_mask, link_ok_e = p["edge_mask"], None
        if chaos:
            # a link-deny plane enters the round as an AND into the
            # [N, K, W] edge mask; the reference takes it flat
            link_ok = jnp.asarray(rng.random((n, k)) < 0.7)
            link_ok_e = net.pack_edges(link_ok)
            edge_mask = jnp.where(link_ok[:, :, None], edge_mask,
                                  jnp.uint32(0))
        tick = jnp.int32(7 + trial)
        want = _composite_reference(net, p, tick, link_ok_e)
        dlv = Delivery(have=p["have"], fwd=p["fwd"],
                       first_round=p["first_round"], fe_words=p["fe_e"])
        got, info = common.delivery_round(net, msgs, dlv, edge_mask, tick)
        for key, arr in [("have", got.have), ("fwd", got.fwd),
                         ("first_round", got.first_round),
                         ("fe", got.fe_words), ("trans_e", info.trans),
                         ("new", info.new_words)]:
            np.testing.assert_array_equal(
                np.asarray(arr), np.asarray(want[key]),
                err_msg=f"{kind} chaos={chaos} trial={trial} {key}")


# ---------------------------------------------------------------------------
# the fused composite pieces are exact recompositions


def test_bounded_scan_equals_associative_scan():
    rng = np.random.default_rng(11)
    for trial in range(10):
        e = int(rng.integers(8, 200))
        cap = int(rng.integers(1, 20))
        # random ragged segments, each no longer than cap
        flags = np.zeros(e, bool)
        i = 0
        while i < e:
            flags[i] = True
            i += int(rng.integers(1, cap + 1))
        x = jnp.asarray(rng.integers(0, 1 << 32, size=(e, 2),
                                     dtype=np.uint32))
        f = jnp.asarray(flags)
        inc_a, exc_a = csrops.segment_or_scan(x, f, cap=None)
        inc_b, exc_b = csrops.segment_or_scan(x, f, cap=cap)
        np.testing.assert_array_equal(np.asarray(inc_a),
                                      np.asarray(inc_b))
        np.testing.assert_array_equal(np.asarray(exc_a),
                                      np.asarray(exc_b))


def test_sorted_rank_equals_pairwise():
    rng = np.random.default_rng(13)
    for trial in range(10):
        r, k = int(rng.integers(1, 20)), int(rng.integers(1, 24))
        # quantized + signed zeros: the tie/total-order hazards
        values = rng.integers(-2, 3, size=(r, k)).astype(np.float32)
        values[rng.random((r, k)) < 0.2] = -0.0
        mask = rng.random((r, k)) < 0.6
        key = (jax.random.key(trial) if trial % 2 == 0 else None)
        a = select.rank_desc(jnp.asarray(values), jnp.asarray(mask),
                             key, fused=False)
        b = select.rank_desc(jnp.asarray(values), jnp.asarray(mask),
                             key, fused=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_selection_kernels_fused_parity():
    rng = np.random.default_rng(17)
    values = jnp.asarray(rng.integers(0, 5, size=(32, 16))
                         .astype(np.float32))
    mask = jnp.asarray(rng.random((32, 16)) < 0.7)
    width = jnp.asarray(rng.integers(0, 20, size=(32,)), jnp.int32)
    key = jax.random.key(3)
    for a, b in [
        (select.select_topk_mask(values, mask, 6, key),
         select.select_topk_mask(values, mask, 6, key, fused=True)),
        (select.select_random_mask(key, mask, 4),
         select.select_random_mask(key, mask, 4, fused=True)),
        (select.masked_width_topk(values, mask, width, 16, key),
         select.masked_width_topk(values, mask, width, 16, key,
                                  fused=True)),
        (select.masked_width_random(key, mask, width, 16),
         select.masked_width_random(key, mask, width, 16, fused=True)),
    ]:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# engine-level fused-vs-unfused parity: full state trees, four engines


def _tree_equal(a, b):
    la, lb = jtu.tree_leaves(a), jtu.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        if jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
            x, y = jax.random.key_data(x), jax.random.key_data(y)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _run_bench(fused, rounds_per_phase=1, steps=4, n=96):
    from go_libp2p_pubsub_tpu.perf.sweep import build_bench

    st, step, _, _ = build_bench(n, M, rounds_per_phase=rounds_per_phase,
                                 heartbeat_every=max(rounds_per_phase, 1),
                                 edge_layout="csr", fused=fused)
    rng = np.random.default_rng(0)
    for t in range(steps):
        if rounds_per_phase > 1:
            r = rounds_per_phase
            po = jnp.asarray(rng.integers(0, n, size=(r, 2)), jnp.int32)
            st = step(st, po, jnp.zeros((r, 2), jnp.int32),
                      jnp.ones((r, 2), bool), do_heartbeat=True)
        else:
            po = jnp.asarray(rng.integers(0, n, size=(2,)), jnp.int32)
            st = step(st, po, jnp.zeros((2,), jnp.int32),
                      jnp.ones((2,), bool))
    return st


def test_gossipsub_fused_parity():
    _tree_equal(_run_bench(False), _run_bench(True))


def test_phase_fused_parity_r1():
    # r=1 phase engine: the degenerate single-sub-round phase dispatch
    _tree_equal(_run_bench(False, rounds_per_phase=1),
                _run_bench(True, rounds_per_phase=1))


@pytest.mark.slow
def test_phase_fused_parity_r8():
    _tree_equal(_run_bench(False, rounds_per_phase=8, steps=3),
                _run_bench(True, rounds_per_phase=8, steps=3))


@pytest.mark.parametrize("engine", ["floodsub", "randomsub"])
def test_factoryless_engines_fused_parity(engine):
    t = graph.ring_lattice(96, d=8)
    subs = graph.subscribe_all(96, 1)

    def run(fused):
        net = Net.build(t, subs, edge_layout="csr", fused=fused)
        st = SimState.init(96, M, seed=0, k=net.max_degree,
                           n_edges=net.n_edges)
        if engine == "floodsub":
            step = lambda s, *a: floodsub_step.__wrapped__(net, s, *a)
        else:
            step = make_randomsub_step(net)
        rng = np.random.default_rng(1)
        for t_ in range(4):
            po = jnp.asarray(rng.integers(0, 96, size=(2,)), jnp.int32)
            st = step(st, po, jnp.zeros((2,), jnp.int32),
                      jnp.ones((2,), bool))
        return st

    _tree_equal(run(False), run(True))


def test_cfg_net_fused_mismatch_raises():
    from go_libp2p_pubsub_tpu.config import (
        GossipSubParams,
        PeerScoreThresholds,
    )
    from go_libp2p_pubsub_tpu.models.gossipsub import (
        GossipSubConfig,
        prepare_step_consts,
    )

    t = graph.ring_lattice(64, d=8)
    subs = graph.subscribe_all(64, 1)
    net = Net.build(t, subs, edge_layout="csr", fused=True)
    cfg = GossipSubConfig.build(
        GossipSubParams(), PeerScoreThresholds(), edge_layout="csr",
        fused=False,
    )
    with pytest.raises(ValueError, match="fused"):
        prepare_step_consts(cfg, net, None, 1.0, None, None, None)
