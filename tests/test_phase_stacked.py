"""Stacked/coalesced vs legacy parity suite (round-7 tentpole).

The round-7 data-plane restructuring — the coalesced wire exchange, the
attribution accumulators of _AccStack (the [N, W] planes as lanes of one
stacked tensor, each [N, K, W] plane in a buffer of its own), the
phase-head publish plan (state.PhasePubPlan), and the stacked
recycled-slot clears in allocate_publishes — claims BIT-IDENTICAL
semantics to the legacy per-plane path. This suite is that claim's
oracle: every router (gossipsub phase engine, floodsub, randomsub, the
per-round gossipsub step) is run on both paths over the same schedule
and the FULL state trees compared, at r ∈ {1, 8, 16} for the phase
engine and across the feature matrix (gater + validation throttle +
queue_cap + adversary, async validation + per-topic delays + exact
trace, wide topic universes (non-incremental membership planes),
dynamic peers).

The phase engine's legacy path additionally stays pinned to the
per-round step through the existing r=1 suite (tests/test_phase.py runs
the DEFAULT — coalesced — path against the per-round oracle), so the
chain per-round == phase(r=1, coalesced) == phase(r=1, legacy) closes.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from go_libp2p_pubsub_tpu import graph
from go_libp2p_pubsub_tpu.config import PeerGaterParams
from go_libp2p_pubsub_tpu.models.floodsub import floodsub_step
from go_libp2p_pubsub_tpu.models.gossipsub import make_gossipsub_step
from go_libp2p_pubsub_tpu.models.gossipsub_phase import (
    _AccStack,
    make_gossipsub_phase_step,
)
from go_libp2p_pubsub_tpu.models.randomsub import make_randomsub_step
from go_libp2p_pubsub_tpu.state import Net, PhasePubPlan, SimState, allocate_publishes

from test_phase import N, P, assert_states_equal, build, run_phase, schedule

M = 64


def _ab_phase(r, rounds=16, seed=3, codes=True, n=N, sched_seed=None,
              dynamic=False, **cfg_kw):
    """Run the phase engine stacked (wire_coalesced=True, the default)
    and legacy over one schedule; return both final states."""
    outs = []
    po, pt, pv = schedule(rounds, seed=sched_seed or seed, n=n, codes=codes)
    ups = None
    if dynamic:
        rng = np.random.default_rng(seed)
        ups = rng.random((rounds // r, n)) > 0.05
    for coalesced in (True, False):
        net, cfg, sp, st = build(seed=seed, n=n, **cfg_kw)
        cfg = dataclasses.replace(cfg, wire_coalesced=coalesced)
        pstep = make_gossipsub_phase_step(
            cfg, net, r, score_params=sp,
            gater_params=cfg_kw.get("gater_params"),
            dynamic_peers=dynamic,
        )
        if dynamic:
            g = po.shape[0] // r
            for p in range(g):
                st = pstep(st, po[p * r:(p + 1) * r], pt[p * r:(p + 1) * r],
                           pv[p * r:(p + 1) * r], jnp.asarray(ups[p]),
                           do_heartbeat=True)
        else:
            st = run_phase(pstep, st, po, pt, pv, r)
        outs.append(st)
    return outs


@pytest.mark.parametrize("r", [1, 8])
def test_phase_stacked_vs_legacy_bitexact(r):
    """Rich v1.1 config (score + flood_publish + PX + fanout + mixed
    verdicts): full state trees bit-identical across the A/B paths."""
    sa, sb = _ab_phase(r)
    assert_states_equal(sa, sb, f"stacked-r{r}/")


@pytest.mark.slow
def test_phase_stacked_vs_legacy_bitexact_r16():
    sa, sb = _ab_phase(16, rounds=32)
    assert_states_equal(sa, sb, "stacked-r16/")


@pytest.mark.slow
def test_phase_stacked_vs_legacy_gater_throttle_queuecap():
    """The gater accumulator lanes + validation throttle + lossy queue:
    the stacked [N,K,W] dup/rejw/ignw lanes and the throttle's accepted
    lane must fold identically."""
    sa, sb = _ab_phase(
        4, rounds=12, seed=7,
        gater_params=PeerGaterParams(), validation_capacity=3, queue_cap=3,
    )
    assert_states_equal(sa, sb, "stacked-gater/")


@pytest.mark.slow
def test_phase_stacked_vs_legacy_validation_delay_trace_exact():
    """Async validation pipeline (per-topic delays) + the exact-trace dup
    lane — the one NON-keep-masked lane of the stack."""
    sa, sb = _ab_phase(
        4, rounds=12, seed=11,
        validation_delay_rounds=2, validation_delay_topic=(1, 2, 1),
        trace_exact=True,
    )
    assert_states_equal(sa, sb, "stacked-valdelay/")


def test_phase_stacked_vs_legacy_every_k_wide_plane():
    """All five K-wide attribution planes live at once (the suite's P3
    and P4 weights: ``mcw`` / ``trans``; the gater: ``dup`` / ``rejw`` /
    ``ignw``) plus the exact-trace ``dupt``, each folded in a buffer of
    its own on the stacked side, at r = 8 with invalid and ignored
    publishes in the schedule and 8 x 4 publishes a phase into M = 64
    slots: from the third phase on every phase recycles slots."""
    sa, sb = _ab_phase(8, rounds=48, seed=17,
                       gater_params=PeerGaterParams(), trace_exact=True)
    for name, plane in (("imd", sa.score.imd), ("reject", sa.gater.reject),
                        ("ignore", sa.gater.ignore),
                        ("duplicate", sa.gater.duplicate),
                        ("dup_trans", sa.dup_trans)):
        assert bool(jnp.any(plane != 0)), f"{name} is idle: nothing compared"
    assert_states_equal(sa, sb, "stacked-kwide/")


@pytest.mark.parametrize("wide", [False, True])
def test_acc_stack_folds_as_the_legacy_planes(wide):
    """_AccStack alone: after the same or_ / keep sequence, get(name) of
    the stacked form (the [N, W] planes lanes of one buffer a part, each
    [N, K, W] plane its own) equals the legacy per-plane arrays, for
    masked and unmasked planes of both shapes; a window with no K-wide
    plane (wide off) holds no array outside the stacks."""
    n, k, w = 12, 5, 3
    specs = [("a", 1, True, None), ("b", 1, False, None),
             ("c", 1, True, "gater")]
    if wide:
        specs += [("t", k, True, "attrib"), ("d", k, True, "gater"),
                  ("u", k, False, None)]
    rng = np.random.default_rng(int(wide))
    word = lambda *shape: jnp.asarray(
        rng.integers(0, 2**32, size=shape, dtype=np.uint32))
    both = [_AccStack(specs, n, w, stacked=s) for s in (True, False)]
    assert set(both[0].planes) == {nm for nm, ln, _, _ in specs if ln > 1}
    assert {nm for g in both[0].groups.values() for nm, _ in g} == {
        nm for nm, ln, _, _ in specs if ln == 1}
    assert not both[1].bufs
    for _ in range(3):
        upd = {nm: word(n, w) if ln == 1 else word(n, ln, w)
               for nm, ln, _, _ in specs}
        keep_w = word(w)
        both = [acc.or_(upd).keep(keep_w) for acc in both]
    for nm, ln, masked, _ in specs:
        got, want = (np.asarray(acc.get(nm)) for acc in both)
        assert got.shape == ((n, w) if ln == 1 else (n, ln, w))
        np.testing.assert_array_equal(got, want, err_msg=nm)
        assert nm in both[0] and nm in both[1]
    assert "absent" not in both[0] and both[0].get("absent", 7) == 7


@pytest.mark.slow
def test_phase_stacked_vs_legacy_dynamic_peers():
    sa, sb = _ab_phase(4, rounds=12, seed=13, codes=False, dynamic=True)
    assert_states_equal(sa, sb, "stacked-dyn/")


def test_phase_stacked_vs_legacy_wide_topics():
    """T > 8 disables the incremental membership planes: the coalesced
    path's per-sub-round recompute must read the plan's table snapshots
    bit-identically (the non-incr branch of the loop)."""
    n, t = 48, 12
    outs = []
    rng = np.random.default_rng(5)
    po = jnp.asarray(rng.integers(0, n, size=(8, P)).astype(np.int32))
    pt = jnp.asarray(rng.integers(0, t, size=(8, P)).astype(np.int32))
    pv = jnp.asarray(np.ones((8, P), bool))
    for coalesced in (True, False):
        topo = graph.random_connect(n, 8, seed=5)
        subs = graph.subscribe_random(n, n_topics=t, topics_per_peer=3, seed=5)
        net = Net.build(topo, subs)
        from test_phase import score_params
        sp = score_params(n_topics=t)
        from go_libp2p_pubsub_tpu.config import (
            GossipSubParams,
            PeerScoreThresholds,
        )
        from go_libp2p_pubsub_tpu.models.gossipsub import GossipSubConfig

        cfg = GossipSubConfig.build(
            GossipSubParams(), PeerScoreThresholds(), score_enabled=True,
            wire_coalesced=coalesced,
        )
        from go_libp2p_pubsub_tpu.models.gossipsub import GossipSubState

        st = GossipSubState.init(net, M, cfg, score_params=sp, seed=5)
        pstep = make_gossipsub_phase_step(cfg, net, 4, score_params=sp)
        st = run_phase(pstep, st, po, pt, pv, 4)
        outs.append(st)
    assert_states_equal(outs[0], outs[1], "stacked-wide/")


def test_phase_pub_plan_matches_sequential_allocate():
    """PhasePubPlan's last-write-wins snapshots == r sequential
    allocate_publishes calls, bit for bit — including HEAVY slot
    recycling (r·P >> M) and REJECT/IGNORE verdict codes."""
    n, m, r, p = 16, 8, 6, 4  # 24 publishes into 8 slots: 3x recycled
    rng = np.random.default_rng(0)
    po = rng.integers(0, n, size=(r, p)).astype(np.int32)
    po[rng.random((r, p)) < 0.3] = -1  # pads
    pt = rng.integers(0, 3, size=(r, p)).astype(np.int32)
    pv = rng.choice([0, 0, 0, 1, 2], size=(r, p)).astype(np.int32)
    st = SimState.init(n, m, seed=0, k=4)
    msgs, dlv = st.msgs, st.dlv
    # non-trivial initial table so untouched slots must survive
    msgs = msgs.replace(
        topic=jnp.arange(m, dtype=jnp.int32) % 3,
        origin=jnp.arange(m, dtype=jnp.int32) % n,
        valid=jnp.asarray(np.arange(m) % 2 == 0),
    )
    plan = PhasePubPlan(msgs, n, st.tick, jnp.asarray(po), jnp.asarray(pt),
                        jnp.asarray(pv))
    for i in range(r):
        snap = plan.msgs_at(i)
        for f in ("topic", "origin", "birth", "valid", "ignored", "cursor"):
            np.testing.assert_array_equal(
                np.asarray(getattr(snap, f)), np.asarray(getattr(msgs, f)),
                err_msg=f"snapshot[{i}].{f}",
            )
        msgs, dlv, slots, is_pub, keep_w, pub_words = allocate_publishes(
            msgs, dlv, st.tick + i, jnp.asarray(po[i]), jnp.asarray(pt[i]),
            jnp.asarray(pv[i]),
        )
        np.testing.assert_array_equal(
            np.asarray(plan.keep_w[i]), np.asarray(keep_w), err_msg=f"keep[{i}]")
        np.testing.assert_array_equal(
            np.asarray(plan.pub_words[i]), np.asarray(pub_words),
            err_msg=f"pub_words[{i}]")
        got = np.asarray(plan.sidx[i])[np.asarray(is_pub)]
        np.testing.assert_array_equal(
            got, np.asarray(slots)[np.asarray(is_pub)], err_msg=f"slots[{i}]")
    final = plan.msgs_at(r)
    for f in ("topic", "origin", "birth", "valid", "ignored", "cursor"):
        np.testing.assert_array_equal(
            np.asarray(getattr(final, f)), np.asarray(getattr(msgs, f)),
            err_msg=f"final.{f}",
        )


def _sim_net(seed=1, n=32):
    topo = graph.random_connect(n, 6, seed=seed)
    subs = graph.subscribe_random(n, n_topics=2, topics_per_peer=1, seed=seed)
    return Net.build(topo, subs)


@pytest.mark.parametrize("queue_cap,val_delay", [(0, 0), (2, 2)])
def test_floodsub_stacked_vs_legacy(queue_cap, val_delay):
    """Floodsub shares allocate_publishes' stacked clears: state trees
    bit-identical with them on vs off (incl. pipeline + lossy queue)."""
    n = 32
    net = _sim_net()
    rng = np.random.default_rng(2)
    po_all = rng.integers(0, n, size=(10, 2)).astype(np.int32)
    po_all[6:] = -1  # drain tail
    outs = []
    for stacked in (True, False):
        st = SimState.init(n, 16, seed=2, k=net.max_degree,
                           val_delay=val_delay)
        for i in range(10):
            st = floodsub_step(
                net, st, jnp.asarray(po_all[i]),
                jnp.asarray(np.full((2,), i % 2, np.int32)),
                jnp.asarray(np.ones((2,), bool)),
                queue_cap=queue_cap, stacked=stacked,
            )
        outs.append(st)
    assert_states_equal(outs[0], outs[1], "flood-stacked/")


def test_randomsub_stacked_vs_legacy():
    n = 32
    net = _sim_net(seed=3)
    rng = np.random.default_rng(4)
    po_all = rng.integers(0, n, size=(10, 2)).astype(np.int32)
    outs = []
    for stacked in (True, False):
        step = make_randomsub_step(net, stacked=stacked)
        st = SimState.init(n, 16, seed=4, k=net.max_degree)
        for i in range(10):
            st = step(st, jnp.asarray(po_all[i]),
                      jnp.asarray(np.full((2,), i % 2, np.int32)),
                      jnp.asarray(np.ones((2,), bool)))
        outs.append(st)
    assert_states_equal(outs[0], outs[1], "randomsub-stacked/")


def test_per_round_gossipsub_stacked_vs_legacy():
    """The per-round step's stacked clears (allocate_publishes + the
    iwant/served tail fold) A/B via cfg.wire_coalesced."""
    outs = []
    po, pt, pv = schedule(10, seed=9, codes=True)
    for coalesced in (True, False):
        net, cfg, sp, st = build(seed=9)
        cfg = dataclasses.replace(cfg, wire_coalesced=coalesced)
        step = make_gossipsub_step(cfg, net, score_params=sp)
        for i in range(10):
            st = step(st, po[i], pt[i], pv[i])
        outs.append(st)
    assert_states_equal(outs[0], outs[1], "per-round-stacked/")
