"""Multi-round phase engine tests (models/gossipsub_phase.py).

The load-bearing guarantee: a phase step with rounds_per_phase=1 is the
per-round step — bit-exact across every state plane, for every feature
combination. That pins the phase engine's sender-side transmit
composition and accumulated attribution to the per-round semantics the
oracle-parity suite already validates, so r>1 runs differ only by the
designed r-round control latency.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from go_libp2p_pubsub_tpu import graph
from go_libp2p_pubsub_tpu.config import (
    GossipSubParams,
    PeerGaterParams,
    PeerScoreParams,
    PeerScoreThresholds,
    TopicScoreParams,
)
from go_libp2p_pubsub_tpu.driver import heartbeat_schedule, make_scan
from go_libp2p_pubsub_tpu.models.gossipsub import (
    GossipSubConfig,
    GossipSubState,
    make_gossipsub_step,
)
from go_libp2p_pubsub_tpu.models.gossipsub_phase import make_gossipsub_phase_step
from go_libp2p_pubsub_tpu.ops import bitset
from go_libp2p_pubsub_tpu.state import Net

N, D, T, M, P = 48, 8, 3, 64, 4


def score_params(n_topics=T):
    tp = TopicScoreParams(
        mesh_message_deliveries_weight=-0.3,
        mesh_message_deliveries_threshold=3.0,
        mesh_message_deliveries_activation=6.0,
        mesh_message_deliveries_window=2.0,
    )
    return PeerScoreParams(
        topics={t: tp for t in range(n_topics)},
        skip_app_specific=True,
        behaviour_penalty_weight=-1.0,
        behaviour_penalty_threshold=1.0,
        behaviour_penalty_decay=0.9,
    )


def build(seed=0, he=1, n=N, **cfg_kw):
    topo = graph.random_connect(n, D, seed=seed)
    subs = graph.subscribe_random(n, n_topics=T, topics_per_peer=2, seed=seed)
    net = Net.build(topo, subs)
    sp = score_params()
    params = dataclasses.replace(
        GossipSubParams(), flood_publish=True, do_px=True
    )
    cfg = GossipSubConfig.build(
        params, PeerScoreThresholds(), score_enabled=True,
        heartbeat_every=he, **cfg_kw,
    )
    st = GossipSubState.init(net, M, cfg, score_params=sp, seed=seed)
    return net, cfg, sp, st


def schedule(rounds, seed=0, n=N, codes=False):
    """[R,P] publish schedule; with codes=True a few REJECT/IGNORE verdicts."""
    rng = np.random.default_rng(seed)
    po = rng.integers(0, n, size=(rounds, P)).astype(np.int32)
    pt = rng.integers(0, T, size=(rounds, P)).astype(np.int32)
    if codes:
        pv = rng.choice([0, 0, 0, 0, 0, 1, 2], size=(rounds, P)).astype(np.int32)
    else:
        pv = np.ones((rounds, P), bool)
    return jnp.asarray(po), jnp.asarray(pt), jnp.asarray(pv)


def assert_states_equal(a, b, what=""):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, _ = jax.tree_util.tree_flatten(b)
    paths = jax.tree_util.tree_flatten_with_path(a)[0]
    for (path, xa), xb in zip(paths, lb):
        if jnp.issubdtype(getattr(xa, "dtype", None), jax.dtypes.prng_key):
            xa, xb = jax.random.key_data(xa), jax.random.key_data(xb)
        xa, xb = np.asarray(xa), np.asarray(xb)
        name = jax.tree_util.keystr(path)
        if np.issubdtype(xa.dtype, np.floating):
            np.testing.assert_allclose(
                xa, xb, rtol=1e-5, atol=1e-6,
                err_msg=f"{what}{name} differs",
            )
        else:
            assert np.array_equal(xa, xb), f"{what}{name} differs"


def run_per_round(step, st, po, pt, pv, he=1):
    sched = heartbeat_schedule(he, 1)
    for i in range(po.shape[0]):
        if he == 1:
            st = step(st, po[i], pt[i], pv[i])
        else:
            st = step(st, po[i], pt[i], pv[i],
                      do_heartbeat=sched[i % len(sched)])
    return st


def run_phase(pstep, st, po, pt, pv, r, he=1):
    sched = heartbeat_schedule(he, r)
    g = po.shape[0] // r
    gro = lambda a: a[: g * r].reshape((g, r) + a.shape[1:])
    po, pt, pv = gro(po), gro(pt), gro(pv)
    for p in range(g):
        st = pstep(st, po[p], pt[p], pv[p], do_heartbeat=sched[p % len(sched)])
    return st


# ---------------------------------------------------------------------------
# r=1 bit-exactness: the phase engine IS the per-round step


@pytest.mark.parametrize("score_counts", [False, True])
def test_phase_r1_bitexact_rich_v11(score_counts):
    """score + flood_publish + PX + fanout + mixed verdicts, he=1.
    16 rounds x 4 pubs < 64 slots => no recycling => every plane equal
    including score counters — on BOTH score-attribution paths (plane
    default and opt-in counts)."""
    net, cfg, sp, st = build(seed=3)
    step = make_gossipsub_step(cfg, net, score_params=sp)
    pstep = make_gossipsub_phase_step(cfg, net, 1, score_params=sp,
                                      score_counts=score_counts)
    po, pt, pv = schedule(16, seed=3, codes=True)
    sa = run_per_round(step, st, po, pt, pv)
    net, cfg, sp, st2 = build(seed=3)
    sb = run_phase(pstep, st2, po, pt, pv, 1)
    assert_states_equal(sa, sb, "r1/")


@pytest.mark.slow
def test_phase_r1_bitexact_static_heartbeat_he2():
    net, cfg, sp, st = build(seed=5, he=2)
    step = make_gossipsub_step(cfg, net, score_params=sp, static_heartbeat=True)
    pstep = make_gossipsub_phase_step(cfg, net, 1, score_params=sp)
    po, pt, pv = schedule(16, seed=5)
    sa = run_per_round(step, st, po, pt, pv, he=2)
    net, cfg, sp, st2 = build(seed=5, he=2)
    sb = run_phase(pstep, st2, po, pt, pv, 1, he=2)
    assert_states_equal(sa, sb, "r1-he2/")


@pytest.mark.slow
def test_phase_r1_bitexact_gater_throttle_queuecap_adversary():
    gp = PeerGaterParams()
    rng = np.random.default_rng(7)
    adv = rng.random(N) < 0.2
    net, cfg, sp, st = build(
        seed=7, gater_params=gp, validation_capacity=3, queue_cap=3,
    )
    step = make_gossipsub_step(cfg, net, score_params=sp, gater_params=gp,
                               adversary_no_forward=adv)
    pstep = make_gossipsub_phase_step(cfg, net, 1, score_params=sp,
                                      gater_params=gp,
                                      adversary_no_forward=adv)
    po, pt, pv = schedule(14, seed=7, codes=True)
    sa = run_per_round(step, st, po, pt, pv)
    net, cfg, sp, st2 = build(seed=7, gater_params=gp, validation_capacity=3,
                              queue_cap=3)
    sb = run_phase(pstep, st2, po, pt, pv, 1)
    assert_states_equal(sa, sb, "r1-gater/")


def test_phase_r1_bitexact_validation_delay():
    net, cfg, sp, st = build(
        seed=11, validation_delay_rounds=2,
        validation_delay_topic=(1, 2, 1),
    )
    step = make_gossipsub_step(cfg, net, score_params=sp)
    pstep = make_gossipsub_phase_step(cfg, net, 1, score_params=sp)
    po, pt, pv = schedule(14, seed=11, codes=True)
    sa = run_per_round(step, st, po, pt, pv)
    net, cfg, sp, st2 = build(seed=11, validation_delay_rounds=2,
                              validation_delay_topic=(1, 2, 1))
    sb = run_phase(pstep, st2, po, pt, pv, 1)
    assert_states_equal(sa, sb, "r1-valdelay/")


def test_phase_r1_bitexact_dynamic_peers():
    net, cfg, sp, st = build(seed=13)
    step = make_gossipsub_step(cfg, net, score_params=sp, dynamic_peers=True)
    pstep = make_gossipsub_phase_step(cfg, net, 1, score_params=sp,
                                      dynamic_peers=True)
    po, pt, pv = schedule(12, seed=13)
    rng = np.random.default_rng(13)
    ups = rng.random((12, N)) > 0.05  # ~5% churn per round
    sa = st
    for i in range(12):
        sa = step(sa, po[i], pt[i], pv[i], jnp.asarray(ups[i]))
    net, cfg, sp, sb = build(seed=13)
    for i in range(12):
        sb = pstep(sb, po[i : i + 1], pt[i : i + 1], pv[i : i + 1],
                   jnp.asarray(ups[i]), do_heartbeat=True)
    assert_states_equal(sa, sb, "r1-dyn/")


def test_phase_r8_dynamic_peers_against_per_round():
    """The r = 8 case beside the r = 1 one above: liveness rows constant
    inside each phase, the phase engine at r = 8 against the per-round
    engine (a heartbeat every 8 rounds) fed the head's row in every round.

    Equal BIT FOR BIT after every phase: ``up``; the emptiness of every
    plane of a down peer (seen-cache, forward set, mcache, mesh rows) and
    of every edge with a down end (mesh), on both sides; the message
    table. Equal only in what the protocol fixes, because the phase engine
    acts on control once a phase (GRAFT / PRUNE a phase later, IWANT
    answers one phase later: the documented control latency) and the two
    draw their mesh candidates from different states: mesh MEMBERSHIP
    (over live edges alone on both, and healed at the end) and ``have`` (once
    the schedule has drained, every peer up without a break since a
    message's birth holds it on both sides if its origin was up, and
    nobody holds a down origin's)."""
    he = r = 8
    phases, quiet = 10, 4
    net, cfg, sp, st = build(seed=29, he=he)
    cfg = dataclasses.replace(cfg, flood_publish=False, do_px=False)
    subs_all = graph.subscribe_all(N, T)
    net = Net.build(graph.random_connect(N, D, seed=29), subs_all)
    step = make_gossipsub_step(cfg, net, score_params=sp, dynamic_peers=True,
                               static_heartbeat=True)
    pstep = make_gossipsub_phase_step(cfg, net, r, score_params=sp,
                                      dynamic_peers=True)
    po, pt, pv = schedule(phases * r, seed=29)
    po = po.at[(phases - quiet) * r:].set(-1)
    rng = np.random.default_rng(29)
    ups = np.ones((phases, N), bool)
    for p in range(2, phases):                   # a few leave, some return
        ups[p] = ups[p - 1]
        ups[p, rng.choice(N, 3, replace=False)] ^= True
    sa = GossipSubState.init(net, M, cfg, score_params=sp, seed=29)
    sb = GossipSubState.init(net, M, cfg, score_params=sp, seed=29)
    sched = heartbeat_schedule(he, 1)
    nbr = np.clip(np.asarray(net.nbr), 0, None)
    ok = np.asarray(net.nbr_ok)
    for p in range(phases):
        row = jnp.asarray(ups[p])
        for i in range(p * r, (p + 1) * r):
            sa = step(sa, po[i], pt[i], pv[i], row,
                      do_heartbeat=sched[i % len(sched)])
        sb = pstep(sb, po[p * r:(p + 1) * r], pt[p * r:(p + 1) * r],
                   pv[p * r:(p + 1) * r], row, do_heartbeat=True)
        live = ok & ups[p][:, None] & ups[p][nbr]
        for s_, name in ((sa, "per-round"), (sb, "phase")):
            assert np.array_equal(np.asarray(s_.up), ups[p]), name
            assert not (np.asarray(s_.mesh) & ~live[:, None, :]).any(), name
            for plane in (s_.core.dlv.have, s_.core.dlv.fwd, s_.mcache):
                assert not np.asarray(plane)[~ups[p]].any(), name
        for field in ("origin", "birth", "topic", "cursor"):
            assert np.array_equal(np.asarray(getattr(sa.core.msgs, field)),
                                  np.asarray(getattr(sb.core.msgs, field)))
    birth = np.asarray(sb.core.msgs.birth)
    origin = np.asarray(sb.core.msgs.origin)
    live_m = np.flatnonzero(birth >= 0)
    first_up = np.array([phases - np.argmax(~ups[::-1, n]) if not ups[:, n].all()
                         else 0 for n in range(N)]) * r       # run's start
    first_up[~ups[-1]] = phases * r
    published = ups[birth[live_m] // r, origin[live_m]] & (
        first_up[origin[live_m]] <= birth[live_m])
    assert published.any() and not published.all()
    for s_ in (sa, sb):
        have = np.asarray(bitset.unpack(s_.core.dlv.have, M))[:, live_m]
        through = first_up[:, None] <= birth[live_m][None, :]
        assert have[:, published][through[:, published]].all()
        down_origin = ~ups[birth[live_m] // r, origin[live_m]]
        assert not have[:, down_origin].any()
    # mesh degree heals on both sides once the last transition is a few
    # heartbeats old (live candidates permitting)
    for s_ in (sa, sb):
        deg = np.asarray(s_.mesh).sum(axis=(1, 2))
        assert (deg[ups[-1]] >= 1).all() and (deg[~ups[-1]] == 0).all()


# ---------------------------------------------------------------------------
# r > 1: delivery still completes; control latency is the only difference


@pytest.mark.parametrize("r", [2, 4])
def test_phase_delivers_everywhere(r):
    net, cfg, sp, st = build(seed=17)
    pstep = make_gossipsub_phase_step(cfg, net, r, score_params=sp)
    rounds = 24
    po, pt, pv = schedule(rounds, seed=17)
    # stop publishing after round 8 so the tail drains
    po = po.at[8:].set(-1)
    st = run_phase(pstep, st, po, pt, pv, r)
    subs = np.asarray(net.subscribed)          # [N,T]
    topic = np.asarray(st.core.msgs.topic)     # [M]
    origin = np.asarray(st.core.msgs.origin)
    have = np.asarray(bitset.unpack(st.core.dlv.have, M))  # [N,M]
    fr_ = np.asarray(st.core.dlv.first_round)
    for s in range(M):
        if origin[s] < 0:
            continue
        subscribers = np.flatnonzero(subs[:, topic[s]])
        cov = have[subscribers, s].mean() if len(subscribers) else 1.0
        assert cov > 0.9, f"slot {s}: coverage {cov}"
    # first_round stamps keep 1-round resolution: arrivals exist at
    # non-phase-boundary ticks
    arr = fr_[(fr_ >= 0) & (np.asarray(st.core.msgs.origin)[None, :] >= 0)]
    assert (arr % r != 0).any()


def test_phase_mesh_maintains():
    net, cfg, sp, st = build(seed=19)
    pstep = make_gossipsub_phase_step(cfg, net, 4, score_params=sp)
    po, pt, pv = schedule(32, seed=19)
    st = run_phase(pstep, st, po, pt, pv, 4)
    deg = np.asarray(st.mesh.sum(axis=2))          # [N,S]
    slot_live = np.asarray(net.my_topics) >= 0
    assert (deg[slot_live] >= 1).all()
    assert (deg[slot_live] <= cfg.Dhi).all()


def test_phase_recycling_invariants():
    """Slot recycling inside a phase: accumulators must drop recycled
    columns (no cross-message attribution) and the engine must stay
    consistent. 10 phases x 8 rounds x 4 pubs >> 64 slots."""
    net, cfg, sp, st = build(seed=23)
    pstep = make_gossipsub_phase_step(cfg, net, 8, score_params=sp)
    po, pt, pv = schedule(80, seed=23)
    st = run_phase(pstep, st, po, pt, pv, 8)
    fr_ = np.asarray(st.core.dlv.first_round)
    birth = np.asarray(st.core.msgs.birth)
    have = np.asarray(bitset.unpack(st.core.dlv.have, M))
    # no receipt can predate its message's birth (stale-bit leak check)
    ok = (fr_ < 0) | (fr_ >= birth[None, :]) | ~have
    assert ok.all()
    # scores stay finite
    assert np.isfinite(np.asarray(st.scores)).all()


# ---------------------------------------------------------------------------
# driver schedule + scan


def test_heartbeat_schedule():
    assert heartbeat_schedule(1, 1) == [True]
    assert heartbeat_schedule(4, 1) == [True, False, False, False]
    assert heartbeat_schedule(4, 2) == [True, False]
    assert heartbeat_schedule(2, 4) == [True]
    assert heartbeat_schedule(3, 2) == [True, True, False]


def test_make_scan_matches_manual_phase():
    net, cfg, sp, st = build(seed=29, he=2)
    pstep = make_gossipsub_phase_step(cfg, net, 2, score_params=sp)
    po, pt, pv = schedule(16, seed=29)
    run = make_scan(pstep, heartbeat_every=2, rounds_per_phase=2, donate=False)
    sa = run(st, po, pt, pv)
    net, cfg, sp, st2 = build(seed=29, he=2)
    sb = run_phase(pstep, st2, po, pt, pv, 2, he=2)
    assert_states_equal(sa, sb, "scan/")


def test_make_scan_per_round_static():
    net, cfg, sp, st = build(seed=31, he=2)
    step = make_gossipsub_step(cfg, net, score_params=sp, static_heartbeat=True)
    po, pt, pv = schedule(12, seed=31)
    run = make_scan(step, heartbeat_every=2, rounds_per_phase=1,
                    static_heartbeat=True, donate=False)
    sa = run(st, po, pt, pv)
    net, cfg, sp, st2 = build(seed=31, he=2)
    sb = run_per_round(step, st2, po, pt, pv, he=2)
    assert_states_equal(sa, sb, "scan-r1/")
    with pytest.raises(ValueError):
        make_scan(step, heartbeat_every=2, rounds_per_phase=1)


def test_phase_trace_exact_dup_plane_reconciles():
    """cfg.trace_exact under the phase engine: the phase-end duplicate
    plane's popcount equals the device duplicate-counter delta — including
    with the validation throttle binding (throttled receipts are fresh
    Rejects, never duplicates)."""
    from go_libp2p_pubsub_tpu.ops import bitset as bs
    from go_libp2p_pubsub_tpu.trace.events import EV

    net, cfg, sp, st = build(seed=37, validation_capacity=3)
    cfg = dataclasses.replace(cfg, trace_exact=True, count_events=True)
    st = GossipSubState.init(net, M, cfg, score_params=sp, seed=37)
    pstep = make_gossipsub_phase_step(cfg, net, 4, score_params=sp)
    po, pt, pv = schedule(16, seed=37)
    sched = heartbeat_schedule(1, 4)
    g = po.shape[0] // 4
    gro = lambda a: a.reshape((g, 4) + a.shape[1:])
    po, pt, pv = gro(po), gro(pt), gro(pv)
    prev_dup = 0
    for p in range(g):
        st = pstep(st, po[p], pt[p], pv[p], do_heartbeat=sched[p % len(sched)])
        dup_now = int(st.core.events[EV.DUPLICATE_MESSAGE])
        plane = int(np.asarray(bs.popcount(st.dup_trans, axis=None)).sum())
        assert plane == dup_now - prev_dup, (p, plane, dup_now - prev_dup)
        prev_dup = dup_now
    assert prev_dup > 0


@pytest.mark.slow
def test_phase_count_vs_plane_score_paths_equal_no_recycle():
    """r=4, no slot recycling: the count-fold and plane score paths are
    bit-equal (integer popcounts are exact in f32; OR preserves the
    transmission multiset)."""
    net, cfg, sp, st = build(seed=41)
    pa = make_gossipsub_phase_step(cfg, net, 4, score_params=sp,
                                   score_counts=False)
    pb = make_gossipsub_phase_step(cfg, net, 4, score_params=sp,
                                   score_counts=True)
    po, pt, pv = schedule(16, seed=41)
    sa = run_phase(pa, st, po, pt, pv, 4)
    net, cfg, sp, st2 = build(seed=41)
    sb = run_phase(pb, st2, po, pt, pv, 4)
    assert_states_equal(sa, sb, "count-vs-plane/")


@pytest.mark.slow
def test_phase_count_path_retains_recycled_credit():
    """Under within-phase recycling the count path retains the score
    credit the plane path sheds (its stated reason to exist): total P2
    first-delivery credit count >= plane, strictly greater when recycling
    actually bites; delivery planes stay identical (attribution never
    affects propagation)."""
    net, cfg, sp, st = build(seed=43)
    pa = make_gossipsub_phase_step(cfg, net, 8, score_params=sp,
                                   score_counts=False)
    pb = make_gossipsub_phase_step(cfg, net, 8, score_params=sp,
                                   score_counts=True)
    po, pt, pv = schedule(80, seed=43)  # 320 pubs >> 64 slots: recycling
    sa = run_phase(pa, st, po, pt, pv, 8)
    net, cfg, sp, st2 = build(seed=43)
    sb = run_phase(pb, st2, po, pt, pv, 8)
    assert np.array_equal(np.asarray(sa.core.dlv.have),
                          np.asarray(sb.core.dlv.have))
    assert np.array_equal(np.asarray(sa.core.dlv.first_round),
                          np.asarray(sb.core.dlv.first_round))
    fa = float(np.asarray(sa.score.fmd).sum())
    fb = float(np.asarray(sb.score.fmd).sum())
    assert fb >= fa
    assert fb > fa, "expected recycling to bite in this workload"


def test_phase_static_weight_elision_scores_exact():
    """With mesh_message_deliveries_weight=0 everywhere (the honest-net
    bench shape) the phase engine skips the in-window mesh-credit plane:
    every state plane except the untracked mmd counter stays bit-exact vs
    the per-round step at r=1, and the SCORES are identical (the elided
    term multiplies by zero)."""
    tp0 = TopicScoreParams(
        mesh_message_deliveries_weight=0.0,
        mesh_failure_penalty_weight=0.0,
    )
    sp = PeerScoreParams(
        topics={t: tp0 for t in range(T)}, skip_app_specific=True,
        behaviour_penalty_weight=-1.0, behaviour_penalty_threshold=1.0,
        behaviour_penalty_decay=0.9,
    )
    topo = graph.random_connect(N, D, seed=47)
    subs = graph.subscribe_random(N, n_topics=T, topics_per_peer=2, seed=47)
    net = Net.build(topo, subs)
    cfg = GossipSubConfig.build(
        GossipSubParams(), PeerScoreThresholds(), score_enabled=True
    )
    st = GossipSubState.init(net, M, cfg, score_params=sp, seed=47)
    step = make_gossipsub_step(cfg, net, score_params=sp)
    pstep = make_gossipsub_phase_step(cfg, net, 1, score_params=sp)
    po, pt, pv = schedule(14, seed=47, codes=True)
    sa = run_per_round(step, st, po, pt, pv)
    sb = run_phase(pstep,
                   GossipSubState.init(net, M, cfg, score_params=sp, seed=47),
                   po, pt, pv, 1)
    # scores identical; everything except the untracked mmd counter exact
    np.testing.assert_allclose(np.asarray(sa.scores), np.asarray(sb.scores),
                               rtol=1e-6)
    assert np.array_equal(np.asarray(sa.core.dlv.have),
                          np.asarray(sb.core.dlv.have))
    assert np.array_equal(np.asarray(sa.core.dlv.first_round),
                          np.asarray(sb.core.dlv.first_round))
    assert np.array_equal(np.asarray(sa.score.imd), np.asarray(sb.score.imd))
    assert np.array_equal(np.asarray(sa.score.fmd), np.asarray(sb.score.fmd))
    # the elided in-window plane leaves mmd tracking first-arrival credit
    # only (on_deliveries adds it regardless); near-first credit is the
    # untracked part — the counter undercounts, the score is untouched
    ma, mb = np.asarray(sa.score.mmd), np.asarray(sb.score.mmd)
    assert (mb <= ma + 1e-6).all()
    assert mb.sum() < ma.sum()


@pytest.mark.slow
def test_phase_no_elision_when_p3b_live():
    """w3=0 but the sticky mesh-failure penalty live (default w3b=-1,
    thr3>0): mmd feeds on_prune's deficit, so the mesh-credit plane must
    NOT be elided — full bit-exactness vs per-round, mmd included (the
    round-4 review's failure scenario)."""
    tp0 = TopicScoreParams(
        mesh_message_deliveries_weight=0.0,
        # mesh_failure_penalty_weight keeps its default (-1): P3b live
        mesh_message_deliveries_threshold=4.0,
        mesh_message_deliveries_activation=6.0,
    )
    sp = PeerScoreParams(
        topics={t: tp0 for t in range(T)}, skip_app_specific=True,
        behaviour_penalty_weight=-1.0, behaviour_penalty_threshold=1.0,
        behaviour_penalty_decay=0.9,
    )
    topo = graph.random_connect(N, D, seed=53)
    subs = graph.subscribe_random(N, n_topics=T, topics_per_peer=2, seed=53)
    net = Net.build(topo, subs)
    cfg = GossipSubConfig.build(
        GossipSubParams(), PeerScoreThresholds(), score_enabled=True
    )
    st = GossipSubState.init(net, M, cfg, score_params=sp, seed=53)
    step = make_gossipsub_step(cfg, net, score_params=sp)
    pstep = make_gossipsub_phase_step(cfg, net, 1, score_params=sp)
    po, pt, pv = schedule(14, seed=53)
    sa = run_per_round(step, st, po, pt, pv)
    sb = run_phase(pstep,
                   GossipSubState.init(net, M, cfg, score_params=sp, seed=53),
                   po, pt, pv, 1)
    assert_states_equal(sa, sb, "p3b-live/")
    assert float(np.asarray(sb.score.mmd).sum()) > 0.0  # plane tracked


def test_phase_exact_counters_disables_elision():
    """exact_counters=True (the api.Network build flag): even with every
    elidable weight zeroed, ALL counters stay bit-exact vs the per-round
    step — the reference's always-exact inspect surface
    (score.go:120-177). This is the introspection-safety contract:
    peer_score_snapshots consumers never see elided counters."""
    tp0 = TopicScoreParams(
        mesh_message_deliveries_weight=0.0,
        mesh_failure_penalty_weight=0.0,
    )
    sp = PeerScoreParams(
        topics={t: tp0 for t in range(T)}, skip_app_specific=True,
        behaviour_penalty_weight=-1.0, behaviour_penalty_threshold=1.0,
        behaviour_penalty_decay=0.9,
    )
    topo = graph.random_connect(N, D, seed=47)
    subs = graph.subscribe_random(N, n_topics=T, topics_per_peer=2, seed=47)
    net = Net.build(topo, subs)
    cfg = GossipSubConfig.build(
        GossipSubParams(), PeerScoreThresholds(), score_enabled=True
    )
    st = GossipSubState.init(net, M, cfg, score_params=sp, seed=47)
    step = make_gossipsub_step(cfg, net, score_params=sp)
    pstep = make_gossipsub_phase_step(cfg, net, 1, score_params=sp,
                                      exact_counters=True)
    po, pt, pv = schedule(14, seed=47, codes=True)
    sa = run_per_round(step, st, po, pt, pv)
    sb = run_phase(pstep,
                   GossipSubState.init(net, M, cfg, score_params=sp, seed=47),
                   po, pt, pv, 1)
    # full bit-exactness INCLUDING the counters elision would corrupt
    assert_states_equal(sa, sb, "exact-counters/")
    # and the elidable planes actually accrued (the test would be vacuous
    # on a workload where no near-first/invalid deliveries happen)
    assert float(np.asarray(sb.score.mmd).sum()) > 0.0
    assert float(np.asarray(sb.score.imd).sum()) > 0.0


def test_phase_api_network_snapshots_exact_counters():
    """api.Network(rounds_per_phase=r) builds with exact_counters: the
    peer_score_snapshots surface shows reference-faithful counters even
    on an all-weights-zero (maximally elidable) config."""
    from go_libp2p_pubsub_tpu.api import Network

    tp0 = TopicScoreParams(
        mesh_message_deliveries_weight=0.0,
        mesh_failure_penalty_weight=0.0,
    )
    sp = PeerScoreParams(
        topics={0: tp0}, skip_app_specific=True,
        behaviour_penalty_weight=-1.0, behaviour_penalty_threshold=1.0,
        behaviour_penalty_decay=0.9,
    )

    def build_net(r):
        netw = Network(score_params=sp, seed=11, rounds_per_phase=r,
                       msg_slots=M)
        nodes = netw.add_nodes(16)
        netw.sparse_connect(d=4, seed=11)
        subs = [n.join("t").subscribe() for n in nodes]
        netw.start()
        return netw, nodes

    na, nodes_a = build_net(1)
    nb, nodes_b = build_net(4)
    for _ in range(3):
        nodes_a[0].topics["t"].publish(b"x")
        nodes_b[0].topics["t"].publish(b"x")
        na.run(4)
        nb.run(4)
    for i in range(16):
        snap_a = nodes_a[i].peer_score_snapshots()
        snap_b = nodes_b[i].peer_score_snapshots()
        assert snap_a.keys() == snap_b.keys()
        for pid, ss_a in snap_a.items():
            ss_b = snap_b[pid]
            for t_name, ts_a in ss_a.topics.items():
                ts_b = ss_b.topics[t_name]
                # the phase build must not elide: fmd/mmd/imd all tracked
                # (values can differ by the designed r-round control
                # latency, but an elided counter would be identically 0
                # network-wide while the r=1 run accrues)
                assert ts_b.mesh_message_deliveries >= 0.0
    # elision would zero mmd network-wide; exact_counters keeps it live.
    # compare network totals within the control-latency tolerance
    mmd_a = float(np.asarray(na.state.score.mmd).sum())
    mmd_b = float(np.asarray(nb.state.score.mmd).sum())
    if mmd_a > 0:
        assert mmd_b > 0, "phase build elided the mmd plane"


def test_admission_invariant_enforced_direct_drivers():
    """The phase engine's publish-capacity invariant (ADVICE round 5,
    item 2), now ENFORCED at the engine layer in two tiers:

    * ``r * pub_width > msg_slots`` — a slot can be re-allocated WITHIN
      one phase, which the deferred recycled-slot clears assume never
      happens: hard PhaseAdmissionError at trace time;
    * ``msg_slots // 2 < r * pub_width <= msg_slots`` — in-flight
      receipts of recycled slots can be wiped before the boundary
      drain observes them: warning.

    API builds (which enforce the flat admission cap on ACTUAL
    publishes) suppress both via admission_capped=True."""
    import warnings

    from go_libp2p_pubsub_tpu.models.gossipsub_phase import (
        PhaseAdmissionError,
    )

    n = 16
    topo = graph.random_connect(n, 4, seed=3)
    net = Net.build(topo, graph.subscribe_all(n, 1))
    cfg = GossipSubConfig.build(GossipSubParams(), PeerScoreThresholds())
    r = 4
    po = jnp.full((r, P), -1, jnp.int32)
    pt = jnp.zeros((r, P), jnp.int32)
    pv = jnp.zeros((r, P), bool)

    # M=8 < r*P=16: within-phase re-allocation possible — hard error
    st = GossipSubState.init(net, 8, cfg, seed=3)
    pstep = make_gossipsub_phase_step(cfg, net, r)
    with pytest.raises(PhaseAdmissionError, match="re-allocated WITHIN"):
        pstep(st, po, pt, pv, do_heartbeat=True)

    # M=24: cap 12 < 16 <= 24 — the warning band
    stw = GossipSubState.init(net, 24, cfg, seed=3)
    pwarn = make_gossipsub_phase_step(cfg, net, r)
    with pytest.warns(UserWarning, match="phase publish capacity"):
        pwarn(stw, po, pt, pv, do_heartbeat=True)

    # the API-certified build stays silent on the raising shape
    st2 = GossipSubState.init(net, 8, cfg, seed=3)
    pcapped = make_gossipsub_phase_step(cfg, net, r, admission_capped=True)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        pcapped(st2, po, pt, pv, do_heartbeat=True)

    # within-capacity shapes never warn
    st3 = GossipSubState.init(net, 64, cfg, seed=3)  # cap 32 >= 16
    pok = make_gossipsub_phase_step(cfg, net, r)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        pok(st3, po, pt, pv, do_heartbeat=True)
