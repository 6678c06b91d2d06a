"""Run-window compiler tests (driver.make_window, docs/DESIGN.md §14).

The round-14 bit-exactness gates: a whole run window compiled as ONE
scan program must reproduce the per-dispatch Python loop EXACTLY —

  * **scanned vs loop parity** on full state trees for all four
    engines (per-round gossipsub under chaos, phase r ∈ {1, 8} on the
    stacked coalesced wire, floodsub, randomsub), telemetry panels
    included bit-for-bit;
  * **identical invariant verdicts** — the folded checker
    (oracle.ScanInvariants inside the scan body) produces the same
    violation masks and tick labels as the per-dispatch InvariantHook,
    on clean runs AND on a seeded violation;
  * **make_scan adapter parity** — the rounds-4..13 driver API, now a
    thin wrapper over the window body, still matches the hand loop for
    plain, static-heartbeat and phase cadences;
  * **segment/checkpoint semantics** — a window split into checkpoint
    segments, saved and restored mid-run, finishes bit-identical to
    the uninterrupted single-dispatch window;
  * **2-D (sims × peers) sharding** — an S=8 ensemble window placed on
    a make_mesh_2d mesh is bit-exact vs unplaced (the 8-virtual-device
    conftest harness);
  * **execution fingerprint + projection dispatch term** — the
    schema-v3 ``execution`` block round-trips (legacy lines read back
    SCAN_OFF) and projection's ``dispatch_overhead_ms`` term defaults
    to zero (the committed round-5 projection reproduces unchanged).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from go_libp2p_pubsub_tpu import checkpoint, ensemble, graph
from go_libp2p_pubsub_tpu.chaos import (
    ChaosConfig,
    halves,
    make_cross_mesh_observer,
    two_group_partition,
)
from go_libp2p_pubsub_tpu.chaos import metrics as cmetrics
from go_libp2p_pubsub_tpu.config import (
    GossipSubParams,
    PeerScoreParams,
    PeerScoreThresholds,
    TopicScoreParams,
)
from go_libp2p_pubsub_tpu.driver import make_scan, make_window, min_cycle
from go_libp2p_pubsub_tpu.models.floodsub import floodsub_step
from go_libp2p_pubsub_tpu.models.gossipsub import (
    GossipSubConfig,
    GossipSubState,
    make_gossipsub_step,
)
from go_libp2p_pubsub_tpu.models.gossipsub_phase import (
    make_gossipsub_phase_step,
)
from go_libp2p_pubsub_tpu.models.randomsub import make_randomsub_step
from go_libp2p_pubsub_tpu.oracle import invariants as oinv
from go_libp2p_pubsub_tpu.state import Net, SimState

N = 48
M = 64
ROUNDS = 8


def _keyless(tree):
    def unkey(x):
        if checkpoint.is_prng_key(x):
            return jax.random.key_data(x)
        return x

    return jax.tree_util.tree_map(unkey, tree)


def assert_trees_bitexact(got, want, context=""):
    flat_g, _ = jax.tree_util.tree_flatten_with_path(_keyless(got))
    flat_w, _ = jax.tree_util.tree_flatten_with_path(_keyless(want))
    assert len(flat_g) == len(flat_w)
    for (path, a), (_, b) in zip(flat_g, flat_w):
        assert a.dtype == b.dtype and a.shape == b.shape, (
            f"{context}{jax.tree_util.keystr(path)}: aval mismatch"
        )
        assert bool(jnp.array_equal(a, b)), (
            f"{context}{jax.tree_util.keystr(path)}: values differ"
        )


def _net(n=N, seed=0):
    topo = graph.random_connect(n, d=4, seed=seed)
    return Net.build(topo, graph.subscribe_all(n, 1))


def _schedule(n, rounds, seed=0, width=4):
    rng = np.random.default_rng(seed)
    po = rng.integers(0, n, size=(rounds, width)).astype(np.int32)
    po[rounds // 2:] = -1
    pt = np.zeros((rounds, width), np.int32)
    pv = np.ones((rounds, width), bool)
    return jnp.asarray(po), jnp.asarray(pt), jnp.asarray(pv)


def _score_params():
    return PeerScoreParams(topics={0: TopicScoreParams()},
                           skip_app_specific=True)


def _gossip_cfg(chaos=None, heartbeat_every=1):
    return GossipSubConfig.build(
        GossipSubParams(D=3, Dlo=2, Dhi=4, Dscore=2, Dout=1),
        PeerScoreThresholds(), score_enabled=True, chaos=chaos,
        heartbeat_every=heartbeat_every,
    )


# ---------------------------------------------------------------------------
# scanned-window vs Python-loop parity, all four engines


def test_window_vs_loop_parity_floodsub():
    net = _net()
    po, pt, pv = _schedule(N, ROUNDS)
    cc = ChaosConfig(loss_rate=0.3)

    def init():
        return SimState.init(N, M, seed=2, k=net.max_degree)

    ref = init()
    for i in range(ROUNDS):
        ref = floodsub_step(net, ref, po[i], pt[i], pv[i], chaos=cc)

    def step(s, a, b, c):
        return floodsub_step(net, s, a, b, c, chaos=cc)

    win = make_window(step)
    got, ys = win(init(), (po, pt, pv))
    assert ys == {}
    assert_trees_bitexact(got, ref, "floodsub window ")


def test_window_vs_loop_parity_randomsub():
    net = _net(seed=3)
    po, pt, pv = _schedule(N, ROUNDS, seed=3)
    step = make_randomsub_step(net)

    def init():
        return SimState.init(N, M, seed=4, k=net.max_degree)

    ref = init()
    for i in range(ROUNDS):
        ref = step(ref, po[i], pt[i], pv[i])
    got, _ = make_window(step)(init(), (po, pt, pv))
    assert_trees_bitexact(got, ref, "randomsub window ")


def test_window_vs_loop_parity_gossipsub_chaos():
    net = _net(seed=5)
    po, pt, pv = _schedule(N, ROUNDS, seed=5)
    sp = _score_params()
    cfg = _gossip_cfg(chaos=ChaosConfig(generator="ge", ge_p_down=0.2,
                                        ge_p_up=0.4))

    def init():
        return GossipSubState.init(net, M, cfg, score_params=sp, seed=6)

    step = make_gossipsub_step(cfg, net, score_params=sp)
    ref = init()
    for i in range(ROUNDS):
        ref = step(ref, po[i], pt[i], pv[i])
    got, _ = make_window(step)(init(), (po, pt, pv))
    assert_trees_bitexact(got, ref, "gossipsub window ")


@pytest.mark.parametrize(
    "r", [1, pytest.param(8, marks=pytest.mark.slow)])
def test_window_vs_loop_parity_phase(r):
    net = _net(seed=7)
    n_phases = 2
    po, pt, pv = _schedule(N, n_phases * r, seed=7)
    po3, pt3, pv3 = (a.reshape(n_phases, r, -1) for a in (po, pt, pv))
    sp = _score_params()
    cfg = _gossip_cfg(heartbeat_every=max(r, 1))
    assert cfg.wire_coalesced

    def init():
        return GossipSubState.init(net, M, cfg, score_params=sp, seed=8)

    step = make_gossipsub_phase_step(cfg, net, r, score_params=sp)
    ref = init()
    for p in range(n_phases):
        ref = step(ref, po3[p], pt3[p], pv3[p], do_heartbeat=True)
    got, _ = make_window(step, heartbeat=[True])(init(), (po3, pt3, pv3))
    assert_trees_bitexact(got, ref, f"phase r={r} window ")


def test_make_scan_adapter_parity_static_heartbeat():
    # the rounds-4..13 make_scan API — now window-backed — must still
    # match a hand loop at every cadence; the static-heartbeat per-round
    # build is the one measure_rate drives for BENCH continuity runs
    net = _net(seed=9)
    he, rounds = 2, ROUNDS
    po, pt, pv = _schedule(N, rounds, seed=9)
    sp = _score_params()
    cfg = _gossip_cfg(heartbeat_every=he)

    def init():
        return GossipSubState.init(net, M, cfg, score_params=sp, seed=10)

    step = make_gossipsub_step(cfg, net, score_params=sp,
                               static_heartbeat=True)
    ref = init()
    for i in range(rounds):
        ref = step(ref, po[i], pt[i], pv[i], do_heartbeat=(i % he == 0))
    scan = make_scan(step, heartbeat_every=he, static_heartbeat=True)
    got = scan(init(), po, pt, pv)
    assert_trees_bitexact(got, ref, "make_scan static-heartbeat ")


def test_min_cycle():
    assert min_cycle([True, False, True, False]) == [True, False]
    assert min_cycle([True]) == [True]
    assert min_cycle([True, True, False]) == [True, True, False]


# ---------------------------------------------------------------------------
# folded invariants: identical verdicts vs the per-dispatch hook


def _flap_cell(seed=11, s=2, rounds=ROUNDS):
    net = _net(seed=seed)
    po, pt, pv = _schedule(N, rounds, seed=seed)
    sp = _score_params()
    cfg = _gossip_cfg(chaos=ChaosConfig(loss_rate=0.4))
    st0 = GossipSubState.init(net, M, cfg, score_params=sp, seed=seed + 1)
    step = make_gossipsub_step(cfg, net, score_params=sp)
    ens = ensemble.lift_step(step)

    def margs(i):
        return (ensemble.tile(po[i], s), ensemble.tile(pt[i], s),
                ensemble.tile(pv[i], s))

    return net, cfg, st0, ens, margs


def test_window_invariant_masks_match_hook():
    s, rounds = 2, ROUNDS
    net, cfg, st0, ens, margs = _flap_cell(s=s, rounds=rounds)
    icfg = oinv.InvariantConfig(check_every=4)

    hook = oinv.InvariantHook("gossipsub", net, cfg, icfg)
    loop = ensemble.run_rounds(ens, ensemble.batch_states(st0, s), margs,
                               rounds, invariants=hook)
    rep_loop = hook.report()

    spec = oinv.ScanInvariants("gossipsub", net, cfg, icfg)
    win = ensemble.run_window(ens, ensemble.batch_states(st0, s), margs,
                              rounds, invariants=spec)
    rep_win = win.invariant_report

    assert rep_win.names == rep_loop.names
    assert rep_win.ticks == rep_loop.ticks
    assert np.array_equal(rep_win.ok, rep_loop.ok)
    assert win.dispatches == 1 and win.compiles == 1
    assert_trees_bitexact(win.states, loop.states, "checked window ")


def test_window_invariant_seeded_violation_matches_hook():
    # corrupt one leaf (a first-receipt stamp on a DEAD message slot —
    # the msgtable-wf property's "stamped ⇒ live" negative shape; the
    # stamp plane is only ever written on first receipt and only
    # cleared on recycle of that slot, which never happens here, so
    # the violation persists across checks) identically for both
    # paths: the folded checker must trip the SAME property at the
    # SAME checks as the hook
    s, rounds = 2, ROUNDS
    net, cfg, st0, ens, margs = _flap_cell(seed=13, s=s, rounds=rounds)

    def corrupt(states):
        dlv = states.core.dlv
        fr = dlv.first_round.at[:, 0, -1].set(0)  # slot M-1: never born
        return states.replace(
            core=states.core.replace(dlv=dlv.replace(first_round=fr)))

    icfg = oinv.InvariantConfig(check_every=4)
    hook = oinv.InvariantHook("gossipsub", net, cfg, icfg)
    ensemble.run_rounds(ens, corrupt(ensemble.batch_states(st0, s)),
                        margs, rounds, invariants=hook)
    rep_loop = hook.report()

    spec = oinv.ScanInvariants("gossipsub", net, cfg, icfg)
    win = ensemble.run_window(ens, corrupt(ensemble.batch_states(st0, s)),
                              margs, rounds, invariants=spec)
    rep_win = win.invariant_report

    assert not rep_loop.all_ok  # the seed actually tripped something
    assert np.array_equal(rep_win.ok, rep_loop.ok)
    assert rep_win.violations() == rep_loop.violations()


# ---------------------------------------------------------------------------
# telemetry rides the carry: panels bit-exact through a window


def test_window_telemetry_panel_bitexact():
    from go_libp2p_pubsub_tpu.telemetry import TelemetryConfig, reconcile

    net = _net(seed=15)
    rounds = ROUNDS
    po, pt, pv = _schedule(N, rounds, seed=15)
    sp = _score_params()
    tcfg = TelemetryConfig(rows=rounds)
    cfg = GossipSubConfig.build(
        GossipSubParams(D=3, Dlo=2, Dhi=4, Dscore=2, Dout=1),
        PeerScoreThresholds(), score_enabled=True,
    )
    import dataclasses as dc

    cfg = dc.replace(cfg, count_events=True)

    def init():
        return GossipSubState.init(net, M, cfg, score_params=sp, seed=16,
                                   telemetry=tcfg)

    step = make_gossipsub_step(cfg, net, score_params=sp, telemetry=tcfg)
    ref = init()
    for i in range(rounds):
        ref = step(ref, po[i], pt[i], pv[i])
    got, _ = make_window(step)(init(), (po, pt, pv))
    panel = np.asarray(got.core.telem.panel)
    assert np.array_equal(panel, np.asarray(ref.core.telem.panel))
    assert reconcile(panel, np.asarray(got.core.events)) == []
    assert_trees_bitexact(got, ref, "telemetry window ")


# ---------------------------------------------------------------------------
# scheduled deny masks + churn-style extra xs through the window


def test_window_scheduled_deny_xs_parity():
    net = _net(seed=17)
    rounds = ROUNDS
    po, pt, pv = _schedule(N, rounds, seed=17)
    sp = _score_params()
    cfg = _gossip_cfg(chaos=ChaosConfig(scheduled=True))
    scenario = two_group_partition(N, start=2, rounds=4)
    nbr = np.asarray(net.nbr)
    denies = jnp.asarray(np.stack([
        d if (d := scenario.link_deny_at(t, nbr)) is not None
        else np.zeros(nbr.shape, bool)
        for t in range(rounds)]))

    def init():
        return GossipSubState.init(net, M, cfg, score_params=sp, seed=18)

    step = make_gossipsub_step(cfg, net, score_params=sp)
    ref = init()
    for i in range(rounds):
        ref = step(ref, po[i], pt[i], pv[i], denies[i])
    got, _ = make_window(step)(init(), (po, pt, pv, denies))
    assert_trees_bitexact(got, ref, "scheduled-deny window ")


def test_window_observe_matches_host_series():
    net = _net(seed=19)
    rounds = ROUNDS
    po, pt, pv = _schedule(N, rounds, seed=19)
    sp = _score_params()
    cfg = _gossip_cfg()
    groups = halves(N)

    def init():
        return GossipSubState.init(net, M, cfg, score_params=sp, seed=20)

    step = make_gossipsub_step(cfg, net, score_params=sp)
    ref, host_series = init(), []
    for i in range(rounds):
        ref = step(ref, po[i], pt[i], pv[i])
        host_series.append(cmetrics.cross_group_mesh_count(
            np.asarray(ref.mesh), np.asarray(net.nbr),
            np.asarray(net.nbr_ok), groups))
    obs = make_cross_mesh_observer(net.nbr, net.nbr_ok, groups)
    got, ys = make_window(step, observe=obs)(init(), (po, pt, pv))
    assert [int(x) for x in np.asarray(ys["obs"])] == host_series
    assert_trees_bitexact(got, ref, "observed window ")


# ---------------------------------------------------------------------------
# segments = checkpoint quantum: mid-window resume == uninterrupted


def test_window_checkpoint_segment_resume(tmp_path):
    s, rounds, seg = 2, ROUNDS, ROUNDS // 2
    net, cfg, st0, ens, margs = _flap_cell(seed=21, s=s, rounds=rounds)

    gold = ensemble.run_window(ens, ensemble.batch_states(st0, s), margs,
                               rounds)
    assert gold.dispatches == 1

    # segmented: checkpoint at the segment boundary, then RESUME FROM
    # DISK into a fresh runner — must finish bit-identical
    path = str(tmp_path / "mid.npz")
    runner = ensemble.WindowRunner(ens, rounds, segment_len=seg)
    runner.run(ensemble.batch_states(st0, s), margs,
               on_segment=lambda g, states: checkpoint.save(path, states))
    restored = checkpoint.restore(path, ensemble.batch_states(st0, s))
    resumed = ensemble.WindowRunner(ens, seg).run(
        restored, lambda i: margs(i + seg))
    assert_trees_bitexact(resumed.states, gold.states, "resumed window ")


# ---------------------------------------------------------------------------
# 2-D (sims × peers) mesh: bit-exact vs unplaced, S=8 window


@pytest.mark.parametrize("axis", ["sims+peers"])
def test_window_2d_mesh_parity(axis):
    from go_libp2p_pubsub_tpu.parallel import make_mesh_2d

    if jax.device_count() < 8:
        pytest.skip("needs the 8-virtual-device harness")
    s = 8
    net = _net(seed=23)
    po, pt, pv = _schedule(N, ROUNDS, seed=23)
    ens = ensemble.lift_floodsub(net)

    def batched():
        return ensemble.batch_states(
            SimState.init(N, M, seed=24, k=net.max_degree), s)

    def margs(i):
        return (ensemble.tile(po[i], s), ensemble.tile(pt[i], s),
                ensemble.tile(pv[i], s))

    gold = ensemble.run_window(ens, batched(), margs, ROUNDS)
    mesh = make_mesh_2d(2, 4)
    placed = ensemble.shard_ensemble_state(batched(), mesh, N, axis=axis)
    run = ensemble.run_window(ens, placed, margs, ROUNDS)
    assert run.dispatches == 1
    assert_trees_bitexact(run.states, gold.states, "2-D placed window ")


def test_mesh_2d_shape_validation():
    from go_libp2p_pubsub_tpu.parallel import make_mesh_2d

    if jax.device_count() < 8:
        pytest.skip("needs the 8-virtual-device harness")
    mesh = make_mesh_2d(2)
    assert mesh.axis_names == ("sims", "peers")
    assert mesh.devices.shape == (2, 4)
    with pytest.raises(ValueError):
        make_mesh_2d(3)  # 3 does not divide 8


# ---------------------------------------------------------------------------
# execution fingerprint + the projection dispatch term


def test_execution_fingerprint_roundtrip():
    import json

    from go_libp2p_pubsub_tpu.perf.artifacts import (
        SCAN_OFF,
        BenchRecord,
        dump_record,
        execution_fingerprint,
        record_from_line,
    )

    rec = BenchRecord(
        metric="x", value=100.0, unit="ticks/s", vs_baseline=0.01,
        schema=3,
        fingerprint={"execution": execution_fingerprint(
            scan=True, segment_rounds=1600, dispatches_per_window=1,
            rounds_per_dispatch=1600, mesh_shape={"sims": 2, "peers": 4},
            unroll=16, check_every=8)},
    )
    back = record_from_line(json.loads(dump_record(rec)))
    assert back.scanned is True
    assert back.execution["mesh_shape"] == {"sims": 2, "peers": 4}
    assert back.dispatches_per_round == 1 / 1600
    # legacy lines: the explicit SCAN_OFF sentinel, never a KeyError
    legacy = record_from_line({"metric": "y", "value": 1.0})
    assert legacy.execution == SCAN_OFF
    assert legacy.scanned is None
    assert legacy.dispatches_per_round is None


def test_projection_dispatch_term():
    from go_libp2p_pubsub_tpu.perf.projection import project

    base = project(0.4247, 16)
    # default: the term is off — pre-round-14 projections unchanged
    assert base.dispatch_ms_per_round == 0.0
    armed_scan = project(0.4247, 16, dispatch_overhead_ms=1.0,
                         dispatches_per_round=1 / 1600)
    armed_loop = project(0.4247, 16, dispatch_overhead_ms=1.0,
                         dispatches_per_round=1 / 16)
    # per-dispatch execution pays 100x the scanned dispatch cost
    assert armed_loop.dispatch_ms_per_round == pytest.approx(
        100 * armed_scan.dispatch_ms_per_round)
    assert armed_loop.central < armed_scan.central <= base.central
    with pytest.raises(ValueError):
        project(0.4, 16, dispatch_overhead_ms=-1.0)


def test_projection_round5_reproduces_with_dispatch_term_off(bench_r01_r05):
    import os

    from go_libp2p_pubsub_tpu.perf.artifacts import _repo_root
    from go_libp2p_pubsub_tpu.perf.projection import project_from_artifacts

    multi = os.path.join(_repo_root(), "MULTICHIP_r05.json")
    proj = project_from_artifacts(bench_r01_r05[4], multi)
    assert 0.44 <= proj.central / 10_000.0 <= 0.455
    assert proj.dispatch_ms_per_round == 0.0


# ---------------------------------------------------------------------------
# window validation errors


def test_window_rejects_misaligned_lengths():
    net = _net(seed=25)
    po, pt, pv = _schedule(N, 6, seed=25)
    step = make_randomsub_step(net)
    win = make_window(step, check=lambda s, p, d: jnp.zeros((1,), bool),
                      check_every=4)
    with pytest.raises(ValueError, match="not a multiple"):
        win(SimState.init(N, M, seed=26, k=net.max_degree),
            (po, pt, pv), jnp.zeros((1, 6), jnp.int32))


def test_window_runner_rejects_misaligned_segments():
    net, cfg, st0, ens, margs = _flap_cell(seed=27)
    with pytest.raises(ValueError, match="segment_len"):
        ensemble.WindowRunner(ens, ROUNDS, segment_len=3)
    spec = oinv.ScanInvariants("gossipsub", net, cfg,
                               oinv.InvariantConfig(check_every=3))
    with pytest.raises(ValueError, match="check_every"):
        ensemble.WindowRunner(ens, ROUNDS, invariants=spec,
                              segment_len=4)
