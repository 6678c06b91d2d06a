"""Supervised service loop (serve/, docs/DESIGN.md §17): durability,
detection, recovery.

The contract under test: a supervised run is OBSERVATIONAL (bit-exact
vs a bare window) when healthy; a SIGKILL at any point — including
mid-checkpoint-write — resumes bit-exact; every health probe has a
seeded-negative that trips EXACTLY that probe and the rollback replay
localizes the injected dispatch; transient dispatch failures retry and
degrade without dropping rounds."""

import json
import os
import signal
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from go_libp2p_pubsub_tpu import checkpoint, ensemble
from go_libp2p_pubsub_tpu.oracle import (
    HealthConfig,
    InvariantConfig,
    ScanInvariants,
    health_check,
    make_health_probe,
)
from go_libp2p_pubsub_tpu.serve import (
    CheckpointStore,
    FaultPlan,
    RetentionPolicy,
    ServiceConfig,
    ServiceHalted,
    Supervisor,
    TransientDispatchError,
    corrupt_leaf_member,
    flip_bit,
    state_digest,
    truncate_file,
)
from go_libp2p_pubsub_tpu.serve._child import build_cell
from go_libp2p_pubsub_tpu.state import SimState

N = 32
ROUNDS = 16
SEG = 4
SEED = 7
LOSS = 0.1

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cell():
    return build_cell(N, ROUNDS, SEED, LOSS)


def _svc(**kw):
    kw.setdefault("n_dispatches", ROUNDS)
    kw.setdefault("segment_len", SEG)
    kw.setdefault("report_name", None)
    kw.setdefault("backoff_base_s", 0.001)
    return ServiceConfig(**kw)


def _spec(cell):
    _step, _margs, _tmpl, net, cfg = cell
    return ScanInvariants(
        "gossipsub", net, cfg,
        InvariantConfig(check_every=SEG, delivery_window=16),
        batched=False)


def _gold_digest(cell):
    step, make_args, template_fn, _net, _cfg = cell
    run = ensemble.WindowRunner(step, ROUNDS).run(template_fn(), make_args)
    return state_digest(run.states)


# ---------------------------------------------------------------------------
# store: retention, manifest, fallback


def _tree(seed=0):
    return SimState.init(8, 16, seed=seed, k=4)


def test_store_retention_keep_last_and_keep_every(tmp_path):
    store = CheckpointStore(str(tmp_path / "s"),
                            RetentionPolicy(keep_last=2, keep_every=3))
    for i in range(7):
        store.save(_tree(i), tick=i * 10)
    ords = [e["ordinal"] for e in store.entries()]
    # last two (5, 6) + every 3rd (0, 3, 6)
    assert ords == [0, 3, 5, 6]
    on_disk = sorted(f for f in os.listdir(store.root)
                     if f.startswith("ckpt_"))
    assert len(on_disk) == 4  # pruned files really deleted
    st, entry = store.restore_latest(_tree())
    assert entry["ordinal"] == 6 and entry["tick"] == 60


def test_store_falls_back_past_damaged_snapshots(tmp_path):
    store = CheckpointStore(str(tmp_path / "s"),
                            RetentionPolicy(keep_last=4))
    for i in range(3):
        store.save(_tree(i), tick=i)
    truncate_file(os.path.join(store.root, store.entries()[-1]["file"]))
    flip_bit(os.path.join(store.root, store.entries()[-2]["file"]))
    st, entry = store.restore_latest(_tree())
    assert entry["ordinal"] == 0
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(st.key)),
        np.asarray(jax.random.key_data(_tree(0).key)))
    # the dropped entries are gone from the rewritten manifest
    store2 = CheckpointStore(store.root)
    assert [e["ordinal"] for e in store2.entries()] == [0]


def test_store_rebuilds_corrupt_manifest_from_files(tmp_path):
    store = CheckpointStore(str(tmp_path / "s"))
    store.save(_tree(1), tick=5)
    store.save(_tree(2), tick=9)
    with open(os.path.join(store.root, "MANIFEST.json"), "w") as f:
        f.write("{not json")
    store2 = CheckpointStore(store.root)
    assert [e["tick"] for e in store2.entries()] == [5, 9]
    st, entry = store2.restore_latest(_tree())
    assert entry["tick"] == 9


def test_store_sweeps_orphan_tmp_files(tmp_path):
    root = str(tmp_path / "s")
    os.makedirs(root)
    orphan = os.path.join(root, "ckpt_000009_t0000000001.npz.tmp.npz")
    with open(orphan, "wb") as f:
        f.write(b"partial write")
    CheckpointStore(root)
    assert not os.path.exists(orphan)


def test_retention_policy_validation():
    with pytest.raises(ValueError):
        RetentionPolicy(keep_last=0)
    with pytest.raises(ValueError):
        RetentionPolicy(keep_every=-1)


# ---------------------------------------------------------------------------
# probes


def test_probe_clean_state_passes(cell):
    _step, _margs, template_fn, _net, _cfg = cell
    st = template_fn()
    probe, names = make_health_probe(HealthConfig())
    ok = np.asarray(probe(st, st.core.events))
    assert names == ("finite-state", "events-monotone", "delivery-floor")
    assert ok.all()


def test_probe_negative_finite_state(cell):
    _step, _margs, template_fn, _net, _cfg = cell
    st = template_fn()
    st = st.replace(scores=st.scores.at[0, 0].set(jnp.nan))
    cfgp = HealthConfig()
    ok = np.asarray(health_check(st, st.core.events, cfgp))
    assert list(ok) == [False, True, True]  # EXACTLY finite-state


def test_probe_negative_events_monotone(cell):
    _step, _margs, template_fn, _net, _cfg = cell
    st = template_fn()
    prev = st.core.events.at[3].set(10)  # counter went backwards
    ok = np.asarray(health_check(st, prev, HealthConfig()))
    assert list(ok) == [True, False, False]  # monotone + floor(delta<0)


def test_probe_negative_delivery_floor(cell):
    _step, _margs, template_fn, _net, _cfg = cell
    st = template_fn()
    ok = np.asarray(health_check(st, st.core.events,
                                 HealthConfig(delivery_floor=10)))
    assert list(ok) == [True, True, False]  # EXACTLY delivery-floor


# ---------------------------------------------------------------------------
# supervisor: clean run, resume, recovery, retry, degradation


def test_supervised_clean_run_bitexact_vs_bare_window(cell, tmp_path):
    step, make_args, template_fn, _net, _cfg = cell
    gold = _gold_digest(cell)
    sup = Supervisor(step, make_args, template_fn, str(tmp_path), _svc(),
                     invariants=_spec(cell))
    rep = sup.run()
    assert state_digest(rep.states) == gold
    assert rep.segments == ROUNDS // SEG
    assert rep.recoveries == 0 and rep.retries == 0
    assert all(v == 1 for v in rep.window_compiles.values())
    assert rep.invariant_checks == ROUNDS // SEG
    hb = json.load(open(rep.heartbeat_path))
    assert hb["status"] == "done" and hb["dispatch"] == ROUNDS
    assert rep.fingerprint()["enabled"] is True


def test_overflow_horizon_startup_note(tmp_path):
    """The serve-side surfacing of the range audit's overflow-horizon
    contract (analysis/ranges.py, docs/DESIGN.md §23): the committed
    RANGE_AUDIT.json horizons become a one-line startup note comparing
    the planned run length against the tightest counter horizon. A
    missing or malformed artifact yields None — never blocks serving."""
    from go_libp2p_pubsub_tpu.serve.supervisor import overflow_horizon_note

    note = overflow_horizon_note(repo_root=_REPO)
    assert note is not None and "int32 event counter" in note
    # the audit's tightest horizon (DUPLICATE_MESSAGE under the flood
    # envelope) appears by name with its round count
    assert "DUPLICATE_MESSAGE" in note

    fits = overflow_horizon_note(total_rounds=1, repo_root=_REPO)
    assert "fits every horizon" in fits
    over = overflow_horizon_note(total_rounds=10**12, repo_root=_REPO)
    assert "EXCEEDS" in over and "counter_events" in over

    # fresh checkout (no artifact) and a corrupt artifact: silent None
    assert overflow_horizon_note(repo_root=str(tmp_path)) is None
    (tmp_path / "RANGE_AUDIT.json").write_text("{not json")
    assert overflow_horizon_note(repo_root=str(tmp_path)) is None


def test_supervised_run_logs_horizon_note(cell, tmp_path, caplog):
    import logging

    step, make_args, template_fn, _net, _cfg = cell
    sup = Supervisor(step, make_args, template_fn, str(tmp_path),
                     _svc(health=None))
    with caplog.at_level(logging.INFO,
                         logger="go_libp2p_pubsub_tpu.serve.supervisor"):
        sup.run()
    assert any("range audit horizons" in r.message for r in caplog.records)


def test_supervised_probes_off_still_bitexact(cell, tmp_path):
    step, make_args, template_fn, _net, _cfg = cell
    sup = Supervisor(step, make_args, template_fn, str(tmp_path),
                     _svc(health=None))
    rep = sup.run()
    assert state_digest(rep.states) == _gold_digest(cell)
    assert rep.probes == ()


def test_supervised_resume_midway_bitexact(cell, tmp_path):
    """Restartable anywhere: a run stopped at the halfway checkpoint and
    re-driven by a FRESH supervisor finishes bit-exact."""
    step, make_args, template_fn, _net, _cfg = cell
    root = str(tmp_path)
    half = Supervisor(step, make_args, template_fn, root,
                      _svc(n_dispatches=ROUNDS // 2))
    half.run()
    full = Supervisor(step, make_args, template_fn, root, _svc())
    rep = full.run()
    assert rep.resumed_from == ROUNDS // 2
    assert state_digest(rep.states) == _gold_digest(cell)


def test_supervised_report_written_incrementally(cell, tmp_path):
    step, make_args, template_fn, _net, _cfg = cell
    sup = Supervisor(step, make_args, template_fn, str(tmp_path),
                     _svc(report_name="service"))
    sup.run()
    rows = [json.loads(x) for x in open(tmp_path / "service.jsonl")]
    assert len(rows) == ROUNDS // SEG
    assert rows[-1]["dispatch"] == ROUNDS
    html = (tmp_path / "service.html").read_text()
    assert "supervised service loop" in html


def test_nan_injection_recovers_and_localizes(cell, tmp_path):
    step, make_args, template_fn, _net, _cfg = cell
    faults = FaultPlan(corrupt_segment=1, corrupt_dispatch=2,
                       corrupt_leaf="scores", corrupt_kind="nan")
    sup = Supervisor(step, make_args, template_fn, str(tmp_path), _svc(),
                     invariants=_spec(cell), faults=faults)
    rep = sup.run()
    assert rep.recoveries == 1
    assert len(rep.bundles) == 1
    b = rep.bundles[0]
    assert b["first_bad_dispatch"] == 1 * SEG + 2
    assert "finite-state" in b["window_probe_failures"]
    assert "finite-state" in b["replay_failures"]
    assert b["nan_census"]  # names the damaged leaf
    assert os.path.exists(os.path.join(b["path"], "bundle.json"))
    # transient corruption: the re-run segment is clean and the final
    # state is the uninterrupted control's
    assert state_digest(rep.states) == _gold_digest(cell)


def test_events_corruption_trips_monotone_probe(cell, tmp_path):
    step, make_args, template_fn, _net, _cfg = cell
    faults = FaultPlan(corrupt_segment=2, corrupt_dispatch=1,
                       corrupt_kind="events")
    sup = Supervisor(step, make_args, template_fn, str(tmp_path), _svc(),
                     faults=faults)
    rep = sup.run()
    assert rep.recoveries == 1
    b = rep.bundles[0]
    assert "events-monotone" in b["window_probe_failures"]
    assert b["first_bad_dispatch"] == 2 * SEG + 1
    assert state_digest(rep.states) == _gold_digest(cell)


def test_persistent_corruption_halts_with_bundle(cell, tmp_path):
    step, make_args, template_fn, _net, _cfg = cell
    faults = FaultPlan(corrupt_segment=1, corrupt_kind="nan",
                       corrupt_leaf="scores",
                       corrupt_max_fires=10 ** 9)
    sup = Supervisor(step, make_args, template_fn, str(tmp_path),
                     _svc(max_recoveries_per_segment=2), faults=faults)
    with pytest.raises(ServiceHalted) as ei:
        sup.run()
    assert ei.value.bundle is not None
    assert "finite-state" in str(ei.value)
    hb = json.load(open(sup.heartbeat_path))
    assert hb["status"] == "halted"


def test_delivery_floor_violation_halts(cell, tmp_path):
    step, make_args, template_fn, _net, _cfg = cell
    sup = Supervisor(
        step, make_args, template_fn, str(tmp_path),
        _svc(health=HealthConfig(delivery_floor=10 ** 9),
             max_recoveries_per_segment=1))
    with pytest.raises(ServiceHalted) as ei:
        sup.run()
    assert "delivery-floor" in str(ei.value)


def test_transient_dispatch_failures_retried(cell, tmp_path):
    step, make_args, template_fn, _net, _cfg = cell
    faults = FaultPlan(fail_dispatches={1: 2})
    sup = Supervisor(step, make_args, template_fn, str(tmp_path), _svc(),
                     faults=faults)
    rep = sup.run()
    assert rep.retries == 2
    assert rep.recoveries == 0
    assert state_digest(rep.states) == _gold_digest(cell)


def test_dispatch_failure_degrades_then_halts(cell, tmp_path):
    step, make_args, template_fn, _net, _cfg = cell
    faults = FaultPlan(fail_dispatches={0: 10 ** 6})
    sup = Supervisor(step, make_args, template_fn, str(tmp_path),
                     _svc(max_retries=1), faults=faults)
    with pytest.raises(ServiceHalted) as ei:
        sup.run()
    assert "degradation ladder is exhausted" in str(ei.value)
    # the ladder was walked: segment halved down to 1 dispatch
    assert [d for d in sup._degradations
            if d.startswith("shrink-segment")] == [
        "shrink-segment:2", "shrink-segment:1"]


def test_degradation_recovers_when_failures_stop(cell, tmp_path):
    """The ladder is for SURVIVING: failures that outlast the retry
    budget but eventually stop leave a degraded-but-complete run with
    every round accounted for."""
    step, make_args, template_fn, _net, _cfg = cell
    faults = FaultPlan(fail_dispatches={0: 3})
    sup = Supervisor(step, make_args, template_fn, str(tmp_path),
                     _svc(max_retries=1), faults=faults)
    rep = sup.run()
    assert rep.degradations == ["shrink-segment:2"]
    assert state_digest(rep.states) == _gold_digest(cell)


def test_config_validation():
    with pytest.raises(ValueError):
        ServiceConfig(n_dispatches=10, segment_len=4)
    with pytest.raises(ValueError):
        ServiceConfig(n_dispatches=8, segment_len=4,
                      checkpoint_every_segments=0)


def test_invariant_cadence_must_divide_segment(cell, tmp_path):
    step, make_args, template_fn, net, cfg = cell
    spec = ScanInvariants("gossipsub", net, cfg,
                          InvariantConfig(check_every=3), batched=False)
    with pytest.raises(ValueError, match="check_every"):
        Supervisor(step, make_args, template_fn, str(tmp_path), _svc(),
                   invariants=spec)


# ---------------------------------------------------------------------------
# the real thing: SIGKILL a child process, resume, compare digests


def _run_child(root, *extra, timeout=240):
    # a CPU-only cell: this parent holds jax already, and the child pins
    # the CPU itself (serve/_child.py main) — one process per chip
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "go_libp2p_pubsub_tpu.serve._child",
           "--root", str(root), "--n", str(N), "--rounds", str(ROUNDS),
           "--segment", str(SEG), "--probes", *extra]
    return subprocess.run(cmd, cwd=_REPO, env=env, capture_output=True,
                          text=True, timeout=timeout)


def test_sigkill_mid_checkpoint_write_resumes_bitexact(tmp_path):
    """The dirtiest crash window: SIGKILL while the checkpoint tmp file
    is half-written. The truncated tmp must not poison the store, and
    the resumed run must finish bit-exact vs an uninterrupted control."""
    ctrl = _run_child(tmp_path / "ctrl", "--fresh")
    assert ctrl.returncode == 0, ctrl.stderr[-800:]
    control = json.loads(open(tmp_path / "ctrl" / "FINAL.json").read())

    crashed = _run_child(tmp_path / "kill", "--fresh",
                         "--kill-segment", "1", "--kill-site", "mid-write")
    assert crashed.returncode in (-signal.SIGKILL, 128 + signal.SIGKILL), (
        crashed.returncode, crashed.stderr[-800:])
    resumed = _run_child(tmp_path / "kill")
    assert resumed.returncode == 0, resumed.stderr[-800:]
    final = json.loads(open(tmp_path / "kill" / "FINAL.json").read())
    assert final["resumed_from"] is not None
    assert final["digest"] == control["digest"]


@pytest.mark.slow
def test_sigkill_randomized_sites_resume_bitexact(tmp_path):
    """Seeded random crash points across every kill site: resume is
    bit-exact regardless of where the run died."""
    ctrl = _run_child(tmp_path / "ctrl", "--fresh")
    assert ctrl.returncode == 0, ctrl.stderr[-800:]
    control = json.loads(open(tmp_path / "ctrl" / "FINAL.json").read())
    rng = np.random.default_rng(99)
    for i, site in enumerate(("post-segment", "post-rename")):
        root = tmp_path / f"kill{i}"
        seg = int(rng.integers(0, ROUNDS // SEG))
        crashed = _run_child(root, "--fresh", "--kill-segment", str(seg),
                             "--kill-site", site)
        assert crashed.returncode in (-signal.SIGKILL,
                                      128 + signal.SIGKILL)
        resumed = _run_child(root)
        assert resumed.returncode == 0, resumed.stderr[-800:]
        final = json.loads(open(root / "FINAL.json").read())
        assert final["digest"] == control["digest"], (site, seg)


# ---------------------------------------------------------------------------
# faults: the file-damage helpers really produce typed corruption


def test_corrupt_helpers_raise_typed_errors(tmp_path):
    st = _tree(3)
    p1 = str(tmp_path / "a.npz")
    checkpoint.save(p1, st)
    truncate_file(p1)
    with pytest.raises(checkpoint.CheckpointCorrupt):
        checkpoint.verify(p1)
    p2 = str(tmp_path / "b.npz")
    checkpoint.save(p2, st)
    flip_bit(p2, seed=1)
    with pytest.raises(checkpoint.CheckpointCorrupt):
        checkpoint.verify(p2)
    p3 = str(tmp_path / "c.npz")
    checkpoint.save(p3, st)
    corrupt_leaf_member(p3, 0)
    with pytest.raises(checkpoint.CheckpointCorrupt, match="leaf_0"):
        checkpoint.verify(p3)


def test_fault_plan_validation_and_budget():
    with pytest.raises(ValueError, match="kill_site"):
        FaultPlan(kill_site="nope")
    plan = FaultPlan(fail_dispatches={2: 2})
    with pytest.raises(TransientDispatchError):
        plan.before_dispatch(2)
    with pytest.raises(TransientDispatchError):
        plan.before_dispatch(2)
    plan.before_dispatch(2)  # budget spent: no raise
    plan.before_dispatch(0)  # unscheduled segment: no raise


def test_replay_localizes_under_nonzero_delivery_floor(cell, tmp_path):
    """Review regression: the delivery floor is a PER-SEGMENT quantity —
    the per-dispatch replay must zero it, or it spuriously trips at the
    first replayed dispatch and mislocalizes. A NaN injected mid-segment
    under a satisfiable floor must still be named as finite-state at the
    injected dispatch."""
    step, make_args, template_fn, _net, _cfg = cell
    faults = FaultPlan(corrupt_segment=1, corrupt_dispatch=2,
                       corrupt_leaf="scores", corrupt_kind="nan")
    sup = Supervisor(step, make_args, template_fn, str(tmp_path),
                     _svc(health=HealthConfig(delivery_floor=1)),
                     faults=faults)
    rep = sup.run()
    b = rep.bundles[0]
    assert b["first_bad_dispatch"] == 1 * SEG + 2
    assert "finite-state" in b["replay_failures"]
    assert "delivery-floor" not in b["replay_failures"]
    assert state_digest(rep.states) == _gold_digest(cell)


def test_ladder_exhausted_halt_updates_heartbeat(cell, tmp_path):
    """Review regression: the retry/degradation halt path must leave a
    'halted' heartbeat, not a stale 'running' one."""
    step, make_args, template_fn, _net, _cfg = cell
    faults = FaultPlan(fail_dispatches={0: 10 ** 6})
    sup = Supervisor(step, make_args, template_fn, str(tmp_path),
                     _svc(max_retries=1), faults=faults)
    with pytest.raises(ServiceHalted):
        sup.run()
    assert json.load(open(sup.heartbeat_path))["status"] == "halted"


def test_store_manifest_never_references_deleted_files(tmp_path):
    """Review regression: pruned files are unlinked only AFTER the
    manifest commit — at every point the manifest on disk references
    only files that exist."""
    store = CheckpointStore(str(tmp_path / "s"),
                            RetentionPolicy(keep_last=1))
    seen = []

    def hook(stage, path):
        if stage != "manifest":
            return
        doc = json.load(open(path))
        for e in doc["entries"]:
            seen.append(os.path.exists(
                os.path.join(str(tmp_path / "s"), e["file"])))

    store.write_hook = hook
    for i in range(4):
        store.save(_tree(i), tick=i)
    assert seen and all(seen)


def test_supervised_observations_surfaced(cell, tmp_path):
    """Review regression: observe= results must reach the caller — the
    stacked per-dispatch pytree over every committed dispatch."""
    import jax.numpy as _jnp

    step, make_args, template_fn, _net, _cfg = cell
    sup = Supervisor(step, make_args, template_fn, str(tmp_path), _svc(),
                     observe=lambda st: _jnp.asarray(st.core.tick))
    rep = sup.run()
    ticks = np.asarray(rep.observations)
    assert ticks.shape == (ROUNDS,)
    assert list(ticks) == list(range(1, ROUNDS + 1))
    assert state_digest(rep.states) == _gold_digest(cell)


# ---------------------------------------------------------------------------
# segment-boundary EV drain: unbounded counter horizon


def test_ev_drain_totals_match_bare_run_and_zero_device(cell, tmp_path):
    """drain_event_counters at a SHRUNK horizon: with the drain on, the
    device i32 counters are zeroed at every committed boundary — so the
    worst value any counter ever holds is ONE segment's growth (here a
    4-dispatch segment standing in for the range audit's ~4k-round
    DUPLICATE_MESSAGE horizon) — while the host i64 totals finish equal
    to the counters a bare (undrained) run accumulates on device."""
    step, make_args, template_fn, _net, _cfg = cell
    run = ensemble.WindowRunner(step, ROUNDS).run(template_fn(), make_args)
    bare = np.asarray(run.states.core.events, np.int64)
    sup = Supervisor(step, make_args, template_fn, str(tmp_path),
                     _svc(drain_event_counters=True))
    rep = sup.run()
    assert rep.ev_totals is not None and rep.ev_totals.dtype == np.int64
    np.testing.assert_array_equal(rep.ev_totals, bare)
    # every boundary drained: the final device counters are zero, and a
    # drained run's non-counter state matches the bare run bit-exactly
    assert not np.asarray(rep.states.core.events).any()
    gold = state_digest(_with_events_test(run.states))
    assert state_digest(_with_events_test(rep.states)) == gold


def _with_events_test(st):
    from go_libp2p_pubsub_tpu.serve.supervisor import _with_events

    return _with_events(st, jnp.zeros_like(st.core.events))


def test_ev_drain_totals_survive_resume(cell, tmp_path):
    """The drained totals ride checkpoint meta: a run stopped halfway
    and re-driven by a FRESH supervisor loses no counts."""
    step, make_args, template_fn, _net, _cfg = cell
    run = ensemble.WindowRunner(step, ROUNDS).run(template_fn(), make_args)
    bare = np.asarray(run.states.core.events, np.int64)
    root = str(tmp_path)
    half = Supervisor(step, make_args, template_fn, root,
                      _svc(n_dispatches=ROUNDS // 2,
                           drain_event_counters=True))
    half.run()
    full = Supervisor(step, make_args, template_fn, root,
                      _svc(drain_event_counters=True))
    rep = full.run()
    assert rep.resumed_from == ROUNDS // 2
    np.testing.assert_array_equal(rep.ev_totals, bare)


def test_ev_drain_requires_per_segment_checkpoints():
    with pytest.raises(ValueError, match="drain_event_counters"):
        _svc(drain_event_counters=True, checkpoint_every_segments=2)
