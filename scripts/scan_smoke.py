#!/usr/bin/env python
"""Whole-run-window gate (``make scan-smoke``; docs/DESIGN.md §14).

Builds the smoke-shape bench window — N=12.5k peers, phase engine at
r=16, 64 rounds — with ALL THREE observability planes enabled (i.i.d.
chaos link flaps, the telemetry panel recorder, the folded invariant
oracle) and asserts the round-14 whole-run-compilation contract:

  1. **one dispatch** — the entire window (4 phase dispatches' worth of
     rounds, checks included) executes as ONE XLA program invocation:
     the window jit's compile-cache grows by exactly 1 AND the window
     callable is entered exactly once, under
     ``jax.transfer_guard('disallow')`` (publish schedules and
     invariant due rows are materialized on device beforehand; the
     violation masks and telemetry panel ride the program).
  2. **observability intact** — zero invariant violations, and the
     telemetry panel reconciles against the drained counters
     bit-for-bit (the §11 anchor, now inside a scanned window).
  3. **measurably faster** — warm-vs-warm against the committed
     per-dispatch path (the same step driven phase-by-phase from
     Python with the per-dispatch InvariantHook): the scanned window
     must be at least SCAN_SMOKE_MIN_SPEEDUP (default 1.0) times the
     per-dispatch rate, and at least SCAN_SMOKE_TOL × the committed
     SCAN_SMOKE.json floor (both rates and the implied
     per-dispatch-overhead are recorded in the artifact).
  4. **projection refresh** — the v5e-8 projection recomputed from the
     committed round-5 shard rates with the new
     ``dispatch_overhead_ms`` term parameterized on the overhead this
     run measured, gated on the 2-D (sims × peers) multichip dryrun
     artifact (MULTICHIP_r06.json — scripts/mesh2d_dryrun.py).

``SCAN_SMOKE_UPDATE=1`` rewrites SCAN_SMOKE.json from this run.
CPU-only by contract, bench PRNG, persistent compile cache — the
perf-smoke gate policy. Shape knobs: SCAN_SMOKE_N / _R / _ROUNDS.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_here))

import numpy as np  # noqa: E402

BASELINE_NAME = "SCAN_SMOKE.json"
MULTICHIP_2D_NAME = "MULTICHIP_r06.json"
SMOKE_N = 12_500
SMOKE_R = 16
SMOKE_ROUNDS = 64
SMOKE_LOSS = 0.05
CHECK_EVERY = 2          # invariant checks per window: dispatches 2 and 4
TIMING_REPS = 3
#: floor: fraction of the committed scanned rate a fresh run must reach
DEFAULT_TOL = 0.4
#: the acceptance bar: scanned must beat the per-dispatch path
DEFAULT_MIN_SPEEDUP = 1.0


def build_cell(n: int, r: int, rounds: int, loss: float, seed: int = 0):
    """The bench workload (ring-lattice d=8, live scoring, honest-net
    weights) with chaos + telemetry enabled — build_bench's decision
    table plus the fault generator the bench build deliberately lacks."""
    import dataclasses as _dc

    from go_libp2p_pubsub_tpu import graph
    from go_libp2p_pubsub_tpu.chaos import ChaosConfig
    from go_libp2p_pubsub_tpu.config import (
        GossipSubParams,
        PeerScoreThresholds,
    )
    from go_libp2p_pubsub_tpu.models.gossipsub import (
        GossipSubConfig,
        GossipSubState,
    )
    from go_libp2p_pubsub_tpu.models.gossipsub_phase import (
        make_gossipsub_phase_step,
    )
    from go_libp2p_pubsub_tpu.perf.sweep import bench_score_params
    from go_libp2p_pubsub_tpu.state import Net
    from go_libp2p_pubsub_tpu.telemetry import TelemetryConfig

    topo = graph.ring_lattice(n, d=8)
    net = Net.build(topo, graph.subscribe_all(n, 1))
    _tp, sp = bench_score_params("default", 1)
    params = _dc.replace(GossipSubParams(), flood_publish=False)
    cfg = GossipSubConfig.build(
        params, PeerScoreThresholds(), score_enabled=True,
        heartbeat_every=r, chaos=ChaosConfig(loss_rate=loss),
    )
    # live counters: the telemetry reconciliation anchor needs them
    cfg = _dc.replace(cfg, count_events=True, fanout_slots=0)
    tcfg = TelemetryConfig(rows=rounds // r)
    st0 = GossipSubState.init(net, 64, cfg, score_params=sp, seed=seed,
                              telemetry=tcfg)
    step = make_gossipsub_phase_step(cfg, net, r, score_params=sp,
                                     telemetry=tcfg)

    def fresh():
        return GossipSubState.init(net, 64, cfg, score_params=sp,
                                   seed=seed, telemetry=tcfg)

    return net, cfg, st0, step, fresh


def run_gate(n: int, r: int, rounds: int, loss: float) -> dict:
    import jax
    import jax.numpy as jnp

    from go_libp2p_pubsub_tpu.driver import make_window
    from go_libp2p_pubsub_tpu.oracle import invariants as oracle_inv
    from go_libp2p_pubsub_tpu.telemetry import reconcile

    assert rounds % r == 0
    d = rounds // r
    failures: list[str] = []
    net, cfg, st0, step, fresh = build_cell(n, r, rounds, loss)

    rng = np.random.default_rng(0)
    po = jnp.asarray(rng.integers(0, n, size=(d, r, 4)).astype(np.int32))
    pt = jnp.asarray(np.zeros((d, r, 4), np.int32))
    pv = jnp.asarray(np.ones((d, r, 4), bool))

    spec = oracle_inv.ScanInvariants(
        "phase", net, cfg,
        oracle_inv.InvariantConfig(check_every=CHECK_EVERY,
                                   delivery_window=24),
        batched=False, rounds_per_step=r,
    )
    due = spec.precompute(d)
    window = make_window(step, heartbeat=[True], check=spec.check,
                         check_every=CHECK_EVERY)

    def cache_size():
        try:
            return int(window._cache_size())
        except Exception:  # pragma: no cover
            return None

    # --- the acceptance run: ONE dispatch, guarded window ------------
    # (one INVOCATION is by construction — the whole run is the single
    # window call below; the compile-count sentinel is what verifies
    # the program really covers all of it)
    before = cache_size()
    st_guarded = fresh()
    with jax.transfer_guard("disallow"):
        st_fin, ys = window(st_guarded, (po, pt, pv), due)
        jax.block_until_ready((st_fin, ys))
    after = cache_size()
    compiles = -1 if before is None or after is None else after - before
    if compiles not in (-1, 1):
        failures.append(
            f"one-dispatch: the window compiled {compiles} times "
            "(expected exactly 1 — chaos + telemetry + checker are one "
            "program)")
    rep = spec.report(ys["ok"])
    if not rep.all_ok:
        failures.append(
            f"invariants: {rep.violated}/{rep.checked} property "
            f"evaluations failed inside the window: {rep.violations(8)}")
    if rep.n_checks != d // CHECK_EVERY:
        failures.append(
            f"invariants: {rep.n_checks} checks recorded, expected "
            f"{d // CHECK_EVERY}")
    panel = np.asarray(st_fin.core.telem.panel)
    mism = reconcile(panel, np.asarray(st_fin.core.events))
    if mism:
        failures.append(
            "telemetry: drain-vs-timeline reconciliation failed inside "
            "the scanned window: " + "; ".join(mism[:4]))

    # --- warm-vs-warm: scanned window vs the per-dispatch path -------
    # the committed pre-round-14 execution: one program per phase from
    # Python, the invariant checks as separate hook dispatches
    hook = oracle_inv.InvariantHook(
        "phase", net, cfg,
        oracle_inv.InvariantConfig(check_every=CHECK_EVERY,
                                   delivery_window=24),
        batched=False, rounds_per_step=r,
    )
    hook.precompute(d)

    def run_loop():
        st = fresh()
        hook.reset()
        t0 = time.perf_counter()
        for p in range(d):
            st = step(st, po[p], pt[p], pv[p], do_heartbeat=True)
            hook.on_step(p, st)
        jax.block_until_ready(st)
        return time.perf_counter() - t0

    def run_scan():
        st = fresh()
        t0 = time.perf_counter()
        st, ys_ = window(st, (po, pt, pv), due)
        jax.block_until_ready((st, ys_))
        return time.perf_counter() - t0

    run_loop()  # warm the per-dispatch program (+ hook checker jit)
    pairs = [(run_scan(), run_loop()) for _ in range(TIMING_REPS)]
    t_scan = min(p[0] for p in pairs)
    t_loop = min(p[1] for p in pairs)
    scan_rate = rounds / t_scan
    loop_rate = rounds / t_loop
    speedup = scan_rate / loop_rate if loop_rate else float("inf")
    # the measured per-dispatch overhead the projection's new term is
    # parameterized on: the warm time delta amortized over the loop's
    # extra dispatches (d phase programs + d/ce checker programs vs 1)
    extra_dispatches = d + d // CHECK_EVERY - 1
    overhead_ms = max(0.0, (t_loop - t_scan) * 1000.0 / extra_dispatches)
    return {
        "failures": failures,
        "n_peers": n,
        "rounds_per_phase": r,
        "rounds": rounds,
        "loss": loss,
        "check_every": CHECK_EVERY,
        "dispatches_per_window": 1,
        "window_compiles": compiles,
        "invariant_checks": rep.n_checks,
        "scanned_rounds_per_sec": round(scan_rate, 2),
        "per_dispatch_rounds_per_sec": round(loop_rate, 2),
        "speedup": round(speedup, 4),
        "dispatch_overhead_ms": round(overhead_ms, 4),
        "window_dispatches_per_sec": round(1.0 / t_scan, 4),
    }


def refresh_projection(root: str, res: dict) -> dict:
    """The v5e-8 projection recomputed with the dispatch term: the
    round-5 shard rates + the 2-D multichip dryrun gate + the overhead
    this run measured, for the scanned (1 dispatch/window) vs
    per-dispatch (1/r) execution shapes."""
    from go_libp2p_pubsub_tpu.perf.projection import project_from_artifacts

    bench = None  # the round-5 headline cell (projection.ROUND5_SHARD_RATES_R16)
    multi2d = os.path.join(root, MULTICHIP_2D_NAME)
    if not os.path.exists(multi2d):
        multi2d = os.path.join(root, "MULTICHIP_r05.json")
    if not os.path.exists(multi2d):
        return {"skipped": "no committed multichip artifact"}
    ov = res["dispatch_overhead_ms"]
    try:
        scanned = project_from_artifacts(
            bench, multi2d, dispatch_overhead_ms=ov,
            dispatches_per_round=1.0 / res["rounds"])
        # per-dispatch = one program per phase at the PROJECTION's own
        # cadence (the round-5 shard table is r=16), not this run's r
        per_dispatch = project_from_artifacts(
            bench, multi2d, dispatch_overhead_ms=ov,
            dispatches_per_round=1.0 / scanned.rounds_per_phase)
    except ValueError as e:
        # a committed-but-failed dryrun (ok=false) must surface as a
        # gate failure, not an unhandled traceback
        return {"error": str(e),
                "multichip_artifact": os.path.basename(multi2d)}
    return {
        "multichip_artifact": os.path.basename(multi2d),
        "dispatch_overhead_ms": ov,
        "scanned": scanned.summary(),
        "per_dispatch": per_dispatch.summary(),
    }


def emit_artifact(res: dict, projection: dict) -> None:
    from go_libp2p_pubsub_tpu.perf.artifacts import (
        BenchRecord,
        chaos_fingerprint,
        dump_record,
        execution_fingerprint,
    )
    from go_libp2p_pubsub_tpu.chaos import ChaosConfig

    rec = BenchRecord(
        metric=(f"scan_window_delivery_rounds_per_sec_"
                f"n{res['n_peers']}_phase{res['rounds_per_phase']}"),
        value=res["scanned_rounds_per_sec"],
        unit="delivery-rounds/s",
        vs_baseline=0.0,
        schema=3,
        fingerprint={
            "chaos": chaos_fingerprint(
                ChaosConfig(loss_rate=res["loss"])),
            "execution": execution_fingerprint(
                scan=True, segment_rounds=res["rounds"],
                dispatches_per_window=res["dispatches_per_window"],
                rounds_per_dispatch=res["rounds"],
                check_every=res["check_every"],
            ),
        },
        extras={
            "per_dispatch_rounds_per_sec":
                res["per_dispatch_rounds_per_sec"],
            "speedup": res["speedup"],
            "dispatch_overhead_ms": res["dispatch_overhead_ms"],
            "projection": projection,
        },
    )
    print(dump_record(rec), flush=True)


def check_baseline(root: str, res: dict) -> list[str]:
    path = os.path.join(root, BASELINE_NAME)
    if not os.path.exists(path) or os.environ.get("SCAN_SMOKE_UPDATE"):
        return []
    with open(path) as f:
        base = json.load(f)
    if (int(base.get("n_peers", res["n_peers"])) != res["n_peers"]
            or int(base.get("rounds", res["rounds"])) != res["rounds"]
            or int(base.get("rounds_per_phase", res["rounds_per_phase"]))
            != res["rounds_per_phase"]):
        return []  # reshape run: the committed rates are shape-specific
    tol = float(os.environ.get("SCAN_SMOKE_TOL", DEFAULT_TOL))
    committed = base.get("scanned_rounds_per_sec")
    out = []
    if committed and res["scanned_rounds_per_sec"] < tol * committed:
        out.append(
            f"scanned window rate regressed: "
            f"{res['scanned_rounds_per_sec']:.1f} < {tol:.2f} x committed "
            f"{committed:.1f} rounds/s ({BASELINE_NAME}; SCAN_SMOKE_TOL "
            "overrides, SCAN_SMOKE_UPDATE=1 rewrites)")
    return out


def write_baseline(root: str, res: dict, projection: dict) -> str:
    path = os.path.join(root, BASELINE_NAME)
    doc = {
        "schema": 1,
        "note": (
            "whole-run-window smoke baseline (scripts/scan_smoke.py); "
            "SCAN_SMOKE_UPDATE=1 rewrites. scanned_* is the ONE-dispatch "
            "window (chaos + telemetry + folded invariants), "
            "per_dispatch_* the same build driven phase-by-phase from "
            "Python with the hook — both warm, min over reps on the "
            "gate machine. dispatch_overhead_ms is the measured per-"
            "dispatch host cost the projection's round-14 term uses."),
        **{k: res[k] for k in (
            "n_peers", "rounds_per_phase", "rounds", "check_every",
            "scanned_rounds_per_sec", "per_dispatch_rounds_per_sec",
            "speedup", "dispatch_overhead_ms",
            "window_dispatches_per_sec")},
        "projection": projection,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="exit non-zero on any gate failure")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_default_prng_impl", "unsafe_rbg")
    from go_libp2p_pubsub_tpu.compile_cache import enable_persistent_cache
    from go_libp2p_pubsub_tpu.perf.regress import repo_root

    root = repo_root()
    enable_persistent_cache(os.path.join(root, ".jax_cache"))

    n = int(os.environ.get("SCAN_SMOKE_N", SMOKE_N))
    r = int(os.environ.get("SCAN_SMOKE_R", SMOKE_R))
    rounds = int(os.environ.get("SCAN_SMOKE_ROUNDS", SMOKE_ROUNDS))
    loss = float(os.environ.get("SCAN_SMOKE_LOSS", SMOKE_LOSS))

    res = run_gate(n, r, rounds, loss)
    failures = res.pop("failures")
    min_speedup = float(os.environ.get("SCAN_SMOKE_MIN_SPEEDUP",
                                       DEFAULT_MIN_SPEEDUP))
    if res["speedup"] < min_speedup:
        failures.append(
            f"scanned window is not faster than the per-dispatch path: "
            f"{res['scanned_rounds_per_sec']:.1f} vs "
            f"{res['per_dispatch_rounds_per_sec']:.1f} rounds/s "
            f"(speedup {res['speedup']:.3f} < {min_speedup}; warm-vs-warm"
            ", min over reps)")

    projection = refresh_projection(root, res)
    if "error" in projection:
        failures.append(
            f"projection refresh failed on "
            f"{projection['multichip_artifact']}: {projection['error']} "
            "(re-run scripts/mesh2d_dryrun.py --write)")
    elif "skipped" not in projection:
        mc = projection["multichip_artifact"]
        if mc != MULTICHIP_2D_NAME:
            failures.append(
                f"projection fell back to {mc} — the 2-D (sims x peers) "
                f"dryrun artifact {MULTICHIP_2D_NAME} is missing or not "
                "ok (run scripts/mesh2d_dryrun.py)")
    emit_artifact(res, projection)
    failures += check_baseline(root, res)
    if os.environ.get("SCAN_SMOKE_UPDATE") and not failures:
        print(f"wrote {write_baseline(root, res, projection)}")

    summary = {"scan_smoke": "PASS" if not failures else "FAIL", **res,
               "failures": failures}
    if args.smoke and failures:
        for f in failures:
            print(f"scan-smoke FAIL: {f}", file=sys.stderr)
        print(json.dumps(summary))
        return 1
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
