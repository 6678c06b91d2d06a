#!/usr/bin/env python
"""Supervised-service-loop gate (``make service-smoke``; docs/DESIGN.md
§17).

Drives the deterministic supervised cell
(``go_libp2p_pubsub_tpu.serve._child`` — chaos + health probes + folded
invariants) through the full failure catalog and asserts the round-17
recovery contract:

  1. **control** — an uninterrupted supervised run completes with zero
     recoveries, exactly ONE window compile per window shape (the
     one-compile-per-window-shape sentinel), and a fresh ``done``
     heartbeat.
  2. **kill/resume bit-exactness** — a child process is SIGKILLed at a
     RANDOMIZED (seeded) segment and crash site — including
     mid-checkpoint-write, where the tmp file is truncated before the
     kill — and the re-invoked run resumes from the rolling store and
     finishes with a final-state digest IDENTICAL to the control's.
  3. **corrupted-checkpoint fallback** — the store's newest snapshot is
     truncated on disk; ``restore_latest`` classifies it
     (``CheckpointCorrupt``) and falls back to the previous manifest
     entry.
  4. **seeded-NaN rollback-and-localize** — a NaN injected into a state
     leaf mid-segment trips the ``finite-state`` probe; the supervisor
     rolls back, the per-dispatch replay names EXACTLY the injected
     dispatch in the forensic bundle, and the recovered run still
     finishes digest-identical to the control.
  5. **heartbeat freshness** — the control's ``HEARTBEAT.json`` is
     ``done``, covers every dispatch, and was written during this gate
     run.
  6. **overhead ceiling** — warm-vs-warm, a supervised run (probes +
     folded invariants + heartbeat; end-of-run checkpoint) must cost at
     most ``SERVICE_SMOKE_OVERHEAD`` (default 10%) over a bare
     ``WindowRunner`` driving the SAME segmented window with the same
     folded invariants — the supervision machinery itself is what's
     being priced; the every-segment checkpoint cadence is measured
     alongside and reported in the artifact (durability price, not
     gated).
  7. **census** — the service loop is observational: with probes off it
     adds zero device ops, so the chaos-off compiled kernel census must
     still equal the on-image baseline (the chaos-report census leg,
     reused).

``SERVICE_SMOKE_UPDATE=1`` rewrites SERVICE_SMOKE.json from this run.
Env knobs: SERVICE_SMOKE_N / _ROUNDS / _SEG (shape),
SERVICE_SMOKE_SEED (kill-site draw), SERVICE_SMOKE_OVERHEAD,
SERVICE_SMOKE_TOL. CPU-only by contract; census under the gate PRNG.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_here))
sys.path.insert(0, _here)

import numpy as np  # noqa: E402

BASELINE_NAME = "SERVICE_SMOKE.json"
CHILD_N = 48
CHILD_ROUNDS = 32
CHILD_SEG = 8
OVERHEAD_N = 2048
OVERHEAD_ROUNDS = 32
OVERHEAD_SEG = 8
TIMING_REPS = 3
DEFAULT_OVERHEAD = 0.10
DEFAULT_TOL = 0.4
CHILD_TIMEOUT = 420


def child_cmd(root: str, *extra: str) -> list:
    return [sys.executable, "-m", "go_libp2p_pubsub_tpu.serve._child",
            "--root", root, "--n", str(CHILD_N),
            "--rounds", str(CHILD_ROUNDS), "--segment", str(CHILD_SEG),
            "--probes", "--invariants", "--report", *extra]


def run_child(repo_root: str, root: str, *extra: str):
    # the child pins the CPU itself (serve/_child.py main): this parent
    # has already initialised jax, and a chip belongs to one process
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               SERVE_CHILD_PRNG="unsafe_rbg")
    return subprocess.run(
        child_cmd(root, *extra), cwd=repo_root, env=env,
        capture_output=True, text=True, timeout=CHILD_TIMEOUT)


def read_final(root: str) -> dict:
    with open(os.path.join(root, "FINAL.json")) as f:
        return json.load(f)


def check_control(repo_root: str, work: str, t_gate0: float,
                  failures: list) -> dict | None:
    root = os.path.join(work, "control")
    proc = run_child(repo_root, root, "--fresh")
    if proc.returncode != 0:
        failures.append(
            f"control: supervised run failed rc={proc.returncode}: "
            f"{proc.stderr[-500:]}")
        return None
    final = read_final(root)
    if final["recoveries"] or final["retries"]:
        failures.append(
            f"control: clean run reported recoveries="
            f"{final['recoveries']} retries={final['retries']}")
    bad = {k: v for k, v in final["window_compiles"].items() if v != 1}
    if bad:
        failures.append(
            f"one-compile-per-window-shape: control window compiled "
            f"{final['window_compiles']} (every shape must be exactly 1)")
    # heartbeat freshness
    hb_path = os.path.join(root, "HEARTBEAT.json")
    try:
        with open(hb_path) as f:
            hb = json.load(f)
        if hb.get("status") != "done":
            failures.append(f"heartbeat: status {hb.get('status')!r}, "
                            "expected 'done'")
        if hb.get("dispatch") != CHILD_ROUNDS:
            failures.append(
                f"heartbeat: dispatch {hb.get('dispatch')} != "
                f"{CHILD_ROUNDS} (stale — not covering the whole run)")
        if not (t_gate0 <= float(hb.get("updated_at", 0))
                <= time.time() + 1):
            failures.append(
                "heartbeat: updated_at is outside this gate run "
                "(stale liveness file)")
    except (OSError, ValueError) as e:
        failures.append(f"heartbeat: unreadable ({e})")
    return final


def check_kill_resume(repo_root: str, work: str, control: dict,
                      seed: int, failures: list) -> dict:
    from go_libp2p_pubsub_tpu.serve import KILL_SITES

    rng = np.random.default_rng(seed)
    n_segments = CHILD_ROUNDS // CHILD_SEG
    seg = int(rng.integers(1, n_segments))
    site = str(rng.choice(list(KILL_SITES)))
    root = os.path.join(work, "kill")
    proc = run_child(repo_root, root, "--fresh",
                     "--kill-segment", str(seg), "--kill-site", site)
    if proc.returncode != -9 and proc.returncode != 137:
        failures.append(
            f"kill/resume: the child was not SIGKILLed "
            f"(rc={proc.returncode}) — the {site}@segment{seg} crash "
            "point never fired")
        return {"segment": seg, "site": site}
    proc = run_child(repo_root, root)
    if proc.returncode != 0:
        failures.append(
            f"kill/resume: resume failed rc={proc.returncode}: "
            f"{proc.stderr[-500:]}")
        return {"segment": seg, "site": site}
    final = read_final(root)
    if final["digest"] != control["digest"]:
        failures.append(
            f"kill/resume: resumed digest {final['digest'][:16]} != "
            f"control {control['digest'][:16]} (SIGKILL at {site}, "
            f"segment {seg}) — resume is NOT bit-exact")
    if final.get("resumed_from") is None:
        failures.append(
            f"kill/resume: the resumed run did not restore from the "
            f"store (resumed_from is null; kill was {site}@segment{seg})")
    return {"segment": seg, "site": site,
            "resumed_from": final.get("resumed_from"),
            "bit_exact": final.get("digest") == control["digest"]}


def check_corrupt_fallback(repo_root: str, work: str,
                           failures: list) -> dict:
    import jax

    jax.config.update("jax_platforms", "cpu")
    from go_libp2p_pubsub_tpu.serve import CheckpointStore, truncate_file
    from go_libp2p_pubsub_tpu.serve._child import build_cell

    store_dir = os.path.join(work, "control", "checkpoints")
    _step, _margs, template_fn, _net, _cfg = build_cell(
        CHILD_N, CHILD_ROUNDS, 7, 0.1)
    store = CheckpointStore(store_dir)
    latest = store.latest()
    if latest is None:
        failures.append("corrupt-fallback: control store has no entries")
        return {}
    truncate_file(os.path.join(store_dir, latest["file"]))
    st, entry = store.restore_latest(template_fn())
    if st is None or entry is None:
        failures.append(
            "corrupt-fallback: no snapshot restored after corrupting "
            "the latest — the manifest fallback is broken")
        return {"corrupted": latest["ordinal"]}
    if entry["ordinal"] >= latest["ordinal"]:
        failures.append(
            f"corrupt-fallback: restored ordinal {entry['ordinal']} is "
            f"not OLDER than the corrupted {latest['ordinal']}")
    return {"corrupted": latest["ordinal"],
            "fell_back_to": entry["ordinal"]}


def check_nan_recovery(repo_root: str, work: str, control: dict,
                       failures: list) -> dict:
    seg, disp = 2, 3
    expect_bad = seg * CHILD_SEG + disp
    root = os.path.join(work, "nan")
    proc = run_child(repo_root, root, "--fresh",
                     "--corrupt-segment", str(seg),
                     "--corrupt-dispatch", str(disp),
                     "--corrupt-leaf", "scores", "--corrupt-kind", "nan")
    if proc.returncode != 0:
        failures.append(
            f"nan-recovery: run failed rc={proc.returncode}: "
            f"{proc.stderr[-500:]}")
        return {}
    final = read_final(root)
    if final["recoveries"] != 1:
        failures.append(
            f"nan-recovery: {final['recoveries']} recoveries, expected "
            "exactly 1 (probe must trip once, then the segment recovers)")
    if final["first_bad"] != [expect_bad]:
        failures.append(
            f"nan-recovery: replay localized dispatch(es) "
            f"{final['first_bad']}, expected [{expect_bad}] — the "
            "rollback replay did not name the injected dispatch")
    if final["digest"] != control["digest"]:
        failures.append(
            "nan-recovery: recovered digest differs from control — "
            "transient corruption must recover bit-exact")
    bundle = (final.get("bundles") or [None])[0]
    if bundle:
        with open(os.path.join(bundle, "bundle.json")) as f:
            b = json.load(f)
        if "finite-state" not in b.get("window_probe_failures", []):
            failures.append(
                f"nan-recovery: bundle names {b.get('window_probe_failures')}"
                " — the finite-state probe should have tripped")
        if not b.get("nan_census"):
            failures.append("nan-recovery: bundle has an empty nan_census")
    else:
        failures.append("nan-recovery: no forensic bundle emitted")
    return {"first_bad": final.get("first_bad"),
            "recoveries": final.get("recoveries"),
            "bit_exact": final.get("digest") == control["digest"]}


def check_overhead(n: int, rounds: int, seg: int, failures: list,
                   ceiling: float) -> dict:
    import jax

    jax.config.update("jax_platforms", "cpu")
    import tempfile

    from go_libp2p_pubsub_tpu import ensemble
    from go_libp2p_pubsub_tpu.oracle import (
        HealthConfig,
        InvariantConfig,
        ScanInvariants,
    )
    from go_libp2p_pubsub_tpu.serve import (
        RetentionPolicy,
        ServiceConfig,
        Supervisor,
    )
    from go_libp2p_pubsub_tpu.serve._child import build_cell

    step, make_args, template_fn, net, cfg = build_cell(
        n, rounds, 7, 0.1)

    def spec():
        return ScanInvariants(
            "gossipsub", net, cfg,
            InvariantConfig(check_every=seg, delivery_window=16),
            batched=False)

    bare = ensemble.WindowRunner(step, rounds, invariants=spec(),
                                 segment_len=seg)

    def run_bare():
        t0 = time.perf_counter()
        bare.run(template_fn(), make_args)
        return time.perf_counter() - t0

    def make_sup(ckpt_every: int, root: str) -> Supervisor:
        svc = ServiceConfig(
            n_dispatches=rounds, segment_len=seg, health=HealthConfig(),
            retention=RetentionPolicy(keep_last=2),
            checkpoint_every_segments=ckpt_every, report_name=None)
        return Supervisor(step, make_args, template_fn, root, svc,
                          invariants=spec())

    tmp = tempfile.mkdtemp(prefix="service_smoke_ov_")
    sup = make_sup(rounds // seg, os.path.join(tmp, "loop"))
    sup_ck = make_sup(1, os.path.join(tmp, "durable"))

    def run_sup(s):
        t0 = time.perf_counter()
        s.run(fresh=True)
        return time.perf_counter() - t0

    # warm every program (window jit + probe jit), then min over reps
    run_bare(), run_sup(sup), run_sup(sup_ck)
    t_bare = min(run_bare() for _ in range(TIMING_REPS))
    t_sup = min(run_sup(sup) for _ in range(TIMING_REPS))
    t_durable = min(run_sup(sup_ck) for _ in range(TIMING_REPS))
    overhead = t_sup / t_bare - 1.0 if t_bare > 0 else float("inf")
    if overhead > ceiling:
        failures.append(
            f"overhead: supervised loop costs {100 * overhead:.1f}% over "
            f"the bare segmented WindowRunner (ceiling "
            f"{100 * ceiling:.0f}%; warm-vs-warm min over "
            f"{TIMING_REPS} reps: {t_sup:.3f}s vs {t_bare:.3f}s; "
            "SERVICE_SMOKE_OVERHEAD overrides)")
    return {
        "n_peers": n, "rounds": rounds, "segment_len": seg,
        "bare_rounds_per_sec": round(rounds / t_bare, 2),
        "supervised_rounds_per_sec": round(rounds / t_sup, 2),
        "durable_rounds_per_sec": round(rounds / t_durable, 2),
        "overhead_frac": round(overhead, 4),
        "checkpoint_cost_frac": round(t_durable / t_sup - 1.0, 4),
    }


def check_census(failures: list) -> dict:
    """The service loop adds zero device ops when probes are off: the
    chaos-off compiled census must equal the on-image baseline — the
    chaos_report census leg, reused verbatim."""
    from chaos_report import check_census as _chaos_census

    census = _chaos_census()
    if not census["equal"]:
        failures.append(
            f"census: chaos-off kernel census {census['total']} != "
            f"on-image baseline {census['on_image']} — the service loop "
            "must add zero device ops when probes are off")
    return census


def emit_artifact(res: dict, control: dict) -> None:
    from go_libp2p_pubsub_tpu.chaos import ChaosConfig
    from go_libp2p_pubsub_tpu.perf.artifacts import (
        BenchRecord,
        chaos_fingerprint,
        dump_record,
        execution_fingerprint,
    )

    ov = res["overhead"]
    rec = BenchRecord(
        metric=(f"service_loop_rounds_per_sec_n{ov['n_peers']}_"
                f"seg{ov['segment_len']}"),
        value=ov["supervised_rounds_per_sec"],
        unit="rounds/s",
        vs_baseline=0.0,
        schema=3,
        fingerprint={
            "chaos": chaos_fingerprint(ChaosConfig(loss_rate=0.1)),
            "execution": execution_fingerprint(
                scan=True, segment_rounds=ov["segment_len"],
                dispatches_per_window=1,
                rounds_per_dispatch=ov["segment_len"]),
            "service": control["service"],
        },
        extras={
            "bare_rounds_per_sec": ov["bare_rounds_per_sec"],
            "durable_rounds_per_sec": ov["durable_rounds_per_sec"],
            "overhead_frac": ov["overhead_frac"],
            "checkpoint_cost_frac": ov["checkpoint_cost_frac"],
            "kill": res["kill"],
            "nan": res["nan"],
        },
    )
    print(dump_record(rec), flush=True)


def check_baseline(root: str, ov: dict) -> list:
    path = os.path.join(root, BASELINE_NAME)
    if not os.path.exists(path) or os.environ.get("SERVICE_SMOKE_UPDATE"):
        return []
    with open(path) as f:
        base = json.load(f)
    if (int(base.get("n_peers", ov["n_peers"])) != ov["n_peers"]
            or int(base.get("rounds", ov["rounds"])) != ov["rounds"]
            or int(base.get("segment_len", ov["segment_len"]))
            != ov["segment_len"]):
        return []  # reshape run: committed rates are shape-specific
    tol = float(os.environ.get("SERVICE_SMOKE_TOL", DEFAULT_TOL))
    committed = base.get("supervised_rounds_per_sec")
    out = []
    if committed and ov["supervised_rounds_per_sec"] < tol * committed:
        out.append(
            f"supervised rate regressed: "
            f"{ov['supervised_rounds_per_sec']:.1f} < {tol:.2f} x "
            f"committed {committed:.1f} rounds/s ({BASELINE_NAME}; "
            "SERVICE_SMOKE_TOL overrides, SERVICE_SMOKE_UPDATE=1 "
            "rewrites)")
    return out


def write_baseline(root: str, ov: dict) -> str:
    path = os.path.join(root, BASELINE_NAME)
    doc = {
        "schema": 1,
        "note": (
            "supervised-service-loop smoke baseline (scripts/"
            "service_smoke.py); SERVICE_SMOKE_UPDATE=1 rewrites. "
            "supervised_* is the probes+invariants loop with an "
            "end-of-run checkpoint, bare_* the same segmented "
            "WindowRunner without supervision, durable_* the "
            "every-segment checkpoint cadence — all warm, min over "
            "reps on the gate machine. overhead_frac gates at "
            "SERVICE_SMOKE_OVERHEAD (default 0.10); the rate floor at "
            "SERVICE_SMOKE_TOL."),
        **ov,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="exit non-zero on any gate failure")
    ap.add_argument("--no-census", action="store_true",
                    help="skip the chaos-off kernel-census leg")
    args = ap.parse_args(argv)

    import tempfile

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_default_prng_impl", "unsafe_rbg")
    from go_libp2p_pubsub_tpu.compile_cache import enable_persistent_cache
    from go_libp2p_pubsub_tpu.perf.regress import repo_root

    root = repo_root()
    enable_persistent_cache(os.path.join(root, ".jax_cache"))

    n_ov = int(os.environ.get("SERVICE_SMOKE_N", OVERHEAD_N))
    rounds_ov = int(os.environ.get("SERVICE_SMOKE_ROUNDS",
                                   OVERHEAD_ROUNDS))
    seg_ov = int(os.environ.get("SERVICE_SMOKE_SEG", OVERHEAD_SEG))
    seed = int(os.environ.get("SERVICE_SMOKE_SEED", 0))
    ceiling = float(os.environ.get("SERVICE_SMOKE_OVERHEAD",
                                   DEFAULT_OVERHEAD))

    failures: list = []
    t_gate0 = time.time()
    work = tempfile.mkdtemp(prefix="service_smoke_")
    control = check_control(root, work, t_gate0, failures)
    res = {"work": work}
    if control is not None:
        res["kill"] = check_kill_resume(root, work, control, seed,
                                        failures)
        res["nan"] = check_nan_recovery(root, work, control, failures)
        res["corrupt_fallback"] = check_corrupt_fallback(root, work,
                                                         failures)
    else:
        res["kill"] = res["nan"] = res["corrupt_fallback"] = {}
    res["overhead"] = check_overhead(n_ov, rounds_ov, seg_ov, failures,
                                     ceiling)
    if not args.no_census:
        res["census"] = check_census(failures)
        if res["census"].get("seeded"):
            print("service-smoke NOTE: on-image census baseline was "
                  "seeded by this run", file=sys.stderr)
    if control is not None:
        emit_artifact(res, control)
    failures += check_baseline(root, res["overhead"])
    if os.environ.get("SERVICE_SMOKE_UPDATE") and not failures:
        print(f"wrote {write_baseline(root, res['overhead'])}")

    summary = {"service_smoke": "PASS" if not failures else "FAIL",
               **{k: v for k, v in res.items() if k != "work"},
               "failures": failures}
    if args.smoke and failures:
        for f in failures:
            print(f"service-smoke FAIL: {f}", file=sys.stderr)
        print(json.dumps(summary))
        return 1
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
