"""The deterministic supervised cell the crash-recovery tests SIGKILL.

``python -m go_libp2p_pubsub_tpu.serve._child --root DIR ...`` builds a
small gossipsub workload (fixed topology / schedule / seeds — every
process with the same arguments sees the identical run) and drives the
supervisor over it. The parent process kills it at a scheduled point
(via the in-process FaultPlan, so the kill lands EXACTLY at the crash
window under test, including mid-checkpoint-write), then re-invokes the
same command line: the resumed run must finish bit-exact vs an
uninterrupted control, witnessed by the ``state_digest`` the child
writes to ``<root>/FINAL.json`` on completion.

Used by tests/test_serve.py and scripts/service_smoke.py; not a user
entry point.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys


def build_cell(n: int, rounds: int, seed: int, loss: float,
               pub_width: int = 2, msg_slots: int = 64):
    """The fixed workload: ring of gossipsub peers under i.i.d. chaos,
    live scoring + event counters (the probes' food), a seeded publish
    schedule. Returns ``(step, make_args, template_fn, net, cfg)``."""
    import jax.numpy as jnp
    import numpy as np

    from go_libp2p_pubsub_tpu import graph
    from go_libp2p_pubsub_tpu.chaos import ChaosConfig
    from go_libp2p_pubsub_tpu.config import (
        GossipSubParams,
        PeerScoreThresholds,
    )
    from go_libp2p_pubsub_tpu.models.gossipsub import (
        GossipSubConfig,
        GossipSubState,
        make_gossipsub_step,
    )
    from go_libp2p_pubsub_tpu.perf.sweep import bench_score_params
    from go_libp2p_pubsub_tpu.state import Net

    # the oracle plane's known-good gossipsub cell (tests/
    # test_invariants.py, scripts/invariant_report.py): per-round
    # heartbeat cadence, bench score params — all 18 properties hold
    topo = graph.random_connect(n, d=4, seed=seed)
    net = Net.build(topo, graph.subscribe_all(n, 1))
    cfg = GossipSubConfig.build(
        GossipSubParams(D=3, Dlo=2, Dhi=4, Dscore=2, Dout=1,
                        history_length=6, history_gossip=4),
        PeerScoreThresholds(), score_enabled=True,
        chaos=ChaosConfig(loss_rate=loss) if loss > 0 else None,
    )
    cfg = dataclasses.replace(cfg, count_events=True)
    sp = bench_score_params("default", 1)[1]
    step = make_gossipsub_step(cfg, net, score_params=sp)

    rng = np.random.default_rng(seed + 1)
    po_all = rng.integers(0, n, size=(rounds, pub_width)).astype(np.int32)
    pt_all = np.zeros((rounds, pub_width), np.int32)
    pv_all = np.ones((rounds, pub_width), bool)

    def make_args(i):
        return (jnp.asarray(po_all[i]), jnp.asarray(pt_all[i]),
                jnp.asarray(pv_all[i]))

    def template_fn():
        return GossipSubState.init(net, msg_slots, cfg, score_params=sp,
                                   seed=seed)

    return step, make_args, template_fn, net, cfg


def build_supervisor(args) -> "object":
    from go_libp2p_pubsub_tpu.oracle import (
        HealthConfig,
        InvariantConfig,
        ScanInvariants,
    )
    from go_libp2p_pubsub_tpu.serve import (
        FaultPlan,
        RetentionPolicy,
        ServiceConfig,
        Supervisor,
    )

    step, make_args, template_fn, net, cfg = build_cell(
        args.n, args.rounds, args.seed, args.loss)
    invariants = None
    if args.invariants:
        invariants = ScanInvariants(
            "gossipsub", net, cfg,
            InvariantConfig(check_every=args.check_every,
                            delivery_window=16),
            batched=False)
    health = None
    if args.probes:
        health = HealthConfig(delivery_floor=args.floor)
    faults = None
    if (args.kill_segment is not None or args.fail_segment is not None
            or args.corrupt_segment is not None):
        faults = FaultPlan(
            kill_segment=args.kill_segment,
            kill_site=args.kill_site,
            fail_dispatches=({args.fail_segment: args.fail_count}
                             if args.fail_segment is not None else {}),
            corrupt_segment=args.corrupt_segment,
            corrupt_dispatch=args.corrupt_dispatch,
            corrupt_leaf=args.corrupt_leaf,
            corrupt_kind=args.corrupt_kind,
            corrupt_max_fires=args.corrupt_max_fires,
        )
    svc = ServiceConfig(
        n_dispatches=args.rounds,
        segment_len=args.segment,
        health=health,
        retention=RetentionPolicy(keep_last=args.keep_last,
                                  keep_every=args.keep_every),
        checkpoint_every_segments=args.checkpoint_every,
        max_retries=args.max_retries,
        backoff_base_s=0.01,
        report_name="service" if args.report else None,
    )
    return Supervisor(step, make_args, template_fn, args.root, svc,
                      invariants=invariants, faults=faults)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", required=True)
    ap.add_argument("--n", type=int, default=48)
    ap.add_argument("--rounds", type=int, default=32)
    ap.add_argument("--segment", type=int, default=8)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--loss", type=float, default=0.1)
    ap.add_argument("--invariants", action="store_true")
    ap.add_argument("--check-every", type=int, default=4)
    ap.add_argument("--probes", action="store_true")
    ap.add_argument("--floor", type=int, default=0)
    ap.add_argument("--keep-last", type=int, default=3)
    ap.add_argument("--keep-every", type=int, default=0)
    ap.add_argument("--checkpoint-every", type=int, default=1)
    ap.add_argument("--max-retries", type=int, default=3)
    ap.add_argument("--report", action="store_true")
    ap.add_argument("--fresh", action="store_true",
                    help="ignore existing checkpoints (the control run)")
    ap.add_argument("--kill-segment", type=int, default=None)
    ap.add_argument("--kill-site", default="post-segment")
    ap.add_argument("--fail-segment", type=int, default=None)
    ap.add_argument("--fail-count", type=int, default=1)
    ap.add_argument("--corrupt-segment", type=int, default=None)
    ap.add_argument("--corrupt-dispatch", type=int, default=-1)
    ap.add_argument("--corrupt-leaf", default="scores")
    ap.add_argument("--corrupt-kind", default="nan")
    ap.add_argument("--corrupt-max-fires", type=int, default=2)
    args = ap.parse_args(argv)

    import jax

    # A CPU-ONLY test cell, by construction: its launchers
    # (tests/test_serve.py, scripts/service_smoke.py) start it from a
    # parent that has already initialised jax, and an accelerator
    # belongs to one process at a time — a child that needed the chip
    # its parent holds would fail or hang. Both sides pin the CPU, so
    # the pair is correct; never point this cell at a chip (the served
    # path on the chip is chip_smoke.py's in-process phase).
    jax.config.update("jax_platforms", "cpu")
    # the parent decides the PRNG impl (service_smoke pins the gate
    # PRNG so its in-process legs share the children's key shapes)
    impl = os.environ.get("SERVE_CHILD_PRNG")
    if impl:
        jax.config.update("jax_default_prng_impl", impl)
    from go_libp2p_pubsub_tpu.compile_cache import enable_persistent_cache

    enable_persistent_cache()

    from go_libp2p_pubsub_tpu.serve import ServiceHalted, state_digest

    sup = build_supervisor(args)
    try:
        report = sup.run(fresh=args.fresh)
    except ServiceHalted as e:
        out = {"status": "halted", "error": str(e),
               "bundle": (e.bundle or {}).get("path")}
        with open(os.path.join(args.root, "FINAL.json"), "w") as f:
            json.dump(out, f)
        print(json.dumps(out))
        return 3
    out = {
        "status": "done",
        "digest": state_digest(report.states),
        "segments": report.segments,
        "recoveries": report.recoveries,
        "retries": report.retries,
        "resumed_from": report.resumed_from,
        "degradations": report.degradations,
        "window_compiles": report.window_compiles,
        "checkpoints": [e["ordinal"] for e in report.checkpoints],
        "bundles": [b["path"] for b in report.bundles],
        "first_bad": [b["first_bad_dispatch"] for b in report.bundles],
        "service": report.fingerprint(),
    }
    with open(os.path.join(args.root, "FINAL.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
