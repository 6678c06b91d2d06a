"""Bench workloads + the declarative (config × N × r) sweep runner.

This module owns the workload the numbers are measured on: ``build_bench``
(moved here from bench.py, which now re-exports it) builds the exact
BASELINE.json configurations, ``workload_fingerprint`` derives the
schema-v2 self-description from the same decision table, and
``run_sweep`` drives a declarative shard/cadence grid — e.g. the eth2
{12.5k, 25k, 50k} shard table the round-5 review asked for:

    python -m go_libp2p_pubsub_tpu.perf.sweep --config eth2 \\
        --n 12500,25000,50000 --r 16

Each sweep cell is emitted as one schema-v2 JSON line (perf.artifacts),
so sweep output is directly comparable against the committed BENCH_r*
trajectory.

jax is imported inside functions (CLI entry points configure platform /
PRNG first — see main()).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
import time
import typing

import numpy as np

#: publish batch width every bench/sweep cell uses ([R, 4] schedules)
PUBS_PER_ROUND = 4

#: incremental membership planes are a narrow-universe optimization
#: (gossipsub_phase.py round-4 addendum 4)
INCR_MEMBERS_MAX_TOPICS = 8


def bench_score_params(config: str, n_topics: int):
    """The per-config score parameterization (single source for the
    workload builder AND the fingerprint).

    Returns (TopicScoreParams, PeerScoreParams)."""
    from ..config import PeerScoreParams, TopicScoreParams

    if config == "sybil":
        # deficit penalties on: the sybils are what scoring must catch
        # (the benchmark's sybil-50k differs: validation capacity 32 not
        # 8, time_in_mesh_cap 4, origins uniform over sybils too)
        tp = TopicScoreParams(
            mesh_message_deliveries_weight=-0.5,
            mesh_message_deliveries_threshold=4.0,
            mesh_message_deliveries_activation=10.0,
            mesh_message_deliveries_window=2.0,
        )
    else:
        tp = TopicScoreParams(
            mesh_message_deliveries_weight=0.0,  # deficit off: honest net
            mesh_failure_penalty_weight=0.0,
            # honest net continued: every publish is valid (pv all-True),
            # so P4 provably never fires — zero weight lets the phase
            # engine's static elision drop the [N,K,W] trans-accumulation
            # plane (sybil keeps the default weight: its adversary vector
            # is what P4 exists to catch)
            invalid_message_deliveries_weight=0.0,
        )
    sp = PeerScoreParams(
        topics={t: tp for t in range(n_topics)},
        skip_app_specific=True,
        behaviour_penalty_weight=-1.0,
        behaviour_penalty_threshold=1.0,
        behaviour_penalty_decay=0.9,
    )
    return tp, sp


def bench_wire_coalesced(wire_coalesced: bool | None = None) -> bool:
    """The bench's engine-path switch (round-7 A/B knob): the coalesced
    stacked wire exchange is the default; BENCH_WIRE_COALESCED=0 selects
    the legacy per-plane path. Single source for the workload builder
    AND the fingerprint."""
    if wire_coalesced is not None:
        return bool(wire_coalesced)
    return os.environ.get("BENCH_WIRE_COALESCED", "1") != "0"


def bench_edge_layout(edge_layout: str | None = None) -> str:
    """The bench's edge-exchange layout (round-15 A/B knob): "dense"
    (the default — the padded [N, K] involution, census-identical to
    every prior round) or "csr" (the capacity-bounded flat edge space,
    ops/csr.py). BENCH_EDGE_LAYOUT overrides. Single source for the
    workload builder AND the fingerprint."""
    if edge_layout is None:
        edge_layout = os.environ.get("BENCH_EDGE_LAYOUT", "dense")
    if edge_layout not in ("dense", "csr"):
        raise ValueError(
            f"BENCH_EDGE_LAYOUT must be 'dense' or 'csr', got {edge_layout!r}"
        )
    return edge_layout


class BenchCell(typing.NamedTuple):
    """One built bench workload. The first four fields are
    :func:`build_bench`'s return; ``net`` / ``cfg`` are what the
    invariant oracle is built from, and ``fresh()`` makes another
    initial state, placed like ``state`` (the served path's restore
    template — steps and windows DONATE the state they are given)."""

    state: object
    step: object
    n_topics: int
    honest: object
    net: object
    cfg: object
    fresh: object


def build_bench(*args, **kwargs):
    """``(state, step, n_topics, honest)`` of :func:`bench_cell` (same
    arguments) — the form every gate, audit and test unpacks."""
    return bench_cell(*args, **kwargs)[:4]


def bench_cell(n_peers: int, msg_slots: int, seed: int = 0, config: str = "default",
               heartbeat_every: int = 1, rounds_per_phase: int = 1,
               wire_coalesced: bool | None = None,
               telemetry=None, count_events: bool | None = None,
               edge_layout: str | None = None,
               lift_scores: bool = False,
               fused: bool = False,
               devices=None) -> BenchCell:
    """Build the :class:`BenchCell` for a BENCH_CONFIG:

    default — GossipSub v1.1, single topic, live scoring (the BASELINE.json
              north-star workload the driver measures)
    eth2    — 100k-peer Eth2 attestation-subnet geometry: 64 topics, each
              peer subscribed to 2 random subnets (BASELINE.json config #5),
              on ``graph.subnet_connect`` (10 random dials a peer plus 5
              to co-subscribers in each of its subnets): the deployment
              ``benchmark/configs/eth2-100k.json`` measures, on which a
              publish DELIVERS (every subscriber of the topic, where the
              origin subscribes it or has a neighbour that does: PARITY.md
              "eth2 subnets: 64 topics" row, and the benchmark's
              ``topic_undelivered``). Until PR 31 this cell sat on the
              banded ring lattice, where a topic's 3%-density induced
              subgraph fragments and publishes stay in their segment:
              every eth2 rate in BASELINE.md and BENCH_r*.json is that
              form's (K=16, rolls), a throughput number of a network
              that does not deliver, and is not comparable with this one
              (K is the draw's largest degree, near 65 at 100k)
    sybil   — 20% sybil attackers (control-plane-only peers that never
              forward data), peer gater + deficit scoring enabled
              (BASELINE.json config #4; default BENCH_N 50k)

    ``rounds_per_phase`` > 1 builds the multi-round phase engine
    (models/gossipsub_phase.py): r delivery rounds per dispatch, control
    once per phase — the reference's continuous-delivery / 1 Hz-heartbeat
    timing shape (gossipsub.go:1278-1301).

    ``telemetry`` (a telemetry.TelemetryConfig) builds the TELEMETRY-ON
    variant of the same workload: the state carries the panel plane and
    the step records one row per round/phase (docs/DESIGN.md §11).
    ``count_events`` overrides the tracer-detached default (False);
    telemetry's EV columns only move when counters are live, so
    telemetry builds that reconcile pass ``count_events=True``.

    ``lift_scores=True`` (round 16, docs/DESIGN.md §16) builds the
    LIFTED variant: the step takes a trailing traced
    ``score.params.ScoreParams`` plane — the same workload, with the
    score weights/thresholds as a run-time input (one compile across
    weight sets; bit-exact vs the static build at matched values).

    ``fused=True`` (round 21, docs/DESIGN.md §21) builds the FUSED
    variant: sort-composite top-k/random selection and the
    capacity-bounded CSR segmented scan replace the pairwise-rank /
    log2(E) forms — bit-exact, fewer hbm bytes per round. The flag is
    threaded to both ``Net.build`` and ``GossipSubConfig.build`` (they
    must match; prepare_step_consts enforces it).

    ``devices`` are the devices the peer axis is sharded over (default:
    every visible device). More than one device that does not divide
    ``n_peers`` raises — a state left on one device would be measured
    under the name of a sharded run.
    """
    import dataclasses as _dc

    import jax

    from .. import graph
    from ..config import GossipSubParams, PeerGaterParams, PeerScoreThresholds
    from ..models.gossipsub import (
        GossipSubConfig,
        GossipSubState,
        make_gossipsub_step,
    )
    from ..models.gossipsub_phase import make_gossipsub_phase_step
    from ..parallel import make_mesh, shard_state
    from ..state import Net

    devices = jax.devices() if devices is None else list(devices)
    if len(devices) > 1 and n_peers % len(devices):
        raise ValueError(
            f"n_peers={n_peers} does not divide over {len(devices)} "
            "devices — pass devices= (e.g. jax.devices()[:1]) to place "
            "the run on the devices it is meant for"
        )

    if config == "eth2":
        n_topics = 64  # attestation subnet count
        subs = graph.subscribe_random(n_peers, n_topics=n_topics,
                                      topics_per_peer=2, seed=seed)
        topo = graph.subnet_connect(subs, d_any=10, d_subnet=5, seed=seed)
    else:
        n_topics = 1
        subs = graph.subscribe_all(n_peers, 1)
        # bounded-degree topology (K stays small and static for the compiler)
        topo = graph.ring_lattice(n_peers, d=8)  # degree 16, K=16
    layout = bench_edge_layout(edge_layout)
    net = Net.build(topo, subs, edge_layout=layout, fused=fused)

    params = _dc.replace(GossipSubParams(), flood_publish=False)
    _tp, sp = bench_score_params(config, n_topics)
    gater = PeerGaterParams() if config == "sybil" else None
    adversary = None
    if config == "sybil":
        rng = np.random.default_rng(seed)
        adversary = rng.random(n_peers) < 0.2
    cfg = GossipSubConfig.build(
        params, PeerScoreThresholds(), score_enabled=True, gater_params=gater,
        validation_capacity=8 if config == "sybil" else 0,
        heartbeat_every=heartbeat_every,
        wire_coalesced=bench_wire_coalesced(wire_coalesced),
        edge_layout=layout,
        fused=fused,
    )
    # tracer-detached configuration (tracing is opt-in in the reference):
    # no aggregate event counters; no fanout slots when every peer
    # subscribes the topic (fanout provably can't occur in that workload)
    cfg = _dc.replace(
        cfg, count_events=(False if count_events is None else count_events),
        fanout_slots=0 if config != "eth2" else cfg.fanout_slots,
    )
    def fresh():
        st = GossipSubState.init(net, msg_slots, cfg, score_params=sp,
                                 seed=seed, telemetry=telemetry)
        if len(devices) > 1:
            st = shard_state(st, make_mesh(devices=devices), n_peers)
        return st

    if rounds_per_phase > 1:
        step = make_gossipsub_phase_step(
            cfg, net, rounds_per_phase, score_params=sp, gater_params=gater,
            adversary_no_forward=adversary, telemetry=telemetry,
            lift_scores=lift_scores,
        )
    else:
        step = make_gossipsub_step(cfg, net, score_params=sp, gater_params=gater,
                                   adversary_no_forward=adversary,
                                   static_heartbeat=heartbeat_every > 1,
                                   telemetry=telemetry,
                                   lift_scores=lift_scores)

    # honest peers only as publish origins: a sybil origin would silently
    # drop its own publish (adversary peers never transmit message data)
    honest = np.flatnonzero(~adversary) if adversary is not None else None
    return BenchCell(fresh(), step, n_topics, honest, net, cfg, fresh)


def measure_phase_gather_sets(
    config: str,
    rounds_per_phase: int,
    wire_coalesced: bool | None = None,
    heartbeat_every: int | None = None,
) -> int:
    # resolve the env-dependent default BEFORE the memo key (a flipped
    # BENCH_WIRE_COALESCED mid-process must not hit a stale cache). A
    # failure propagates (and lru_cache never memoizes a raise): a
    # fingerprint without the field would make the projection fall back
    # to the legacy 16·(r+4) formula in silence
    return _measure_phase_gather_sets(
        config, int(rounds_per_phase),
        bench_wire_coalesced(wire_coalesced), heartbeat_every,
    )


@functools.lru_cache(maxsize=64)
def _measure_phase_gather_sets(
    config: str,
    rounds_per_phase: int,
    wire_coalesced: bool,
    heartbeat_every: int | None,
) -> int:
    """MEASURE the phase engine's halo gather-set count per phase — the
    number the v5e-8 projection's ICI term is built from (each set is one
    cross-peer gather, lowering to one rolled collective-permute per band
    direction under GSPMD; parallel/sharding.py).

    Counts real gather CALLS at trace time (ops/edges.tally_halo_gathers
    under ``jax.eval_shape`` — no compile) on a tiny banded replica of
    the bench config, so the fingerprint records what THIS build of the
    engine actually does instead of the hard-coded 16·(r+4) formula the
    rounds-3..6 projections assumed (the coalesced wire exchange makes
    it r+1). Gather structure is shape-independent, so the tiny N stands
    in for any shard size (one device: nothing executes, so the tiny
    replica is never sharded). Raises when the step cannot be traced."""
    import jax
    import jax.numpy as jnp

    from ..ops import edges

    r = max(int(rounds_per_phase), 1)
    he = heartbeat_every if heartbeat_every is not None else max(r, 1)
    st, step, _, _ = build_bench(
        64, 64, config=config, heartbeat_every=he, rounds_per_phase=r,
        wire_coalesced=wire_coalesced, devices=jax.devices()[:1],
    )
    shape = (r, PUBS_PER_ROUND) if r > 1 else (PUBS_PER_ROUND,)
    po = jnp.zeros(shape, jnp.int32)
    pt = jnp.zeros(shape, jnp.int32)
    pv = jnp.ones(shape, bool)
    if r > 1 or he > 1:
        fn = functools.partial(step, do_heartbeat=True)
    else:
        fn = step
    tally: list = []
    with edges.tally_halo_gathers(tally):
        jax.eval_shape(fn, st, po, pt, pv)
    return len(tally)


def device_stamp() -> dict:
    """What jax reports about the device a measurement runs on — carried
    by every line bench / sweep / profile print, so a number can never
    be read without the device it came from."""
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "n_devices": len(devs),
    }


def select_platform(platform: str | None) -> dict:
    """The measuring entry points' platform rule: an explicitly named
    ``platform`` (BENCH_PLATFORM / --platform) is applied as asked;
    with none named the backend must be a TPU, or the run fails — a
    bench that finds no chip does not carry on under the device
    metric's name. Returns :func:`device_stamp`."""
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
    stamp = device_stamp()
    if not platform and stamp["platform"] != "tpu":
        raise RuntimeError(
            f"no TPU found (backend is {stamp['platform']!r}, "
            f"{stamp['device_kind']!r}); name a platform explicitly "
            "(BENCH_PLATFORM / --platform) to measure somewhere else"
        )
    return stamp


def bench_schedule(n_peers: int, n_topics: int, honest, rounds: int):
    """The bench's seeded publish schedule: ``(po, pt, pv)`` numpy
    ``[rounds, PUBS_PER_ROUND]`` planes (origins drawn from ``honest``
    when the config has adversaries, every publish valid)."""
    rng = np.random.default_rng(0)
    shape = (rounds, PUBS_PER_ROUND)
    if honest is not None:
        po = honest[rng.integers(0, len(honest), size=shape)]
    else:
        po = rng.integers(0, n_peers, size=shape)
    pt = rng.integers(0, n_topics, size=shape)
    return po.astype(np.int32), pt.astype(np.int32), np.ones(shape, bool)


def make_bench_scan(step, heartbeat_every: int, rounds_per_phase: int,
                    unroll: int | None = None):
    """The scanned window the bench measures: ``(scan, unroll_used)``.

    unroll: adjacent iterations let XLA cancel the carry layout
    conversions the while-loop form pays per tick (profiled ~35% of
    device time); 4 rounds is the per-round knee, and phase mode gains
    another ~7-8% from unrolling TWO phases per scan iteration
    (round-4/5 measurements in BASELINE.md)."""
    from ..driver import make_scan

    he, r = int(heartbeat_every), int(rounds_per_phase)
    group = math.lcm(he, r)
    u = unroll if unroll is not None else (2 * group if r > 1 else 4)
    scan = make_scan(
        step,
        heartbeat_every=he,
        rounds_per_phase=r,
        static_heartbeat=he > 1 or r > 1,
        unroll=max(1, u // group),
    )
    return scan, u


def _chaos_fingerprint():
    from .artifacts import chaos_fingerprint

    return chaos_fingerprint()


def _router_fingerprint(router):
    from .artifacts import router_fingerprint

    # the bench matrix never arms a router (protocol A/B lives in the
    # choke-smoke gate, scripts/choke_smoke.py); the explicit v1.1
    # block keeps new artifacts self-describing (round 24)
    return router_fingerprint(router)


def _params_fingerprint(lift_scores: bool):
    from .artifacts import params_fingerprint

    if not lift_scores:
        return params_fingerprint(lifted=False)
    from ..score.params import LIFTED_FIELD_NAMES

    return params_fingerprint(lifted=True, traced=LIFTED_FIELD_NAMES)


def workload_fingerprint(
    config: str,
    n_peers: int,
    msg_slots: int,
    heartbeat_every: int,
    rounds_per_phase: int,
    seg_rounds: int | None = None,
    unroll: int | None = None,
    wire_coalesced: bool | None = None,
    edge_layout: str | None = None,
    lift_scores: bool = False,
    router=None,
) -> dict:
    """The schema-v2 self-description of a bench cell: everything a
    future reader needs to know what the number measured, derived from
    the SAME decision table ``build_bench`` uses.

    The elision flags are the ADVICE-round-5 ask: whether the phase
    engine's static weight elision dropped the mesh-credit (P3/mmd) and
    invalid-delivery (P4/imd) attribution planes for this config — a
    workload property that changes what the headline prices."""
    from ..state import SCATTER_FORM_MIN_PEERS

    n_topics = 64 if config == "eth2" else 1
    tp, sp = bench_score_params(config, n_topics)
    phase = rounds_per_phase > 1
    coalesced = bench_wire_coalesced(wire_coalesced)
    p3_elided = (
        tp.mesh_message_deliveries_weight == 0.0
        and (tp.mesh_failure_penalty_weight == 0.0
             or tp.mesh_message_deliveries_threshold <= 0.0)
    )
    p4_elided = tp.invalid_message_deliveries_weight == 0.0
    fp = {
        "config": config,
        "n_peers": int(n_peers),
        "msg_slots": int(msg_slots),
        # ring_lattice(d=8) — K = 2d; eth2's subnet_connect has no fixed
        # degree (K is the draw's largest, mean 40)
        "degree": None if config == "eth2" else 16,
        "n_topics": n_topics,
        "topics_per_peer": 2 if config == "eth2" else 1,
        "adversary_fraction": 0.2 if config == "sybil" else 0.0,
        "rounds_per_phase": int(rounds_per_phase),
        "heartbeat_every": int(heartbeat_every),
        "pubs_per_round": PUBS_PER_ROUND,
        "score_weights": {
            "mesh_message_deliveries_weight": tp.mesh_message_deliveries_weight,
            "mesh_failure_penalty_weight": tp.mesh_failure_penalty_weight,
            "invalid_message_deliveries_weight":
                tp.invalid_message_deliveries_weight,
            "first_message_deliveries_weight":
                tp.first_message_deliveries_weight,
            "time_in_mesh_weight": tp.time_in_mesh_weight,
            "behaviour_penalty_weight": sp.behaviour_penalty_weight,
        },
        # static weight elision is phase-engine-only (per-round engines
        # never elide — BASELINE.md round-5 addendum)
        "elides_mesh_message_deliveries": bool(phase and p3_elided),
        "elides_invalid_message_deliveries": bool(phase and p4_elided),
        "engine": {
            "mode": "phase" if phase else "per_round",
            # the round-7 stacked/coalesced data plane (phase wire
            # exchange + accumulator stacking + head publish plan);
            # False = the legacy per-plane A/B path
            "wire_coalesced": coalesced,
            # the round-15 sparse data plane: "dense" (padded [N, K]
            # involution) or "csr" (flat [E] edge space, ops/csr.py);
            # legacy artifacts without the field read back "dense"
            # (artifacts.BenchRecord.edge_layout)
            "edge_layout": bench_edge_layout(edge_layout),
            "gater": config == "sybil",
            "validation_capacity": 8 if config == "sybil" else 0,
            "count_events": False,
            "fanout_slots": 2 if config == "eth2" else 0,
            "scatter_publish_alloc": bool(
                phase and n_peers >= SCATTER_FORM_MIN_PEERS),
            # incremental membership planes exist only in the phase
            # engine (gossipsub_phase.py round-4 addendum 4)
            "incr_members": bool(phase and n_topics <= INCR_MEMBERS_MAX_TOPICS),
        },
        # the bench wire is lossless; the explicit off block keeps new
        # artifacts self-describing (chaos runs — scripts/chaos_report.py
        # — emit their generator/scenario here instead). Legacy artifacts
        # without the field read back as off (artifacts.BenchRecord.chaos)
        "chaos": _chaos_fingerprint(),
        # the traced-vs-static config split (round 16, schema v3): a
        # lifted build names the LIFT_AUDIT-proved fields riding the
        # traced ScoreParams plane; legacy lines read back the
        # PARAMS_STATIC sentinel via BenchRecord.params
        "params": _params_fingerprint(lift_scores),
        "router": _router_fingerprint(router),
    }
    if seg_rounds is not None:
        fp["seg_rounds"] = int(seg_rounds)
    if unroll is not None:
        fp["unroll"] = int(unroll)
    stamp = device_stamp()
    n_devices = stamp["n_devices"]
    if seg_rounds is not None:
        # the bench measurement loop is whole-window compiled
        # (driver.make_scan -> make_window): one XLA dispatch per
        # seg_rounds-round segment — the execution self-description the
        # projection's dispatch_overhead_ms term reads (round 14)
        from .artifacts import execution_fingerprint

        fp["execution"] = execution_fingerprint(
            scan=True, segment_rounds=int(seg_rounds),
            dispatches_per_window=1, rounds_per_dispatch=int(seg_rounds),
            mesh_shape=({"peers": n_devices} if n_devices > 1
                        and n_peers % n_devices == 0 else None),
            unroll=unroll,
        )
    if phase:
        # MEASURED halo gather sets per phase (16 rolled permutes each on
        # the banded bench topology) — the projection's ICI input; legacy
        # artifacts without this field fall back to the 16·(r+4) formula
        fp["permute_sets_per_phase"] = int(measure_phase_gather_sets(
            config, rounds_per_phase, wire_coalesced=coalesced,
            heartbeat_every=heartbeat_every,
        ))
    import jax

    # the device the number was measured on rides in every emitted line
    fp.update(stamp)
    fp["prng_impl"] = str(jax.config.jax_default_prng_impl)
    return fp


def measure_rate(config: str, n_peers: int, msg_slots: int,
                 heartbeat_every: int, rounds_per_phase: int,
                 seg_rounds: int, reps: int = 3,
                 unroll: int | None = None):
    """Build + run one bench cell at exactly ``n_peers``; returns
    ``(rounds_per_sec, unroll_used)``. Any failure — out of device
    memory included — propagates: a cell that shrank under the same
    name would not be the cell that was asked for."""
    import jax
    import jax.numpy as jnp

    he, r = int(heartbeat_every), int(rounds_per_phase)
    group = math.lcm(he, r)
    seg = seg_rounds - seg_rounds % group
    if seg <= 0:
        raise ValueError(
            f"seg_rounds={seg_rounds} < one lcm(heartbeat_every, "
            f"rounds_per_phase) group ({group})"
        )
    st, step, n_topics, honest = build_bench(
        n_peers, msg_slots, config=config, heartbeat_every=he,
        rounds_per_phase=r,
    )
    po_j, pt_j, pv_j = (
        jnp.asarray(a) for a in bench_schedule(n_peers, n_topics, honest, seg)
    )
    scan, u = make_bench_scan(step, he, r, unroll)

    st = scan(st, po_j, pt_j, pv_j)  # compile + warmup
    jax.block_until_ready(st)
    rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        st = scan(st, po_j, pt_j, pv_j)
        # jax.block_until_ready IS a true completion barrier on this
        # runtime (chip_smoke.py times one segment both ways on the v5e:
        # ended by it 0.9201 s, ended by a scalar readback 0.9215 s, and
        # a readback issued after it returns in 2.1 ms — my chip run,
        # PR 23), so no scalar readback rides the timed region
        jax.block_until_ready(st)
        dt = time.perf_counter() - t0
        rates.append(seg / dt)
    return max(rates), u


def metric_name(config: str, n_peers: int, rounds_per_phase: int) -> str:
    """The metric naming convention rounds 1-5 established (BASELINE.md
    equivalence rule: phase metrics carry the cadence in the name)."""
    tag = "" if config == "default" else f"_{config}"
    if rounds_per_phase > 1:
        return (
            f"gossipsub_v1.1_delivery_rounds_per_sec_n{n_peers}{tag}"
            f"_phase{rounds_per_phase}"
        )
    return f"gossipsub_v1.1_heartbeat_ticks_per_sec_n{n_peers}{tag}"


def measure_record(config: str, n_peers: int, msg_slots: int,
                   heartbeat_every: int, rounds_per_phase: int,
                   seg_rounds: int, reps: int = 3,
                   unroll: int | None = None):
    """One sweep cell -> a schema-v2 BenchRecord."""
    from .artifacts import NORTH_STAR_RATE, BenchRecord

    value, u = measure_rate(config, n_peers, msg_slots, heartbeat_every,
                            rounds_per_phase, seg_rounds, reps=reps,
                            unroll=unroll)
    r = rounds_per_phase
    extras = {}
    if r > 1:
        extras["heartbeats_per_sec"] = round(value / heartbeat_every, 2)
    return BenchRecord(
        metric=metric_name(config, n_peers, r),
        value=round(value, 2),
        unit="ticks/s" if r == 1 else "delivery-rounds/s",
        vs_baseline=round(value / NORTH_STAR_RATE, 4),
        schema=2,
        fingerprint=workload_fingerprint(
            config, n_peers, msg_slots, heartbeat_every, r,
            seg_rounds=seg_rounds, unroll=u,
        ),
        extras=extras,
    )


@dataclasses.dataclass
class SweepSpec:
    """A declarative (config × N × r) grid. ``heartbeat_every`` defaults
    to r per cell (the phase engine's standard cadence) when None."""

    configs: tuple = ("default",)
    ns: tuple = (100_000,)
    rs: tuple = (8,)
    msg_slots: int = 64
    seg_rounds: int = 1600
    reps: int = 3
    heartbeat_every: int | None = None

    def cells(self):
        for c in self.configs:
            for n in self.ns:
                for r in self.rs:
                    he = self.heartbeat_every
                    yield c, int(n), int(r), int(he if he else max(r, 1))


def run_sweep(spec: SweepSpec, emit=None) -> list:
    """Run every cell of the grid; returns the BenchRecords. ``emit`` is
    called with each record as it completes (the CLI prints JSON lines —
    a long sweep that dies keeps its finished cells)."""
    out = []
    for config, n, r, he in spec.cells():
        rec = measure_record(config, n, spec.msg_slots, he, r,
                             spec.seg_rounds, reps=spec.reps)
        out.append(rec)
        if emit is not None:
            emit(rec)
    return out


def main(argv=None):
    import argparse

    from .artifacts import dump_record

    ap = argparse.ArgumentParser(
        description="declarative (config x N x r) bench sweep; one "
        "schema-v2 JSON line per cell")
    ap.add_argument("--config", default="default",
                    help="comma-separated: default,eth2,sybil")
    ap.add_argument("--n", default="100000", help="comma-separated peer counts")
    ap.add_argument("--r", default="8", help="comma-separated rounds-per-phase")
    ap.add_argument("--msg-slots", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=1600,
                    help="segment length (rounds) per timed rep")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--platform", default=os.environ.get("BENCH_PLATFORM"),
                    help="jax platform, named explicitly (e.g. cpu); "
                    "without it the run requires a TPU")
    ap.add_argument("--prng", default=os.environ.get("BENCH_PRNG", "unsafe_rbg"),
                    help="jax PRNG impl ('' keeps threefry)")
    args = ap.parse_args(argv)

    import jax

    from ..compile_cache import enable_persistent_cache

    select_platform(args.platform)
    if args.prng:
        jax.config.update("jax_default_prng_impl", args.prng)
    enable_persistent_cache()

    spec = SweepSpec(
        configs=tuple(args.config.split(",")),
        ns=tuple(int(x) for x in args.n.split(",")),
        rs=tuple(int(x) for x in args.r.split(",")),
        msg_slots=args.msg_slots,
        seg_rounds=args.rounds,
        reps=args.reps,
    )
    run_sweep(spec, emit=lambda rec: print(dump_record(rec), flush=True))


if __name__ == "__main__":
    main()
