"""Trace-time guard harness: re-trace every engine under JAX's paranoid
modes and pin what the trace is allowed to look like.

simlint (the AST half of the analysis plane) catches what source text
can prove; this half catches what only a trace can: silent weak-type
promotion paths, hidden host↔device transfers at dispatch, recompiles
inside the round loop, dropped buffer donation, and state-tree dtype/
shape drift. Per engine (gossipsub per-round, gossipsub phase with the
stacked coalesced wire path, floodsub, randomsub):

  strict-dtype   the full step traces under
                 ``jax.numpy_dtype_promotion('strict')`` +
                 ``jax_enable_checks`` — every cross-dtype op in the
                 program is an explicit cast, so a refactor that mixes
                 int32 into the uint32 word planes fails HERE, not as
                 a corrupted bitset three PRs later.
  schema         every leaf of the step's output state tree matches the
                 committed ``STATE_SCHEMA.json`` baseline (path, dtype,
                 shape, weak_type). ``ANALYZE_UPDATE=1`` rewrites — the
                 PERF_SMOKE/BASELINE pattern. A weak-typed leaf is
                 rejected even on update: a weak output leaf re-traced
                 as an input next call IS the classic recompile-per-
                 round bug.
  donation       the lowered step carries buffer-donation markers for
                 its state argument (``jax.buffer_donor`` /
                 ``tf.aliasing_output`` in the StableHLO) — losing
                 donation doubles resident state HBM at the 100k-peer
                 shapes.
  recompile      executing a multi-round run (fresh publish args every
                 round) under ``jax.transfer_guard('disallow')``
                 compiles EXACTLY once. The transfer guard turns any
                 implicit host array sneaking into the loop into an
                 error; the compile sentinel turns weak-type/shape
                 wobble or an unhashable static into a failure instead
                 of a silent 100x slowdown.

Two derived paths run the same guard set without their own committed
baselines: the ENSEMBLE engine (S=2 vmap lift; schema = base rows plus
a leading S axis) and, since round 11, the TELEMETRY engine (the base
bench step with the per-round panel recorder on; schema = base rows
plus the pinned ``.core.telem`` leaves — its transfer_guard run is the
"telemetry records every round with zero host transfers and one
compile" acceptance invariant).

The harness shapes are deliberately small (N=192, K=16, M=64, r=4 —
compile-bound, ~seconds warm via the shared .jax_cache); the invariants
they pin are shape-independent. Entry: ``scripts/analyze.py`` /
``make analyze``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import os

#: harness shape: big enough that every plane (mesh, mcache, score,
#: fanout-free default config) is live, small enough to compile in
#: seconds on the tier-1 CPU container
GUARD_N = 192
GUARD_M = 64
GUARD_R = 4          # phase-engine sub-rounds
GUARD_ROUNDS = 6     # executed steps for the recompile sentinel
PUB_WIDTH = 4

SCHEMA_NAME = "STATE_SCHEMA.json"

ENGINES = ("gossipsub", "gossipsub_phase", "floodsub", "randomsub")

#: the batched path (round 10): one ensemble engine — the gossipsub
#: bench step lifted through ensemble.lift_step at S=ENSEMBLE_S — runs
#: the same guard set. Its schema is NOT committed separately: every
#: leaf must be the base engine's leaf with a leading S axis, so the
#: check STRIPS the leading dim and compares against the committed
#: ``gossipsub`` rows (ANALYZE_UPDATE=1 refreshes those; the ensemble
#: rows are always derived, never duplicated into the baseline).
ENSEMBLE_ENGINE = "ensemble"
ENSEMBLE_BASE = "gossipsub"
ENSEMBLE_S = 2

#: the telemetry path (round 11): the gossipsub bench step built with a
#: TelemetryConfig runs the same guard set — in particular the
#: GUARD_ROUNDS execution under ``transfer_guard('disallow')`` with the
#: one-compile sentinel, which is the "zero host transfers in the run
#: window, one compile, telemetry on" acceptance invariant. Like the
#: ensemble engine its schema is NOT committed separately: stripping the
#: ``.core.telem`` leaves must yield EXACTLY the committed ``gossipsub``
#: rows (telemetry only ADDS the panel plane), and the telem leaves
#: themselves are pinned against TelemetryConfig/N_METRICS here.
TELEMETRY_ENGINE = "telemetry"
TELEMETRY_BASE = "gossipsub"
TELEMETRY_ROWS = GUARD_ROUNDS
TELEMETRY_TRACKED = (0, 7)
_TELEM_PREFIX = ".core.telem"

#: the sparse-data-plane path (round 15): the gossipsub bench step built
#: with ``edge_layout="csr"`` (ops/csr.py — the flat [E] edge exchange)
#: runs the same guard set. Its schema is NOT committed separately:
#: since round 18 the csr build carries the CSR-RESIDENT state tier
#: (fe_words/served_* as [E, W], peerhave/iasked as [E] — docs/
#: DESIGN.md §18), so the rows must equal the committed ``gossipsub``
#: rows transformed by :func:`csr_variant_rows` — exactly those five
#: leaves flat, everything else byte-equal. Any other drift means the
#: layout leaked beyond the sanctioned tier.
CSR_ENGINE = "csr"
CSR_BASE = "gossipsub"

#: the combined phase+CSR path (round 16): the multi-round phase
#: engine built on the flat-[E] edge layout — a cell with real bugs to
#: catch (the stacked wire head AND every sub-round exchange route
#: through the CSR seams) that previously had no guard coverage. Its
#: schema must equal the committed ``gossipsub_phase`` rows under the
#: same round-18 csr-variant transformation.
PHASE_CSR_ENGINE = "phase_csr"
PHASE_CSR_BASE = "gossipsub_phase"

#: the lifted-score path (round 16, docs/DESIGN.md §16): the gossipsub
#: bench step built with ``lift_scores=True`` — the traced ScoreParams
#: plane rides as a trailing argument. Its schema must EQUAL the
#: committed ``gossipsub`` rows (the plane is an INPUT, never state),
#: and its GUARD_ROUNDS run ALTERNATES two distinct weight/threshold
#: sets, so the one-compile cache sentinel IS the recompile-free A/B
#: sentinel the lift exists for.
LIFTED_ENGINE = "lifted"
LIFTED_BASE = "gossipsub"

#: the fused-plane paths (round 21, docs/DESIGN.md §21). ``csr_fused``
#: is the csr row rebuilt with ``fused=True`` — the sort-composite
#: selection and capacity-bounded segmented scan under the full guard
#: set (fusion is a pure recomposition: schema must stay the csr
#: variant of the committed ``gossipsub`` rows). ``lifted_fused`` is
#: the lifted row rebuilt with ``fused=True``: the alternating-plane
#: one-compile sentinel runs through the sort-form selection
#: composites (a threshold that re-entered the program as a Python
#: scalar would recompile here).
CSR_FUSED_ENGINE = "csr_fused"
CSR_FUSED_BASE = "gossipsub"
LIFTED_FUSED_ENGINE = "lifted_fused"
LIFTED_FUSED_BASE = "gossipsub"

#: the dynamic-overlay path (round 22, docs/DESIGN.md §22): the
#: gossipsub step built with ``dynamic_peers=True, dynamic_topo=True``
#: on an unbanded net, driven through a REAL mutation storm
#: (topo.dynamics.churn_storm — kill/replace/rewire/join write batches
#: ride the per-round args). Its schema is NOT committed separately:
#: the state gains EXACTLY the ``.core.topo`` overlay plane (pinned
#: here against the Net's [N, K] geometry); stripping it must yield
#: the committed ``gossipsub`` rows byte-equal. Its GUARD_ROUNDS run
#: under ``transfer_guard('disallow')`` with the one-compile sentinel
#: IS the recompile-free-mutation acceptance invariant: the topology
#: changes every dispatch and the program never re-traces.
DYNAMIC_ENGINE = "dynamic"
DYNAMIC_BASE = "gossipsub"
_TOPO_PREFIX = ".core.topo"

#: the router rows (round 24, docs/DESIGN.md §24): the bench-default
#: gossipsub build with a RouterConfig armed. ``idontwant`` is the
#: GossipSub v1.2 suppression row (§24a) — the state gains EXACTLY the
#: ``.dontwant`` announce plane; ``choke`` is the episub lazy-choke row
#: ON TOP of the §24c latency ring (a static link_delay plane drives
#: the [N, K, L, W] delayed-commit ring through every guard) — the
#: state gains ``.choked``/``.choke_ema``/``.inflight``. Neither schema
#: is committed separately: the router leaves are pinned against the
#: harness's RouterConfig/Net geometry and STRIPPING them must yield
#: the committed ``gossipsub`` rows byte-equal — the router plane only
#: ADDS state, so any other drift is a real state change hiding behind
#: the config (the elision contract, from the schema side).
IDONTWANT_ENGINE = "idontwant"
IDONTWANT_BASE = "gossipsub"
CHOKE_ENGINE = "choke"
CHOKE_BASE = "gossipsub"
CHOKE_RING_L = 2
_ROUTER_LEAVES = (".dontwant", ".choked", ".choke_ema", ".inflight")

#: StableHLO markers proving the state argument is donated
_DONATION_MARKERS = ("jax.buffer_donor", "tf.aliasing_output")


class GuardViolation(Exception):
    """One failed guard; .engine and .guard say which."""

    def __init__(self, engine: str, guard: str, msg: str):
        super().__init__(f"[{engine}] {guard}: {msg}")
        self.engine = engine
        self.guard = guard


@dataclasses.dataclass
class EngineHarness:
    """One engine under test: a fresh jitted step plus everything the
    guards need to drive it."""

    name: str
    jit_fn: object          # the jitted callable (cache-fresh)
    state: object           # initial state pytree
    make_args: object       # round_index -> positional args after state
    static_kwargs: dict     # constant static kwargs for every call


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _pub_args(shape, i: int):
    """Round-i publish batch: one valid publish from peer ``i`` so the
    traced program includes live allocator + delivery work."""
    import jax.numpy as jnp
    import numpy as np

    po = np.full(shape, -1, np.int32)
    po.reshape(-1)[0] = i % GUARD_N
    pt = np.zeros(shape, np.int32)
    pv = np.ones(shape, bool)
    return jnp.asarray(po), jnp.asarray(pt), jnp.asarray(pv)


def build_engine(name: str) -> EngineHarness:
    """Construct a fresh-jit harness for one of ENGINES. Fresh jit
    objects make the recompile sentinel exact: the cache starts empty
    regardless of what else ran in this process."""
    import jax

    from .. import graph
    from ..state import Net, SimState

    if name in ("gossipsub", "gossipsub_phase"):
        from ..perf.sweep import build_bench

        r = GUARD_R if name == "gossipsub_phase" else 1
        st, step, _, _ = build_bench(
            GUARD_N, GUARD_M, heartbeat_every=max(r, 1), rounds_per_phase=r,
        )
        shape = (r, PUB_WIDTH) if r > 1 else (PUB_WIDTH,)
        kwargs = {"do_heartbeat": True} if r > 1 else {}
        return EngineHarness(
            name, step, st, lambda i: _pub_args(shape, i), kwargs
        )

    topo = graph.ring_lattice(GUARD_N, d=8)
    subs = graph.subscribe_all(GUARD_N, 1)
    net = Net.build(topo, subs)
    st = SimState.init(GUARD_N, GUARD_M, k=net.max_degree)
    if name == "floodsub":
        from ..models import floodsub

        # re-jit the raw step so the compile cache is this harness's own
        step = jax.jit(
            floodsub.floodsub_step.__wrapped__, donate_argnums=1,
            static_argnames=("queue_cap", "stacked", "chaos"),
        )
        return EngineHarness(
            name,
            step,
            st,
            lambda i: _pub_args((PUB_WIDTH,), i),
            {"net": net},
        )
    if name == "randomsub":
        from ..models.randomsub import make_randomsub_step

        step = make_randomsub_step(net)
        return EngineHarness(
            name, step, st, lambda i: _pub_args((PUB_WIDTH,), i), {}
        )
    raise ValueError(f"unknown engine {name!r}; expected one of {ENGINES}")


def build_ensemble_harness() -> EngineHarness:
    """The batched-path harness: the ENSEMBLE_BASE bench step lifted to
    S=ENSEMBLE_S sims (ensemble.lift_step — a fresh jit, so the
    recompile sentinel covers the LIFTED program), driven with tiled
    publish args. Same guard set as the per-sim engines."""
    from ..ensemble import batch as ebatch
    from ..perf.sweep import build_bench

    st, step, _, _ = build_bench(
        GUARD_N, GUARD_M, heartbeat_every=1, rounds_per_phase=1,
    )
    states = ebatch.batch_states(st, ENSEMBLE_S)
    ens = ebatch.lift_step(step)

    def make_args(i):
        return tuple(ebatch.tile(a, ENSEMBLE_S)
                     for a in _pub_args((PUB_WIDTH,), i))

    return EngineHarness(ENSEMBLE_ENGINE, ens, states, make_args, {})


def build_csr_harness() -> EngineHarness:
    """The sparse-plane path: the CSR_BASE bench step built with
    ``edge_layout="csr"`` — a fresh jit via build_bench, so the
    recompile sentinel covers the CSR program (a layout that
    cache-busts or transfers mid-loop fails here)."""
    from ..perf.sweep import build_bench

    st, step, _, _ = build_bench(
        GUARD_N, GUARD_M, heartbeat_every=1, rounds_per_phase=1,
        edge_layout="csr",
    )
    return EngineHarness(
        CSR_ENGINE, step, st, lambda i: _pub_args((PUB_WIDTH,), i), {},
    )


def build_phase_csr_harness() -> EngineHarness:
    """The combined phase+CSR path (round 16): the r=GUARD_R phase
    engine on the flat-[E] edge layout — the stacked coalesced wire
    head and every data sub-round exchange route through the CSR
    seams under the full guard set (a cell no row covered before)."""
    from ..perf.sweep import build_bench

    st, step, _, _ = build_bench(
        GUARD_N, GUARD_M, heartbeat_every=GUARD_R,
        rounds_per_phase=GUARD_R, edge_layout="csr",
    )
    return EngineHarness(
        PHASE_CSR_ENGINE, step, st,
        lambda i: _pub_args((GUARD_R, PUB_WIDTH), i),
        {"do_heartbeat": True},
    )


def lifted_plane_pair():
    """Two DISTINCT weight/threshold planes for the A/B sentinel:
    plane A is the bench default parameterization; plane B moves every
    lifted surface — per-topic weights/decays/caps, the P7 scalars,
    the topic score cap, and all five v1.1 thresholds."""
    import dataclasses as _dc

    from ..config import PeerScoreThresholds
    from ..perf.sweep import bench_score_params
    from ..score.params import ScoreParams

    tp_a, sp_a = bench_score_params("default", 1)
    plane_a = ScoreParams.build(sp_a, PeerScoreThresholds(), 1)
    tp_b = _dc.replace(
        tp_a,
        first_message_deliveries_weight=2.0,
        mesh_message_deliveries_weight=-0.25,
        mesh_message_deliveries_threshold=4.0,
        invalid_message_deliveries_weight=-0.5,
        time_in_mesh_weight=0.5,
    )
    sp_b = _dc.replace(
        sp_a, topics={0: tp_b}, behaviour_penalty_weight=-2.0,
        behaviour_penalty_threshold=0.5, topic_score_cap=50.0,
    )
    thr_b = PeerScoreThresholds(
        gossip_threshold=-4.0, publish_threshold=-20.0,
        graylist_threshold=-40.0, accept_px_threshold=5.0,
        opportunistic_graft_threshold=10.0,
    )
    return plane_a, ScoreParams.build(sp_b, thr_b, 1)


def build_lifted_harness() -> EngineHarness:
    """The lifted-score path (round 16): the gossipsub bench step with
    ``lift_scores=True``, driven with ALTERNATING weight planes — so
    ``run_rounds_guarded``'s one-compile cache sentinel doubles as the
    recompile-free A/B sentinel (two distinct score-weight sets, one
    XLA program), executed under ``transfer_guard('disallow')``."""
    from ..perf.sweep import build_bench

    st, step, _, _ = build_bench(
        GUARD_N, GUARD_M, heartbeat_every=1, rounds_per_phase=1,
        lift_scores=True,
    )
    plane_a, plane_b = lifted_plane_pair()

    def make_args(i):
        return _pub_args((PUB_WIDTH,), i) + (
            plane_a if i % 2 == 0 else plane_b,)

    return EngineHarness(LIFTED_ENGINE, step, st, make_args, {})


def build_csr_fused_harness() -> EngineHarness:
    """The fused sparse-plane path (round 21): the csr harness rebuilt
    with ``fused=True`` on both the Net and the config — the
    sort-composite top-k/random selection and the capacity-bounded
    segmented scan replace the pairwise/log2(E) forms inside the same
    step, bit-exact, under the full guard set."""
    from ..perf.sweep import build_bench

    st, step, _, _ = build_bench(
        GUARD_N, GUARD_M, heartbeat_every=1, rounds_per_phase=1,
        edge_layout="csr", fused=True,
    )
    return EngineHarness(
        CSR_FUSED_ENGINE, step, st,
        lambda i: _pub_args((PUB_WIDTH,), i), {},
    )


def build_lifted_fused_harness() -> EngineHarness:
    """The lifted+fused path (round 21): ``lift_scores=True`` AND
    ``fused=True`` (the sort-form selection composites) in one step,
    under the alternating-plane one-compile A/B run."""
    from ..perf.sweep import build_bench

    st, step, _, _ = build_bench(
        GUARD_N, GUARD_M, heartbeat_every=1, rounds_per_phase=1,
        lift_scores=True, fused=True,
    )
    plane_a, plane_b = lifted_plane_pair()

    def make_args(i):
        return _pub_args((PUB_WIDTH,), i) + (
            plane_a if i % 2 == 0 else plane_b,)

    return EngineHarness(LIFTED_FUSED_ENGINE, step, st, make_args, {})


def check_schema_equal(h: EngineHarness, out_tree, base_rows: list | None,
                       base_name: str, why: str) -> list:
    """Schema guard for derived rows whose state tree must EQUAL a base
    engine's exactly (csr / phase_csr: the layout lives in the Net;
    lifted: the plane is an argument, never state): weak-type audit,
    then the exact-equality diff against the base rows."""
    rows = schema_of(out_tree)
    weak = [r["path"] for r in rows if r["weak_type"]]
    if weak:
        raise GuardViolation(
            h.name, "schema",
            f"weak-typed state leaves {weak[:4]} in the {h.name} step",
        )
    if base_rows is not None:
        mism = diff_schema(h.name, rows, base_rows)
        if mism:
            raise GuardViolation(
                h.name, "schema",
                f"{len(mism)} state-leaf drift(s) vs the {base_name!r} "
                f"baseline — {why}: " + "; ".join(mism[:5]),
            )
    return rows


def csr_variant_rows(base_rows: list, n_edges: int) -> list:
    """The CSR VARIANT of a dense engine's schema rows (round 18): the
    CSR-resident leaves (state.CSR_RESIDENT_SUFFIXES — the single
    source of the tier's membership) take their flat shapes ([E, W]
    word planes, [E] counters, [E, L, W] the router latency ring);
    every other row must stay byte-equal to the dense baseline — so the
    dense STATE_SCHEMA.json rows remain the single committed source and
    the variant is derived, never duplicated (the same pattern as the
    ensemble strip)."""
    from ..state import (CSR_RESIDENT_COUNTERS, CSR_RESIDENT_RING_PLANES,
                         CSR_RESIDENT_WORD_PLANES)

    out = []
    for r in base_rows:
        p = r["path"]
        if p.endswith(CSR_RESIDENT_WORD_PLANES):
            out.append({**r, "shape": [n_edges, list(r["shape"])[-1]]})
        elif p.endswith(CSR_RESIDENT_RING_PLANES):
            out.append({**r, "shape": [n_edges] + list(r["shape"])[-2:]})
        elif p.endswith(CSR_RESIDENT_COUNTERS):
            out.append({**r, "shape": [n_edges]})
        else:
            out.append(r)
    return out


def _harness_n_edges(h: EngineHarness) -> int:
    """E of a CSR harness, read off the flat first-arrival plane."""
    core = getattr(h.state, "core", h.state)
    return int(core.dlv.fe_words.shape[0])


def check_schema_csr(h: EngineHarness, out_tree,
                     base_rows: list | None) -> list:
    """Schema guard for the CSR engine: exact equality with the base
    rows TRANSFORMED to the CSR-resident variant (csr_variant_rows) —
    any drift beyond the five sanctioned flat leaves means the layout
    leaked somewhere it must not (the checkpoint contract: dense and
    csr snapshots differ in exactly those leaf shapes)."""
    base = (csr_variant_rows(base_rows, _harness_n_edges(h))
            if base_rows is not None else None)
    return check_schema_equal(
        h, out_tree, base, CSR_BASE,
        "the csr layout leaked beyond the resident tier",
    )


def build_telemetry_harness() -> EngineHarness:
    """The telemetry-on path: the TELEMETRY_BASE bench step built with a
    TelemetryConfig (panel rows sized to the guarded run, two tracked
    flight-recorder peers) and live event counters — the build every
    reconciliation gate uses. Fresh jit via build_bench, so the
    recompile sentinel covers the telemetry-on program."""
    from ..perf.sweep import build_bench
    from ..telemetry import TelemetryConfig

    tcfg = TelemetryConfig(rows=TELEMETRY_ROWS, tracked=TELEMETRY_TRACKED)
    st, step, _, _ = build_bench(
        GUARD_N, GUARD_M, heartbeat_every=1, rounds_per_phase=1,
        telemetry=tcfg, count_events=True,
    )
    return EngineHarness(
        TELEMETRY_ENGINE, step, st,
        lambda i: _pub_args((PUB_WIDTH,), i), {},
    )


def build_dynamic_harness() -> EngineHarness:
    """The dynamic-overlay path: the bench-default gossipsub build on
    an unbanded dynamic Net (``Net.build(dynamic=True)``) with the
    mutable topo plane in the state, its per-round args carrying a
    churn-storm's liveness rows and mutation write batches — so every
    guard runs against a step whose topology actually changes."""
    import dataclasses as _dc

    import jax.numpy as jnp

    from .. import graph
    from ..config import GossipSubParams, PeerScoreThresholds
    from ..models.gossipsub import (
        GossipSubConfig,
        GossipSubState,
        make_gossipsub_step,
    )
    from ..perf.sweep import bench_score_params, bench_wire_coalesced
    from ..state import Net
    from ..topo.dynamics import churn_storm

    topo = graph.ring_lattice(GUARD_N, d=8)
    subs = graph.subscribe_all(GUARD_N, 1)
    net = Net.build(topo, subs, dynamic=True)
    params = _dc.replace(GossipSubParams(), flood_publish=False)
    _tp, sp = bench_score_params("default", 1)
    cfg = GossipSubConfig.build(
        params, PeerScoreThresholds(), score_enabled=True,
        validation_capacity=0, heartbeat_every=1,
        wire_coalesced=bench_wire_coalesced(None),
    )
    cfg = _dc.replace(cfg, count_events=False, fanout_slots=0)
    st = GossipSubState.init(net, GUARD_M, cfg, score_params=sp, seed=0,
                             dynamic_topo=True)
    step = make_gossipsub_step(cfg, net, score_params=sp,
                               dynamic_peers=True, dynamic_topo=True)
    sched = churn_storm(topo, n_dispatches=GUARD_ROUNDS, kill_frac=0.1,
                        rewires=4, joins=1, join_links=2, seed=0)
    writes, up = sched.build()

    def make_args(i):
        d = i % GUARD_ROUNDS
        return _pub_args((PUB_WIDTH,), i) + (
            jnp.asarray(up[d]), jnp.asarray(writes[d]))

    return EngineHarness(DYNAMIC_ENGINE, step, st, make_args, {})


def check_schema_dynamic(h: EngineHarness, out_tree,
                         base_rows: list | None) -> list:
    """Schema guard for the dynamic engine: weak-type audit, pin the
    five ``.core.topo`` overlay leaves (state.TopoState — int32/bool
    [N, K] against the harness Net's geometry), then the REMAINING
    rows must equal the base engine's committed rows — dynamic_topo
    only ADDS the overlay plane; any other drift is a real state
    change hiding behind the flag (the mutation-off-statically-free
    contract, from the schema side)."""
    rows = schema_of(out_tree)
    weak = [r["path"] for r in rows if r["weak_type"]]
    if weak:
        raise GuardViolation(
            h.name, "schema",
            f"weak-typed state leaves {weak[:4]} in the dynamic step",
        )
    shape = list(h.state.core.topo.nbr.shape)
    want_topo = {
        f"{_TOPO_PREFIX}.nbr": "int32",
        f"{_TOPO_PREFIX}.nbr_ok": "bool",
        f"{_TOPO_PREFIX}.rev": "int32",
        f"{_TOPO_PREFIX}.edge_perm": "int32",
        f"{_TOPO_PREFIX}.epoch": "int32",
    }
    got_topo = {r["path"]: r for r in rows
                if r["path"].startswith(_TOPO_PREFIX)}
    for path, dt in want_topo.items():
        r = got_topo.get(path)
        if r is None or r["dtype"] != dt or r["shape"] != shape:
            raise GuardViolation(
                h.name, "schema",
                f"overlay leaf {path} expected {dt} {shape}, got {r} — "
                "the topo plane does not match the Net's [N, K] geometry",
            )
    if set(got_topo) != set(want_topo):
        raise GuardViolation(
            h.name, "schema",
            "unexpected overlay leaves "
            f"{sorted(set(got_topo) - set(want_topo))}",
        )
    stripped = [r for r in rows if not r["path"].startswith(_TOPO_PREFIX)]
    if base_rows is not None:
        mism = diff_schema(h.name, stripped, base_rows)
        if mism:
            raise GuardViolation(
                h.name, "schema",
                f"{len(mism)} non-overlay leaf drift(s) vs the "
                f"{DYNAMIC_BASE!r} baseline after stripping "
                f"{_TOPO_PREFIX}.*: " + "; ".join(mism[:5]),
            )
    return stripped


def build_router_harness(name: str, router, link_delay=None) -> EngineHarness:
    """A router-row harness (round 24): the bench-default gossipsub
    build — same topology, params, score plane, and tracer-detached
    config as ``build_bench(config="default")``, so the stripped rows
    anchor to the committed ``gossipsub`` baseline — with a
    ``RouterConfig`` armed (and, for the ring, its static link_delay
    plane)."""
    import dataclasses as _dc

    from .. import graph
    from ..config import GossipSubParams, PeerScoreThresholds
    from ..models.gossipsub import (
        GossipSubConfig,
        GossipSubState,
        make_gossipsub_step,
    )
    from ..perf.sweep import bench_score_params, bench_wire_coalesced
    from ..state import Net

    topo = graph.ring_lattice(GUARD_N, d=8)
    subs = graph.subscribe_all(GUARD_N, 1)
    net = Net.build(topo, subs)
    params = _dc.replace(GossipSubParams(), flood_publish=False)
    _tp, sp = bench_score_params("default", 1)
    cfg = GossipSubConfig.build(
        params, PeerScoreThresholds(), score_enabled=True,
        validation_capacity=0, heartbeat_every=1,
        wire_coalesced=bench_wire_coalesced(None),
        router=router,
    )
    cfg = _dc.replace(cfg, count_events=False, fanout_slots=0)
    st = GossipSubState.init(net, GUARD_M, cfg, score_params=sp, seed=0)
    step = make_gossipsub_step(cfg, net, score_params=sp,
                               link_delay=link_delay)
    return EngineHarness(
        name, step, st, lambda i: _pub_args((PUB_WIDTH,), i), {}
    )


def check_schema_router(h: EngineHarness, out_tree,
                        base_rows: list | None) -> list:
    """Schema guard for a router row: weak-type audit, pin every armed
    router leaf (dtype + shape read off the HARNESS's initial state —
    GossipSubState.init sizes them from the RouterConfig and the Net's
    geometry, so a step that reshapes or retypes one fails here), then
    the REMAINING rows must equal the base engine's committed rows —
    the router plane only ADDS state leaves."""
    rows = schema_of(out_tree)
    weak = [r["path"] for r in rows if r["weak_type"]]
    if weak:
        raise GuardViolation(
            h.name, "schema",
            f"weak-typed state leaves {weak[:4]} in the {h.name} step",
        )
    want = {}
    for path in _ROUTER_LEAVES:
        leaf = getattr(h.state, path[1:], None)
        if leaf is not None:
            want[path] = {"dtype": str(leaf.dtype),
                          "shape": list(leaf.shape)}
    got = {r["path"]: r for r in rows if r["path"] in _ROUTER_LEAVES}
    for path, w in want.items():
        r = got.get(path)
        if r is None or r["dtype"] != w["dtype"] or r["shape"] != w["shape"]:
            raise GuardViolation(
                h.name, "schema",
                f"router leaf {path} expected {w['dtype']} {w['shape']}, "
                f"got {r} — the plane does not match its RouterConfig/"
                "Net geometry",
            )
    if set(got) != set(want):
        raise GuardViolation(
            h.name, "schema",
            f"unexpected router leaves {sorted(set(got) - set(want))} — "
            "a leaf the RouterConfig did not arm is in the state",
        )
    stripped = [r for r in rows if r["path"] not in _ROUTER_LEAVES]
    if base_rows is not None:
        mism = diff_schema(h.name, stripped, base_rows)
        if mism:
            raise GuardViolation(
                h.name, "schema",
                f"{len(mism)} non-router leaf drift(s) vs the "
                f"{CHOKE_BASE!r} baseline after stripping the router "
                "plane: " + "; ".join(mism[:5]),
            )
    return stripped


def check_schema_telemetry(h: EngineHarness, out_tree,
                           base_rows: list | None) -> list:
    """Schema guard for the telemetry engine: weak-type audit, pin the
    ``.core.telem`` leaves (panel/flight dtype + shape from the static
    TelemetryConfig), then the REMAINING rows must equal the base
    engine's committed rows — telemetry only adds the panel plane; any
    other drift is a real state change hiding behind the flag. That
    includes the ``events`` leaf: the telemetry build counts events
    (count_events=True) while the committed bench rows are
    tracer-detached, and the comparison doubles as the pin that the
    live-counters build changes no leaf schema."""
    from ..telemetry import N_FLIGHT, N_METRICS

    rows = schema_of(out_tree)
    weak = [r["path"] for r in rows if r["weak_type"]]
    if weak:
        raise GuardViolation(
            h.name, "schema",
            f"weak-typed state leaves {weak[:4]} in the telemetry step",
        )
    telem = [r for r in rows if r["path"].startswith(_TELEM_PREFIX)]
    want_telem = {
        f"{_TELEM_PREFIX}.panel": [TELEMETRY_ROWS, N_METRICS],
        f"{_TELEM_PREFIX}.flight": [TELEMETRY_ROWS,
                                    len(TELEMETRY_TRACKED), N_FLIGHT],
    }
    got_telem = {r["path"]: r for r in telem}
    for path, shape in want_telem.items():
        r = got_telem.get(path)
        if r is None or r["dtype"] != "float32" or r["shape"] != shape:
            raise GuardViolation(
                h.name, "schema",
                f"telemetry leaf {path} expected float32 {shape}, got "
                f"{r} — the panel plane does not match its static "
                "TelemetryConfig",
            )
    if set(got_telem) != set(want_telem):
        raise GuardViolation(
            h.name, "schema",
            f"unexpected telemetry leaves {sorted(set(got_telem) - set(want_telem))}",
        )
    stripped = [r for r in rows if not r["path"].startswith(_TELEM_PREFIX)]
    if base_rows is not None:
        mism = diff_schema(h.name, stripped, base_rows)
        if mism:
            raise GuardViolation(
                h.name, "schema",
                f"{len(mism)} non-telemetry leaf drift(s) vs the "
                f"{TELEMETRY_BASE!r} baseline after stripping "
                f"{_TELEM_PREFIX}.*: " + "; ".join(mism[:5]),
            )
    return stripped


def _call(h: EngineHarness, state, i: int):
    kw = dict(h.static_kwargs)
    net = kw.pop("net", None)
    args = h.make_args(i)
    if net is not None:
        return h.jit_fn(net, state, *args, **kw)
    return h.jit_fn(state, *args, **kw)


@contextlib.contextmanager
def _enable_checks():
    import jax

    prev = jax.config.jax_enable_checks
    jax.config.update("jax_enable_checks", True)
    try:
        yield
    finally:
        jax.config.update("jax_enable_checks", prev)


# ---------------------------------------------------------------------------
# individual guards (each usable standalone — the negative tests do)


def strict_trace(h: EngineHarness):
    """Abstractly evaluate the step under strict dtype promotion +
    enable_checks; returns the output state avals (schema input)."""
    import jax

    with _enable_checks(), jax.numpy_dtype_promotion("strict"):
        try:
            return jax.eval_shape(lambda s, i=0: _call(h, s, i), h.state)
        except Exception as e:
            raise GuardViolation(
                h.name, "strict-dtype",
                f"{type(e).__name__}: {str(e)[:400]}",
            ) from e


def schema_of(out_tree) -> list:
    """Flatten an aval tree into the committed leaf-schema rows. PRNG
    key dtypes are normalized to "key" so the baseline is independent
    of the ambient jax_default_prng_impl."""
    import jax

    rows = []
    leaves = jax.tree_util.tree_flatten_with_path(out_tree)[0]
    for path, leaf in leaves:
        dt = str(leaf.dtype)
        if dt.startswith("key<"):
            dt = "key"
        rows.append({
            "path": jax.tree_util.keystr(path),
            "dtype": dt,
            "shape": list(leaf.shape),
            "weak_type": bool(getattr(leaf, "weak_type", False)),
        })
    return rows


def diff_schema(engine: str, got: list, want: list) -> list:
    """Human-readable mismatch lines between two leaf-schema lists."""
    gm = {r["path"]: r for r in got}
    wm = {r["path"]: r for r in want}
    out = []
    for path in sorted(set(gm) | set(wm)):
        g, w = gm.get(path), wm.get(path)
        if g is None:
            out.append(f"{path}: leaf disappeared (baseline {w})")
        elif w is None:
            out.append(f"{path}: new leaf {g} not in baseline")
        elif g != w:
            out.append(f"{path}: {g} != baseline {w}")
    return out


def check_schema(h: EngineHarness, out_tree, baseline: dict | None) -> list:
    """Compare the step's output state tree against the committed
    baseline; returns this engine's fresh rows (for ANALYZE_UPDATE
    rewrites). Weak-typed leaves fail regardless of baseline."""
    rows = schema_of(out_tree)
    weak = [r["path"] for r in rows if r["weak_type"]]
    if weak:
        raise GuardViolation(
            h.name, "schema",
            f"weak-typed state leaves {weak[:4]} — a weak output leaf "
            "re-traced as next round's input recompiles every call",
        )
    if baseline is not None:
        want = (baseline.get("engines", {}).get(h.name) or {}).get("leaves")
        if want is None:
            raise GuardViolation(
                h.name, "schema",
                f"no committed baseline for engine {h.name!r} in "
                f"{SCHEMA_NAME} (ANALYZE_UPDATE=1 to record)",
            )
        mism = diff_schema(h.name, rows, want)
        if mism:
            raise GuardViolation(
                h.name, "schema",
                f"{len(mism)} state-leaf drift(s) vs {SCHEMA_NAME} "
                f"(ANALYZE_UPDATE=1 rewrites): " + "; ".join(mism[:5]),
            )
    return rows


def strip_leading_sims(engine: str, rows: list, n_sims: int) -> list:
    """Validate + strip the leading S axis from a batched engine's
    schema rows: every leaf must carry ``shape[0] == n_sims``; the
    stripped rows are then comparable to the BASE engine's committed
    baseline — no duplicated ensemble baseline to rot."""
    out = []
    for r in rows:
        shape = list(r["shape"])
        if not shape or shape[0] != n_sims:
            raise GuardViolation(
                engine, "schema",
                f"leaf {r['path']} shape {shape} does not carry the "
                f"leading S={n_sims} sim axis — the vmap lift dropped "
                "or reordered a batch dimension",
            )
        out.append({**r, "shape": shape[1:]})
    return out


def check_schema_batched(h: EngineHarness, out_tree,
                         base_rows: list | None) -> list:
    """Schema guard for the ensemble engine: weak-type audit, then the
    leading-S strip, then comparison against the BASE engine's rows
    (committed or freshly computed on update runs)."""
    rows = schema_of(out_tree)
    weak = [r["path"] for r in rows if r["weak_type"]]
    if weak:
        raise GuardViolation(
            h.name, "schema",
            f"weak-typed state leaves {weak[:4]} in the batched step",
        )
    stripped = strip_leading_sims(h.name, rows, ENSEMBLE_S)
    if base_rows is not None:
        mism = diff_schema(h.name, stripped, base_rows)
        if mism:
            raise GuardViolation(
                h.name, "schema",
                f"{len(mism)} per-sim leaf drift(s) vs the "
                f"{ENSEMBLE_BASE!r} baseline after stripping the "
                f"S={ENSEMBLE_S} axis: " + "; ".join(mism[:5]),
            )
    return stripped


def check_donation(h: EngineHarness):
    """The lowered step must donate its state buffers."""
    lowered = _lower(h)
    txt = lowered.as_text()
    if not any(m in txt for m in _DONATION_MARKERS):
        raise GuardViolation(
            h.name, "donation",
            "no buffer-donation markers in the lowered step — state "
            "buffers are copied every round (donate_argnums lost?)",
        )


def _lower(h: EngineHarness):
    kw = dict(h.static_kwargs)
    net = kw.pop("net", None)
    args = h.make_args(0)
    if net is not None:
        return h.jit_fn.lower(net, h.state, *args, **kw)
    return h.jit_fn.lower(h.state, *args, **kw)


def run_rounds_guarded(h: EngineHarness, rounds: int = GUARD_ROUNDS):
    """Execute ``rounds`` steps with fresh per-round publish args under
    transfer_guard('disallow'); assert exactly one compile."""
    import jax

    # per-round args built OUTSIDE the guard: only the loop is pinned
    all_args = [h.make_args(i) for i in range(rounds)]
    kw = dict(h.static_kwargs)
    net = kw.pop("net", None)
    state = h.state
    before = h.jit_fn._cache_size()
    with jax.transfer_guard("disallow"):
        try:
            for args in all_args:
                if net is not None:
                    state = h.jit_fn(net, state, *args, **kw)
                else:
                    state = h.jit_fn(state, *args, **kw)
        except Exception as e:
            raise GuardViolation(
                h.name, "transfer",
                f"round loop tripped the transfer guard: "
                f"{type(e).__name__}: {str(e)[:300]}",
            ) from e
    compiles = h.jit_fn._cache_size() - before
    if compiles != 1:
        raise GuardViolation(
            h.name, "recompile",
            f"{compiles} compiles across a {rounds}-round run (expected "
            "exactly 1) — static-arg wobble, weak-type drift, or an "
            "unhashable config is cache-busting the step",
        )
    return state


# ---------------------------------------------------------------------------
# driver


def load_baseline(root: str | None = None) -> dict | None:
    path = os.path.join(root or _repo_root(), SCHEMA_NAME)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def write_baseline(schemas: dict, root: str | None = None) -> str:
    path = os.path.join(root or _repo_root(), SCHEMA_NAME)
    payload = {
        "schema": 1,
        "note": (
            "state-tree leaf baseline for make analyze "
            "(analysis/guards.py); ANALYZE_UPDATE=1 rewrites"
        ),
        "shape": {"n_peers": GUARD_N, "msg_slots": GUARD_M,
                  "rounds_per_phase": GUARD_R},
        "engines": {
            name: {"leaves": rows} for name, rows in schemas.items()
        },
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def run_engine(name: str, baseline: dict | None) -> list:
    """All guards for one engine; returns its schema rows."""
    h = build_engine(name)
    out_tree = strict_trace(h)
    rows = check_schema(h, out_tree, baseline)
    check_donation(h)
    run_rounds_guarded(h)
    return rows


def run_ensemble_engine(base_rows: list | None) -> list:
    """All guards for the batched path: strict-dtype trace of the S=2
    lifted step, leading-S schema validation against the base engine's
    rows, buffer-donation audit of the lifted program, and the
    GUARD_ROUNDS execution under transfer_guard with the one-compile
    cache sentinel. Returns the stripped (per-sim) rows."""
    h = build_ensemble_harness()
    out_tree = strict_trace(h)
    rows = check_schema_batched(h, out_tree, base_rows)
    check_donation(h)
    run_rounds_guarded(h)
    return rows


def run_csr_engine(base_rows: list | None) -> list:
    """All guards for the sparse-plane path: strict-dtype trace of the
    CSR-built step (the flat-edge kernels must promote nothing), the
    exact-equality schema check against the base engine's rows, buffer
    donation, and the GUARD_ROUNDS one-compile/transfer-guard run."""
    h = build_csr_harness()
    out_tree = strict_trace(h)
    rows = check_schema_csr(h, out_tree, base_rows)
    check_donation(h)
    run_rounds_guarded(h)
    return rows


def run_phase_csr_engine(base_rows: list | None) -> list:
    """All guards for the combined phase+CSR row (round 16): schema
    must equal the committed ``gossipsub_phase`` rows transformed to
    the CSR-resident variant (round 18: the five per-edge planes
    allocate flat against a csr Net)."""
    h = build_phase_csr_harness()
    out_tree = strict_trace(h)
    base = (csr_variant_rows(base_rows, _harness_n_edges(h))
            if base_rows is not None else None)
    rows = check_schema_equal(
        h, out_tree, base, PHASE_CSR_BASE,
        "the csr layout leaked beyond the resident tier (phase)",
    )
    check_donation(h)
    run_rounds_guarded(h)
    return rows


def run_lifted_engine(base_rows: list | None) -> list:
    """All guards for the lifted-score row (round 16): schema must
    equal the committed ``gossipsub`` rows exactly (the plane is an
    argument, never state), donation must survive the extra traced
    input, and the GUARD_ROUNDS run alternates TWO weight planes under
    transfer_guard — its one-compile sentinel IS the recompile-free
    A/B acceptance invariant."""
    h = build_lifted_harness()
    out_tree = strict_trace(h)
    rows = check_schema_equal(
        h, out_tree, base_rows, LIFTED_BASE,
        "the lifted score plane leaked into the state tree",
    )
    check_donation(h)
    run_rounds_guarded(h)
    return rows


def run_csr_fused_engine(base_rows: list | None) -> list:
    """All guards for the fused csr row (round 21): the schema must
    stay the csr variant of the committed ``gossipsub`` rows — fusion
    recomposes the selection/scan programs and must not touch the
    state tree — plus donation and the one-compile/transfer-guard
    run over the fused step."""
    h = build_csr_fused_harness()
    out_tree = strict_trace(h)
    rows = check_schema_csr(h, out_tree, base_rows)
    check_donation(h)
    run_rounds_guarded(h)
    return rows


def run_lifted_fused_engine(base_rows: list | None) -> list:
    """All guards for the lifted+fused row (round 21): schema equal to
    the committed ``gossipsub`` rows (neither the score plane nor the
    fused composites may leak into state), donation, and the
    alternating A/B plane run under transfer_guard with the one-compile
    sentinel (a recompile here means a threshold re-entered the program
    as a Python scalar)."""
    h = build_lifted_fused_harness()
    out_tree = strict_trace(h)
    rows = check_schema_equal(
        h, out_tree, base_rows, LIFTED_FUSED_BASE,
        "the lifted plane or the fused composites leaked into the state tree",
    )
    check_donation(h)
    run_rounds_guarded(h)
    return rows


def run_telemetry_engine(base_rows: list | None) -> list:
    """All guards for the telemetry-on path: strict-dtype trace, the
    telem-leaf pin + base-row comparison, buffer-donation audit, and
    the GUARD_ROUNDS execution under ``transfer_guard('disallow')``
    with the one-compile sentinel — i.e. the recorder writes every
    round with ZERO host transfers in the run window and no
    per-round recompiles. Returns the stripped (non-telem) rows."""
    h = build_telemetry_harness()
    out_tree = strict_trace(h)
    rows = check_schema_telemetry(h, out_tree, base_rows)
    check_donation(h)
    run_rounds_guarded(h)
    return rows


def run_dynamic_engine(base_rows: list | None) -> list:
    """All guards for the dynamic-overlay row (round 22): strict-dtype
    trace of the mutating step, the topo-leaf pin + base-row
    comparison, buffer donation (the overlay planes must ride the
    donated state, not copy), and the GUARD_ROUNDS run driving a real
    churn storm under ``transfer_guard('disallow')`` — its one-compile
    sentinel is the recompile-free-mutation acceptance invariant
    (every dispatch rewrites topology; the program never re-traces).
    Returns the stripped (non-overlay) rows."""
    h = build_dynamic_harness()
    out_tree = strict_trace(h)
    rows = check_schema_dynamic(h, out_tree, base_rows)
    check_donation(h)
    run_rounds_guarded(h)
    return rows


def run_idontwant_engine(base_rows: list | None) -> list:
    """All guards for the v1.2 IDONTWANT row (round 24): strict-dtype
    trace of the suppression step (the announce plane is u32 word
    algebra — a promotion here corrupts the mask), the ``.dontwant``
    leaf pin + base-row comparison, buffer donation, and the
    GUARD_ROUNDS one-compile/transfer-guard run."""
    from ..routers import RouterConfig

    h = build_router_harness(IDONTWANT_ENGINE, RouterConfig(idontwant=True))
    out_tree = strict_trace(h)
    rows = check_schema_router(h, out_tree, base_rows)
    check_donation(h)
    run_rounds_guarded(h)
    return rows


def run_choke_engine(base_rows: list | None) -> list:
    """All guards for the lazy-choke row (round 24): the choke EMA +
    decision machinery ON TOP of a depth-CHOKE_RING_L latency ring
    (a deterministic [N, K] delay plane, classes 0..L) — strict-dtype
    trace (f32 EMA next to u32 ring words), the choked/choke_ema/
    inflight leaf pins + base-row comparison, donation (the ring must
    ride the donated state, not copy), and the one-compile/transfer-
    guard run — the ring shift and the heartbeat choke decisions
    re-trace nothing."""
    import numpy as np

    from ..routers import RouterConfig

    delay = (np.add.outer(np.arange(GUARD_N), np.arange(16))
             % (CHOKE_RING_L + 1)).astype(np.int32)
    h = build_router_harness(
        CHOKE_ENGINE,
        RouterConfig(choke=True, latency_rounds=CHOKE_RING_L),
        link_delay=delay,
    )
    out_tree = strict_trace(h)
    rows = check_schema_router(h, out_tree, base_rows)
    check_donation(h)
    run_rounds_guarded(h)
    return rows


@dataclasses.dataclass(frozen=True)
class GuardRow:
    """One declarative harness row (round-16 dedup of the per-engine
    copy-paste): ``runner`` is the module-level ``run_*`` callable
    name; ``base`` names the COMMITTED engine (one of ``ENGINES``)
    whose schema rows the derived row validates against — every
    derived row anchors to a committed baseline, never a second
    committed copy. Adding an engine variant — the lifted-score row, a
    future v1.2 router — is one line here plus its builder/runner
    pair (a variant needing its own committed rows goes in ``ENGINES``
    instead)."""

    name: str
    runner: str
    base: str


#: every derived row `make analyze` runs after the four committed
#: engines; each validates against its base engine's rows (committed
#: normally, this run's fresh ones on ANALYZE_UPDATE — a deliberate
#: state change updates ONE baseline and every derived row follows)
DERIVED_ROWS = (
    GuardRow(ENSEMBLE_ENGINE, "run_ensemble_engine", ENSEMBLE_BASE),
    GuardRow(TELEMETRY_ENGINE, "run_telemetry_engine", TELEMETRY_BASE),
    GuardRow(CSR_ENGINE, "run_csr_engine", CSR_BASE),
    GuardRow(PHASE_CSR_ENGINE, "run_phase_csr_engine", PHASE_CSR_BASE),
    GuardRow(LIFTED_ENGINE, "run_lifted_engine", LIFTED_BASE),
    GuardRow(CSR_FUSED_ENGINE, "run_csr_fused_engine", CSR_FUSED_BASE),
    GuardRow(LIFTED_FUSED_ENGINE, "run_lifted_fused_engine",
             LIFTED_FUSED_BASE),
    GuardRow(DYNAMIC_ENGINE, "run_dynamic_engine", DYNAMIC_BASE),
    GuardRow(IDONTWANT_ENGINE, "run_idontwant_engine", IDONTWANT_BASE),
    GuardRow(CHOKE_ENGINE, "run_choke_engine", CHOKE_BASE),
)

#: all row names, for reporting (scripts/analyze.py)
ALL_ROWS = tuple(ENGINES) + tuple(r.name for r in DERIVED_ROWS)


def run(update: bool | None = None, root: str | None = None) -> list:
    """The full harness over every row of the registry. Returns a list
    of failure strings (empty = pass). ``update`` (default: env
    ANALYZE_UPDATE) rewrites the schema baseline from this run instead
    of comparing."""
    if update is None:
        update = bool(os.environ.get("ANALYZE_UPDATE"))
    baseline = None if update else load_baseline(root)
    if baseline is None and not update:
        return [
            f"{SCHEMA_NAME} missing — run ANALYZE_UPDATE=1 "
            "scripts/analyze.py to record the baseline"
        ]
    failures: list[str] = []
    schemas: dict[str, list] = {}
    for name in ENGINES:
        try:
            schemas[name] = run_engine(name, baseline)
        except GuardViolation as e:
            failures.append(str(e))
        except Exception as e:  # noqa: BLE001 — any crash is a finding
            failures.append(f"[{name}] harness crashed: "
                            f"{type(e).__name__}: {str(e)[:300]}")

    def base_rows_of(base: str):
        if update:
            return schemas.get(base)
        return ((baseline or {}).get("engines", {})
                .get(base) or {}).get("leaves")

    for row in DERIVED_ROWS:
        base_rows = base_rows_of(row.base)
        if base_rows is None:
            # a hard failure, like check_schema's missing-baseline case
            # — otherwise leaf drift in a derived row would pass
            # silently whenever its base rows are absent (truncated
            # baseline, or the base harness crashed on an update run)
            failures.append(
                f"[{row.name}] no {row.base!r} schema rows to validate "
                "against (committed baseline missing the engine, or its "
                "harness failed on this update run)"
            )
            continue
        try:
            globals()[row.runner](base_rows)
        except GuardViolation as e:
            failures.append(str(e))
        except Exception as e:  # noqa: BLE001 — any crash is a finding
            failures.append(f"[{row.name}] harness crashed: "
                            f"{type(e).__name__}: {str(e)[:300]}")
    if update and not failures:
        write_baseline(schemas, root)
    return failures
