"""Multi-round phase engine: r delivery rounds per dispatch, control once.

The reference runs *continuous* delivery (every RPC is forwarded the
moment validation finishes) against a 1 Hz maintenance heartbeat
(gossipsub.go:1278-1301) — message hops are ~ms apart while GRAFT/PRUNE/
IHAVE/IWANT/score refresh happen ~1000x less often. The per-round step
(`make_gossipsub_step`) compresses that to "control every hop": a
deliberately *heavier* coupling than the reference's. This module builds
the step the other way — faithful to the reference's timing shape — by
batching ``rounds_per_phase`` (r) delivery rounds into ONE jitted phase:

  * control plane (wire exchange, GRAFT/PRUNE ingest, PX connect, IHAVE
    ingest, IWANT service, gater draw, score attribution, heartbeat) runs
    once per phase — control latency becomes r rounds, the analogue of
    the reference's heartbeat-granularity control;
  * the data plane (publish allocation, mesh/fanout/flood push, seen-
    cache dedup, first-arrival attribution, mcache insertion) runs every
    sub-round, so per-hop delivery latency is UNCHANGED — the
    propagation CDF keeps 1-round resolution via per-sub-round
    ``first_round`` stamps.

Perf shape: the sub-round body is computed *sender-side* — each sender
composes what it pushes per edge (mesh/fanout carry & fwd & not-echo) so
the whole data exchange crosses the edge involution in ONE [N,K,W]
gather, vs three for the receiver-side form (fwd peer-gather + echo
edge-gather + carry edge-gather). On the sharded mesh that is one set of
halo permutes per sub-round. The two forms are boolean-algebra equal;
tests/test_phase.py pins r=1 phase == per-round step bit-exactly.

Edge layout (round 15): every cross-peer gather here — the sub-round
sender-side exchange AND the stacked coalesced control head — goes
through ``net.edge_gather``/``net.peer_gather``, so a
``cfg.edge_layout="csr"`` build (ops/csr.py, with a matching
``Net.build(edge_layout="csr")``) routes the whole phase over the flat
[E] edge space with zero runtime branching; prepare_step_consts
rejects a layout mismatch, and tests/test_csr.py pins phase-engine
dense-vs-CSR bit-exactness at r∈{4,8} with chaos on.

Round 7 (cfg.wire_coalesced, the default) restructures the rest of the
phase the same way — launch count over everything else, because at the
12.5k shard BOTH terms of rate = 1/(shard_ms + ici_ms) are
launch-overhead, not bytes:
  * the CONTROL HEAD coalesces into one stacked wire exchange
    (gossipsub.control_exchange_coalesced): control outboxes + score
    plane + IWANT mcache window + (when weighted) the P5 app plane
    cross the edge involution in ONE gather — the phase's halo budget
    drops from 16·(r+4) to 16·(r+1) permutes (the number the v5e-8
    projection charges; tests/test_collectives.py pins it exactly);
  * the per-sub-round PUBLISH ALLOCATION hoists to the head
    (state.PhasePubPlan): slot/index math, recycled-slot keep masks,
    origin pub words and message-table snapshots precompute as wide
    ops, replacing r allocate_publishes calls' tiny-kernel swarm
    ([M]-table scatters, cursor scalar chains — the round-6 profile's
    dominant launch pool);
  * the ATTRIBUTION ACCUMULATORS' [N, W] planes (new / recv /
    accepted) fold as lanes of one stacked tensor (_AccStack) — one OR
    + one keep-AND per sub-round for all of them — and the shared
    keep-clears go through bitset.masked_keep. (Round 7 stacked the
    [N, K, W] planes too; since PR 40 each of those folds in a buffer
    of its own: on the chip at 50k peers the concatenated stacks cost
    the sub-round gathers their fast-memory tables and launches
    besides, see _AccStack.)
Measured on this image's XLA:CPU at N=12.5k r=16: 410.9 -> 85.1
executed kernels/round (docs/PERF.md round-7 table). The legacy
per-plane path stays selectable (cfg.wire_coalesced=False) and
bit-identical (tests/test_phase_stacked.py compares full state trees
across gossipsub/floodsub/randomsub at r in {1, 8, 16}).

Score/gater attribution is folded over the phase in packed word planes:
every (edge, msg) pair transmits at most once per phase (the fwd set is
one-shot and IWANT retransmissions are capped per phase head), so OR
accumulation preserves the exact transmission multiset. The P3 window
gate is evaluated per sub-round against each arrival's own tick
(on_deliveries(mesh_credit_words=...)), keeping window semantics at
1-round resolution.

Known deviations vs the per-round step, all bounded in PARITY.md:
  * control actions (grafts taking effect, gossip emission, IWANT
    service, score refresh, gater decisions) lag up to r-1 rounds — the
    reference's own control lags up to a full heartbeat interval;
  * deliveries of a message whose slot is recycled by a *later publish
    in the same phase* earn no score/gater credit (per-round attribution
    ran before each round's publishes; phase attribution runs at phase
    end, after recycled columns are cleared). Slots live M/publish-rate
    rounds, so this touches only messages already ~fully propagated;
  * heartbeat-tick quantization: the heartbeat always executes at the
    phase TAIL with ``tick_last``, while the schedule owner
    (driver.heartbeat_schedule) flags a phase when ANY tick in its
    window [t, t+r) is ≡ 0 (mod heartbeat_every). When heartbeat_every
    is a multiple of rounds_per_phase (every bench/driver default) the
    nominal tick IS the phase tail and there is no drift; when it is
    not, the executed heartbeat tick drifts up to r-1 rounds from the
    nominal schedule tick, so backoff expiry and fanout-TTL expiry —
    which compare against tick — quantize to phase tails. Callers
    choosing ``heartbeat_every % rounds_per_phase != 0`` accept that
    quantization (the reference's own timers are heartbeat-quantized
    the same way: backoff slack, gossipsub.go:1596).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from .. import state as state_mod
from ..chaos import faults as chaos_faults
from ..ops import bitset
from ..perf import spans, stages
from ..score.engine import (
    apply_delivery_counts,
    on_deliveries,
    per_slot_counts,
    slot_topic_words,
)
from ..score.gater import gater_on_round
from ..state import Net, PhasePubPlan, allocate_publishes, wrap_csr_resident
from ..trace.events import EV
from .common import RoundInfo, accumulate_round_events, finish_delivery
from .gossipsub import (
    GossipSubConfig,
    GossipSubState,
    accept_gates,
    apply_peer_transitions,
    apply_validation_throttle,
    control_exchange,
    control_exchange_coalesced,
    fanout_carry_words_packed,
    handle_graft_prune,
    pack_fanout_peers,
    unpack_fanout_peers,
    handle_ihave,
    heartbeat,
    iwant_responses,
    joined_msg_words,
    live_step_views,
    merge_extra_tx,
    origin_msg_words,
    prepare_step_consts,
    px_connect,
    sender_carry_words,
    update_fanout_on_publish,
)


class PhaseAdmissionError(ValueError):
    """The phase publish schedule can re-allocate message slots WITHIN
    one phase (``rounds_per_phase * pub_width > msg_slots``) — the
    deferred recycled-slot clears' exactness assumption breaks, so the
    built step refuses at trace time (ADVICE round 5, item 2: the
    engine layer enforces what previously only ``api.Network._run_phase``
    enforced). Cap admitted publishes (``admission_capped=True`` after
    doing so), raise ``msg_slots``, or lower the publish rate."""


class _AccStack:
    """The phase's attribution accumulators. Every live plane takes the
    same two word-algebra folds per sub-round: OR the sub-round's update
    in, AND the recycled-slot keep mask.

    Stacked (``cfg.wire_coalesced``), the ``[N, W]`` planes of one part
    (``new``, ``recv``, ``accepted``) ride as lanes of ONE ``[N, C, W]``
    buffer and share one OR and one keep-AND a sub-round (the round-7
    form: at the 12.5k shard the phase engine was fusion-count-bound,
    docs/PERF.md round-6 table, and a lane is cheaper than a launch). An
    ``[N, K, W]`` plane (``trans``, ``mcw``, ``dup``, ``rejw``, ``ignw``,
    ``dupt``) folds in a buffer of its own. Until PR 40 the K-wide planes
    of a part were concatenated into one ``[N, 2K, W]`` / ``[N, 3K, W]``
    stack too: at ``sybil-50k`` those 70 and 105 MB temporaries, live
    beside a 56 MB gather table, made the TPU compiler leave the table of
    five of the cell's ten 1.2M-row edge gathers in HBM (2.86 x the time
    of the five it prefetched into the fast memory space), and the
    concatenates were ops of their own (711 -> 572 fusions in a
    dispatch's text). With every table in the fast space that cell runs
    53.10 -> 91.73 rounds/s (PERF.md §6, PR 40).

    ``stacked=False`` keeps every plane a separate array with separate
    folds — the legacy round-4..6 kernel structure — selected by
    ``cfg.wire_coalesced=False`` for A/B; both paths run the same
    updates in the same order, so they are bit-identical by
    construction (pinned by tests/test_phase_stacked.py)."""

    def __init__(self, specs, n: int, w: int, stacked: bool):
        # specs: (name, lanes, keep_masked, part); lanes=1 is an [N, W]
        # plane, lanes=k an [N, k, W] plane. Stacked, the [N, W] planes of
        # one ``part`` (perf/stages.PARTS; None: the honest planes every
        # scored build folds) share one buffer (``bufs``); every other
        # plane is an array of its own (``planes``). Each fold runs under
        # its plane's part scope, so the trace can tell what the P3 / P4
        # and the gater planes cost
        self.specs = tuple(specs)
        self.offs = {}      # stacked name -> (part, its lane in the group)
        self.groups = {}    # part -> [(name, keep_masked)], stacked planes
        for name, lanes, masked, part in self.specs:
            if stacked and lanes == 1:
                group = self.groups.setdefault(part, [])
                self.offs[name] = (part, len(group))
                group.append((name, masked))
        self.bufs = {
            part: jnp.zeros((n, len(group), w), jnp.uint32)
            for part, group in self.groups.items()
        }
        self.planes = {
            name: jnp.zeros((n, w) if lanes == 1 else (n, lanes, w),
                            jnp.uint32)
            for name, lanes, _, _ in self.specs if name not in self.offs
        }

    @staticmethod
    def _scope(part):
        return stages.part(part) if part else contextlib.nullcontext()

    def __contains__(self, name: str) -> bool:
        return name in self.offs or name in self.planes

    def or_(self, updates: dict) -> "_AccStack":
        """OR the sub-round's updates in — one wide op for the stacked
        lanes of a part, one a plane otherwise. Every live plane must
        have an update (all accumulation sites run every sub-round)."""
        for part, group in self.groups.items():
            with self._scope(part):
                n, _, w = self.bufs[part].shape
                upd = jnp.concatenate(
                    [updates[name].reshape(n, 1, w) for name, _ in group],
                    axis=1)
                self.bufs[part] = self.bufs[part] | upd
        for name, _, _, part in self.specs:
            if name in self.planes:
                with self._scope(part):
                    self.planes[name] = self.planes[name] | updates[name]
        return self

    def keep(self, keep_w: jax.Array) -> "_AccStack":
        """AND the recycled-slot keep mask into every keep-masked plane —
        one wide op for the stacked lanes of a part (a lane that must
        survive recycling would ride an all-ones mask), one a plane
        otherwise (the exact-trace dup plane, not keep-masked, takes
        none)."""
        for part, group in self.groups.items():
            with self._scope(part):
                lane_masked = jnp.asarray([m for _, m in group], bool)
                mask = jnp.where(
                    lane_masked[:, None], keep_w[None, :],
                    jnp.uint32(0xFFFFFFFF))
                self.bufs[part] = self.bufs[part] & mask[None]
        for name, lanes, masked, part in self.specs:
            if masked and name in self.planes:
                km = keep_w[None, :] if lanes == 1 else keep_w[None, None, :]
                with self._scope(part):
                    self.planes[name] = self.planes[name] & km
        return self

    def get(self, name: str, default=None):
        if name in self.planes:
            return self.planes[name]
        if name not in self.offs:
            return default
        part, lane = self.offs[name]
        return self.bufs[part][:, lane, :]


@spans.span("setup.step_build")
def make_gossipsub_phase_step(
    cfg: GossipSubConfig,
    net: Net,
    rounds_per_phase: int,
    score_params=None,
    heartbeat_interval: float = 1.0,
    gater_params=None,
    dynamic_peers: bool = False,
    adversary_no_forward: np.ndarray | None = None,
    sub_knowledge_holes: np.ndarray | None = None,
    score_counts: bool | None = None,
    exact_counters: bool = False,
    admission_capped: bool = False,
    telemetry=None,
    adversary=None,
    lift_scores: bool = False,
):
    """Build the jitted multi-round phase step.

    With ``lift_scores=True`` (round 16, docs/DESIGN.md §16) the step
    takes a trailing TRACED ``score_plane`` (score.params.ScoreParams):
    weights/decays/thresholds read from the plane, one compiled
    program across weight sets, bit-exact vs the static build at
    matched values. The phase engine's static weight elision
    (p3_live/p4_live) is a build-time STRUCTURE decision on weight
    values, so the lifted build pins the conservative all-planes-live
    structure — LIFT_AUDIT.json records those reads as the guarded
    elision sites they are.

    phase_step(state, pub_origin[r,P], pub_topic[r,P], pub_valid[r,P],
               [up_next], *, do_heartbeat) -> state     (tick advances by r)

    ``do_heartbeat`` is a REQUIRED static bool: the caller owns the
    heartbeat schedule (`driver.scan_rounds` does this for you — phases
    whose tick window [t, t+r) contains a multiple of
    ``cfg.heartbeat_every`` must pass True). The heartbeat runs at most
    once per phase, at the phase tail, with the phase's last tick.

    Publish batches land per sub-round: ``pub_*[i]`` is injected at tick
    ``t + i`` exactly as the per-round step would, so workload timing and
    the propagation CDF are directly comparable.

    ``cfg.wire_coalesced`` (default True) selects the round-7 stacked
    data plane — coalesced control-head exchange, head publish plan,
    stacked accumulator folds (see the module docstring); False builds
    the legacy per-plane structure, bit-identical, for A/B.

    **Admission invariant** (enforced here since round 6): a phase may
    admit at most ``msg_slots // 2`` publishes — slots recycled WITHIN a
    phase wipe their in-flight receipts before the boundary drain can
    observe them, and the deferred recycled-slot clears below additionally
    assume a slot is never re-allocated within its phase. The API layer
    caps admission (api.Network._run_phase); direct drivers feeding full
    ``[r, P]`` schedules can exceed it silently (e.g. pub_width=4, r=32,
    M=64 = 128 potential publishes/phase), so the built step WARNS at
    trace time when ``rounds_per_phase * pub_width > msg_slots // 2``.
    ``admission_capped=True`` (the API's builds) suppresses the warning —
    the caller certifies it enforces the flat cap itself.

    ``telemetry`` (a telemetry.TelemetryConfig) appends the time-series
    recorder at the phase TAIL: ONE panel row per PHASE
    (``rounds_per_row = r`` — the same cadence caveat the drain and the
    chaos metrics document), whose EV deltas cover all r sub-rounds plus
    the control head and heartbeat, so summed rows still reconcile
    bit-for-bit against the drained counters. The state must be built
    with the same config (``GossipSubState.init(telemetry=...)``) and a
    driver must start ticks at a multiple of r (every scan/driver does —
    the row index is ``tick0 // r``). None elides the plane statically.

    ``adversary`` (a chaos.adversary.Adversary) arms the vectorized
    attack suite (docs/DESIGN.md §13) at phase cadence: the data-plane
    behaviors (drop-on-forward, censorship) mask each sub-round's
    SENDER-side transmit composition with that round's own activity
    window, and the heartbeat-cadence behaviors (lie-in-IHAVE,
    graft-spam, self-promotion) ride the phase-tail heartbeat. None
    elides the plane statically (tests/test_adversary.py pins
    bit-exact adversary-off parity on the stacked wire path).
    """
    r = int(rounds_per_phase)
    assert r >= 1
    if lift_scores and not cfg.score_enabled:
        raise ValueError(
            "lift_scores=True needs cfg.score_enabled — the lifted "
            "plane parameterizes the v1.1 score machinery"
        )
    if cfg.router is not None:
        raise ValueError(
            "the phase engine predates the router plane (docs/DESIGN.md "
            "§24) — IDONTWANT suppression, choking, and the latency ring "
            "hook the per-round delivery composition; use "
            "make_gossipsub_step for router builds"
        )
    consts = prepare_step_consts(
        cfg, net, score_params, heartbeat_interval, gater_params,
        sub_knowledge_holes, adversary_no_forward, adversary,
    )
    adv = consts.adv
    tp = consts.tp
    # chaos plane: None elides it statically (the traced program is the
    # pre-chaos one — tests/test_chaos.py pins bit-exactness and `make
    # chaos-smoke` pins the compiled kernel census). When on, the control
    # head's outage mask is ONE AND on the stacked wire gather (net_w),
    # and each data sub-round applies its own round's link mask; the
    # Gilbert–Elliott chain advances once per sub-round, so fault
    # sequences match the per-round engine's cadence. Scheduled builds
    # take ONE link_deny per phase — partitions quantize to phase
    # boundaries, exactly like the churn plane's peer transitions.
    chaos = chaos_faults.resolve(cfg.chaos)
    chaos_sched = chaos is not None and chaos.scheduled
    adv_self = (
        jnp.asarray(adversary_no_forward, bool)
        if adversary_no_forward is not None else None
    )
    n_peers, k_dim = net.nbr.shape
    val_delay = cfg.validation_delay_rounds
    use_counts = bool(score_counts)
    # read through the module at build time (tests move the crossover)
    scatter_form = n_peers >= state_mod.SCATTER_FORM_MIN_PEERS
    # static weight elision: the topic score params are jit constants, so
    # attribution planes whose consuming weights are zero EVERYWHERE can
    # be skipped at build time. The mmd counter has TWO consumers: P3
    # (deficit via w3, compute_scores) and the sticky P3b mesh-failure
    # penalty (on_prune folds deficit^2 into mfp whenever w3b != 0 and
    # thr3 > 0 — score/engine.py on_prune), so the in-window mesh-credit
    # plane stays live if EITHER is weighted for any topic. The honest-
    # net bench configs zero both, dropping one of the two [N,K,W]
    # OR+store passes per sub-round. imd's only consumer is P4 via w4.
    #
    # ``exact_counters=True`` disables elision outright: scores are
    # bit-identical either way (the elided term multiplies by zero), but
    # elision leaves the UNREAD counters non-reference-faithful (mmd
    # undercounts near-first credit, mfp can overcount — see the loop
    # comment below). The reference's inspect surface is exact always
    # (score.go:120-177), so any build with a score inspector / snapshot
    # consumer attached (api.Network: peer_score_snapshots) must pass
    # this; the tracer-detached bench keeps elision.
    _w3 = np.asarray(consts.tpa.w3)
    _w3b = np.asarray(consts.tpa.w3b)
    _thr3 = np.asarray(consts.tpa.thr3)
    p3_live = exact_counters or bool(
        np.any(_w3 != 0.0) or np.any((_w3b != 0.0) & (_thr3 > 0.0))
    )
    p4_live = exact_counters or bool(np.any(np.asarray(consts.tpa.w4) != 0.0))
    if lift_scores:
        # a TRACED weight cannot drive build-time structure: the lifted
        # program keeps every attribution plane live so ONE compile is
        # correct for every weight set the plane sweeps (the elision
        # sites above are LIFT_AUDIT.json's guarded-elision evidence)
        p3_live = p4_live = True

    # ``stage(name)`` moves one `gs.*` scope along the phase (perf/
    # stages.py); the callees that are a stage of their own nest theirs
    # inside it
    @stages.with_cursor
    def _phase(stage, st: GossipSubState, pub_origin, pub_topic, pub_valid,
               up_next, do_heartbeat: bool, link_deny=None,
               score_plane=None) -> GossipSubState:
        # lifted score plane (round 16): the VALUE-proved score fields
        # read from the traced plane; score_plane=None is the static
        # path, byte-identical to the pre-lift program (thr=cfg routes
        # threshold reads to the same Python floats)
        # a combined candidate plane (round 20) nests score + MeshParams;
        # detect by its `mesh` attribute, bare ScoreParams is unchanged
        mesh_plane = getattr(score_plane, "mesh", None)
        if score_plane is not None:
            sc = score_plane.score if mesh_plane is not None else score_plane
            tp_r = sc.gather(net.my_topics)
            sp_r, thr, wrt = sc, sc, sc.window_rounds
        else:
            tp_r, sp_r, thr, wrt = (tp, consts.score_params, cfg,
                                    consts.window_rounds_t)
        msh = cfg if mesh_plane is None else mesh_plane
        # telemetry: counters at phase ENTRY, before the churn plane's
        # ADD/REMOVE_PEER accounting (the phase-tail row's deltas cover
        # the whole phase, so the panel sums telescope exactly)
        ev_prev = st.core.events if telemetry is not None else None
        # ---- control head (once per phase) ------------------------------
        stage("control_head")
        if dynamic_peers:
            # the churn part: what a dynamic build pays beyond a static
            # one at the head (the transitions, the traced liveness views)
            with stages.part("churn"):
                st, live = apply_peer_transitions(cfg, net, st, up_next, tp_r)
                net_l, nbr_sub_l, flood_from_l, nbr_sub_words_l = (
                    live_step_views(cfg, net, st, live, consts))
                # the publish gate: a publish whose origin is down (as
                # this head left ``up``) does not happen. Its slot is
                # allocated as the ring says and nobody holds it
                # (state.PhasePubPlan); the fanout update and the publish
                # count read its entry as padding
                pub_holder = jnp.where(
                    st.up[jnp.clip(pub_origin, 0)], pub_origin, -1)
        else:
            live = None
            net_l, nbr_sub_l, flood_from_l, nbr_sub_words_l = live_step_views(
                cfg, net, st, None, consts
            )
            pub_holder = None
        core = st.core
        tick0 = core.tick
        m = core.msgs.capacity
        w = bitset.n_words(m)

        # the admission invariant, enforced at trace time (shapes are
        # static): see the builder docstring. ADVICE round 5 item 2.
        # Two tiers: a schedule that can exceed msg_slots WITHIN one
        # phase would re-allocate a slot inside its own phase — the
        # deferred recycled-slot clears are then WRONG, not merely
        # lossy, so that is a hard error; the (msg_slots//2, msg_slots]
        # band stays a warning (in-flight receipts of the previous
        # occupants can be wiped before the boundary drain sees them).
        if not admission_capped:
            flat_cap = r * pub_origin.shape[-1]
            if flat_cap > m:
                raise PhaseAdmissionError(
                    f"phase publish capacity rounds_per_phase*pub_width = "
                    f"{r}*{pub_origin.shape[-1]} = {flat_cap} exceeds "
                    f"msg_slots = {m}: a slot can be re-allocated WITHIN "
                    "one phase, which the deferred recycled-slot clears "
                    "assume never happens. Cap admitted publishes at "
                    f"{m // 2} per phase (api.Network._run_phase does; "
                    "pass admission_capped=True once you do), raise "
                    "msg_slots, or lower the publish rate."
                )
            if flat_cap > m // 2:
                import warnings

                warnings.warn(
                    f"phase publish capacity rounds_per_phase*pub_width = "
                    f"{r}*{pub_origin.shape[-1]} exceeds msg_slots//2 = "
                    f"{m // 2}: slots recycled within a phase silently wipe "
                    "in-flight receipts. Cap admitted publishes at "
                    f"{m // 2} per phase (api.Network._run_phase does), "
                    "raise msg_slots, or lower the publish rate.",
                    stacklevel=3,
                )

        acc_ok, acc_msg = accept_gates(cfg, net_l, st, gater_params,
                                       core.key, tick0, thr=thr)

        # ---- chaos plane: the phase-head round's link outages ----------
        # The control head crosses the wire ONCE, at round tick0 — its
        # outage mask is round tick0's, applied as a single AND on the
        # (stacked) wire gather via net_w. Data sub-rounds each apply
        # their own round's mask below (gate_i); the GE chain advances
        # once per sub-round so the fault cadence matches the per-round
        # engine's.
        if chaos is not None:
            chaos_seed = chaos_faults.chaos_seed(core.key)
            ge_bad = core.chaos.ge_bad if core.chaos is not None else None
            link_ok0, ge_bad = chaos_faults.round_link_ok(
                chaos, chaos_seed, net.nbr, tick0, ge_bad, link_deny,
            )
            net_w = net_l.replace(nbr_ok=net_l.nbr_ok & link_ok0)
            n_link_down = (
                chaos_faults.count_links_down(net.nbr, net_l.nbr_ok, link_ok0)
                if cfg.count_events else None
            )
        else:
            link_ok0 = ge_bad = n_link_down = None
            net_w = net_l

        if cfg.wire_coalesced:
            # ONE stacked gather for the whole control head: control
            # outboxes + score plane + IWANT window (+ the P5 app plane
            # when its weight is live) — the phase's halo budget drops
            # from 16·(r+4) to 16·(r+1) permutes (perf/projection.py)
            include_app = (
                cfg.score_enabled
                and consts.score_params.app_specific_weight != 0.0
            )
            (graft_in_raw, prune_in_raw, ihave_in_raw, px_in_raw,
             nbr_score_of_me, window_g, app_g) = control_exchange_coalesced(
                cfg, net, net_w, st, include_app=include_app
            )
        else:
            (graft_in_raw, prune_in_raw, ihave_in_raw, px_in_raw,
             nbr_score_of_me) = control_exchange(cfg, net, net_w, st)
            window_g = app_g = None
        st2, prune_resp, px_resp, px_ok, n_graft, n_prune = handle_graft_prune(
            cfg, net_l, st, tp_r, acc_ok, graft_in_raw, prune_in_raw,
            px_in_raw, thr=thr, msh=msh,
        )
        events = st.core.events
        if cfg.count_events:
            events = events.at[EV.GRAFT].add(n_graft).at[EV.PRUNE].add(n_prune)
        edge_live_next = px_connect(cfg, net, net_l, st, px_ok, live)
        # the IWANT-service window gather rides the wire view (net_w):
        # responses on a flapped link are lost and the retransmission
        # counters don't tick (the data never arrived)
        st2, iwant_resp = iwant_responses(cfg, net_w, st2, nbr_score_of_me,
                                          window_g=window_g, thr=thr)
        st2 = handle_ihave(cfg, net_l, st2, joined_msg_words(net_l, core.msgs),
                           acc_ok, ihave_in_raw, thr=thr)
        if consts.sender_fwd_ok is not None:
            with stages.part("attrib"):
                iwant_resp = jnp.where(
                    consts.sender_fwd_ok[:, :, None], iwant_resp,
                    jnp.uint32(0)
                )
        # adversary data plane: an active drop/censor attacker withholds
        # its IWANT service too (the responses ride sub-round 0, so the
        # head tick's activity window applies) — receiver-side nbr-view
        # constants, zero extra halo permutes
        n_adv_drop = None
        if adv is not None and adv.data_plane:
            iwant_resp, rem_resp = adv.mask_transmit_nbr(
                tick0, iwant_resp, core.msgs)
            if cfg.count_events:
                n_adv_drop = bitset.popcount(
                    rem_resp, axis=None).sum().astype(jnp.int32)
        iwant_resp = jnp.where(acc_msg[:, :, None], iwant_resp, jnp.uint32(0))

        # phase-fixed data-plane constants (the r-round control latency:
        # mesh membership, scores, accept gates hold for the whole phase)
        mesh2 = st2.mesh
        if cfg.score_enabled:
            send_score_ok = st.scores >= thr.publish_threshold
        else:
            send_score_ok = net_l.nbr_ok
        # floodsub-semantics edges, sender side: I speak only floodsub =>
        # I push everything on every live edge (floodsub.go:76-100); my
        # neighbor speaks only floodsub => I push everything I'd publish
        # to it, score-gated (gossipsub.go:973-978)
        flood_send = (
            (consts.i_am_floodsub[:, None] & net_l.nbr_ok)
            | (flood_from_l & send_score_ok)
        )
        recv_gate = net_l.nbr_ok & acc_msg  # [N,K] receiver-side edge gate
        if cfg.flood_publish:
            fp_ok = send_score_ok if cfg.score_enabled else net_l.nbr_ok

        # ---- data loop: r delivery sub-rounds ---------------------------
        stage("data_round")
        msgs = core.msgs
        dlv = core.dlv
        mcache = st2.mcache
        iwant_out = st2.iwant_out
        served_lo, served_hi = st2.served_lo, st2.served_hi
        promise_mid = st2.promise_mid
        fanout_st = st2  # fanout_topic/lastpub evolve per sub-round
        # fanout peers ride the loop in packed [N,F,ceil(K/32)] u32 form
        # (the bool [N,F,K] plane is a pathological per-sub-round write
        # target — see pack_fanout_peers); unpacked back at the phase tail
        fp_pack = (
            pack_fanout_peers(st2.fanout_peers)
            if cfg.fanout_slots > 0 else None
        )

        zkw = jnp.zeros((n_peers, k_dim, w), jnp.uint32)
        zw = jnp.zeros((n_peers, w), jnp.uint32)
        keep_acc = jnp.full((w,), 0xFFFFFFFF, jnp.uint32)
        s_slots = net.my_topics.shape[1]
        # Two score-attribution paths. The COUNT path (inline validation
        # only) reduces each sub-round's transmit tensor to per-
        # (peer,slot,edge) popcounts at arrival time — no [N,K,W]
        # attribution plane survives the loop, and credit lands exactly
        # when the per-round engine would land it, including a message's
        # death round. Measured on the real chip (N=100k) it LOSES to the
        # plane path (r=8: 1048 vs 1200 rounds/s; r=16: 1250 vs 1365):
        # the r-per-phase popcount trees cost more VPU time than the
        # plane ORs cost HBM stores on this libtpu. The PLANE path is
        # therefore the default; the count path stays as an opt-in
        # (score_counts=True) for workloads where
        # within-phase slot recycling would otherwise shave score credit,
        # and is required-off for the async-validation pipeline (pend_dup
        # needs cross-sub-round word algebra).
        count_score = cfg.score_enabled and val_delay == 0 and use_counts
        plane_score = cfg.score_enabled and not count_score
        # elision keeps the score values bit-identical (the elided term
        # multiplies by a zero weight everywhere) but changes what the
        # unread counters show to introspection: imd reads 0; mmd still
        # accrues first-arrival credit (on_deliveries adds it regardless)
        # but not the near-first/window portion — an undercount — and
        # consequently mfp (fed by on_prune's thr3 - mmd deficit) can
        # OVERcount when w3b==0 with thr3>0. All pinned by tests/
        # test_phase.py::test_phase_static_weight_elision_scores_exact
        # (an attempted round-4 optimization derived P4 from the
        # first-edge plane, on the theory that invalid messages travel
        # exactly one hop; FALSIFIED by the r=1 bit-exactness tests — an
        # origin advertises and IWANT-serves its own invalid publishes
        # from mcache, so invalid arrivals repeat across rounds on the
        # same edge. The trans plane stays.)
        # the live attribution planes, folded through _AccStack: when
        # cfg.wire_coalesced one OR + one keep-AND per sub-round over the
        # stacked [N, W] planes and one each a K-wide plane (a buffer of
        # its own since PR 40: stacked too they kept the sub-round
        # gathers' tables out of the chip's fast memory), per-plane folds
        # throughout (the legacy kernel structure) otherwise. The
        # exact-trace dup plane is the one NON-keep-masked plane — see the
        # dup_trace comment below.
        acc_specs = []
        if plane_score:
            acc_specs += [("new", 1, True, None), ("recv", 1, True, None)]
        if plane_score or cfg.gater_enabled:
            acc_specs += [("accepted", 1, True, None)]
        if plane_score and p4_live:
            acc_specs += [("trans", k_dim, True, "attrib")]
        if plane_score and p3_live:
            acc_specs += [("mcw", k_dim, True, "attrib")]
        if cfg.gater_enabled:
            acc_specs += [("dup", k_dim, True, "gater"),
                          ("rejw", k_dim, True, "gater"),
                          ("ignw", k_dim, True, "gater")]
        if cfg.trace_exact:
            acc_specs += [("dupt", k_dim, False, None)]
        accs = _AccStack(acc_specs, n_peers, w, stacked=cfg.wire_coalesced)
        if count_score:
            zsc = jnp.zeros((n_peers, s_slots, k_dim), jnp.float32)
            fmd_counts, mmd_counts, imd_counts = zsc, zsc, zsc
        if cfg.gater_enabled:
            n_validated_acc = jnp.zeros((n_peers,), jnp.int32)
            n_throttled_acc = jnp.zeros((n_peers,), jnp.int32)
        if cfg.count_events:
            cnt = dict(n_deliver=jnp.int32(0), n_reject=jnp.int32(0),
                       n_duplicate=jnp.int32(0), n_rpc=jnp.int32(0),
                       n_drop=jnp.int32(0))
            n_pub = jnp.int32(0)
        info = None

        # phase-head batched publish allocation (state.PhasePubPlan): the
        # whole [r, P] schedule's slot/index math, keep masks, origin pub
        # words, and message-table snapshots as one set of wide head ops,
        # replacing r calls to allocate_publishes (~15 tiny kernels each
        # — the dominant launch swarm at the 12.5k shard)
        plan = (
            PhasePubPlan(msgs, n_peers, tick0, pub_origin, pub_topic,
                         pub_valid, pub_holder=pub_holder)
            if cfg.wire_coalesced else None
        )
        pub_origin_held = pub_origin if pub_holder is None else pub_holder

        # membership word planes: on NARROW topic universes (T <= 8) the
        # planes are carried incrementally — a sub-round changes the
        # slot->topic mapping only at its <=P publish slots, so clearing
        # recycled columns + OR-ing per-publish one-hot word columns
        # replaces the per-sub-round recompute (measured +7% on the
        # default bench). On wide universes (eth2's T=64) the batched
        # compare+pack FUSES into its consumers and the incremental
        # dependency chain measured 9% SLOWER, so those recompute.
        incr_members = net.n_topics <= 8
        if incr_members:
            slotw = slot_topic_words(net_l, msgs.topic)
            joined_w = joined_msg_words(net_l, msgs)
        if plan is not None:
            # the origin word plane rides the loop incrementally on the
            # plan path: (origin_w & keep) | pub_words IS the next
            # sub-round's origin_msg_words (the recycled columns now
            # belong to the new publishes), replacing an [M]-scatter per
            # sub-round with one wide fold
            origin_w = origin_msg_words(net_l, msgs)

        n_iwant_rec = None
        for i in range(r):
            tick_i = tick0 + i
            # chaos: this sub-round's link mask (round tick0's was already
            # computed at the head — the control head shares it)
            if chaos is not None:
                if i == 0:
                    link_ok_i = link_ok0
                else:
                    link_ok_i, ge_bad = chaos_faults.round_link_ok(
                        chaos, chaos_seed, net.nbr, tick_i, ge_bad, link_deny,
                    )
                    if cfg.count_events:
                        n_link_down = n_link_down + chaos_faults.count_links_down(
                            net.nbr, net_l.nbr_ok, link_ok_i
                        )
                gate_i = recv_gate & link_ok_i
            else:
                gate_i = recv_gate
            if plan is not None:
                # the table as allocate_publishes would have left it after
                # sub-rounds < i (bit-identical snapshot; see PhasePubPlan)
                msgs = plan.msgs_at(i)
            if not incr_members:
                slotw = slot_topic_words(net_l, msgs.topic)
                joined_w = joined_msg_words(net_l, msgs)
            if plan is None:
                origin_w = origin_msg_words(net_l, msgs)

            # sender-side transmit composition: ONE edge gather per
            # sub-round carries the entire data plane
            carry = sender_carry_words(mesh2, slotw)
            if fp_pack is not None:
                carry = carry | fanout_carry_words_packed(
                    fp_pack, k_dim, fanout_st.fanout_topic, msgs.topic
                )
            carry = carry | jnp.where(
                flood_send[:, :, None], jnp.uint32(0xFFFFFFFF), jnp.uint32(0)
            )
            if cfg.flood_publish:
                # v1.1 flood-publish, sender-side fold (== the receiver-side
                # origin compare: nbr_score_of_me at the receiver IS the
                # sender's score of that edge; gossipsub.go:957-963)
                carry = carry | jnp.where(
                    fp_ok[:, :, None], origin_w[:, None, :], jnp.uint32(0)
                )
            send = carry & dlv.fwd[:, None, :] & ~dlv.fe_words
            if adv_self is not None:
                # adversary behavior vector: marked peers run control but
                # never transmit message data (sybilSquatter analogue)
                with stages.part("attrib"):
                    send = jnp.where(
                        adv_self[:, None, None], jnp.uint32(0), send
                    )
            if adv is not None and adv.data_plane:
                # scheduled drop/censor attackers mask their OWN rows
                # before the one edge gather (sender-side — the phase
                # engine's transmit composition), each sub-round under
                # its own tick's activity window; the removed bits are
                # the withheld-transmission attribution (sender-side —
                # an upper bound: the receiver's joined/origin/link
                # gates apply after the gather)
                send, rem_send = adv.mask_transmit_self(tick_i, send, msgs)
                if cfg.count_events:
                    n_adv_drop = n_adv_drop + bitset.popcount(
                        rem_send, axis=None).sum().astype(jnp.int32)
            trans = jnp.where(
                gate_i[:, :, None], net_l.edge_gather(send), jnp.uint32(0)
            )
            nm = ~origin_w
            if msgs.wire_block is not None:
                nm = nm & ~bitset.pack(msgs.wire_block)[None, :]
            trans = trans & (joined_w & nm)[:, None, :]

            pre_have = dlv.have
            dlv, info = finish_delivery(
                net_l, msgs, dlv, trans, tick_i,
                count_events=cfg.count_events, queue_cap=cfg.queue_cap,
                val_delay_topic=cfg.validation_delay_topic,
            )
            if i == 0:
                # IWANT responses computed at the phase head ride the first
                # sub-round (r-round service latency, like the reference's
                # heartbeat-batched gossip turnaround)
                have_pre_merge = dlv.have
                dlv, info = merge_extra_tx(
                    net_l, msgs, dlv, info, iwant_resp, tick_i,
                    count_events=cfg.count_events, queue_cap=cfg.queue_cap,
                    val_delay_topic=cfg.validation_delay_topic,
                )
                if chaos is not None and cfg.count_events:
                    # IWANT-recovery attribution (same arrival-cohort
                    # convention as the per-round step): first arrivals
                    # that rode the IWANT service
                    valid_w_head = (
                        plan.valid_words[0] if plan is not None
                        else bitset.pack(msgs.valid)
                    )
                    n_iwant_rec = bitset.popcount(
                        (dlv.have & ~have_pre_merge)
                        & valid_w_head[None, :], axis=None,
                    ).sum().astype(jnp.int32)
            acc_upd = {}
            if cfg.trace_exact:
                # pre-throttle, like the per-round step: throttled receipts
                # are fresh (traced Reject), not duplicates. Phase
                # resolution coarsens timestamps; totals stay exact. NOT
                # keep-masked below: a dup bit names the message its slot
                # held at arrival, attributed against the phase-START
                # slot->mid mapping (exact while slots outlive a phase —
                # the M >> r*P sizing every tracing config satisfies)
                acc_upd["dupt"] = (
                    info.trans
                    & ~(dlv.fe_words & info.recv_new_words[:, None, :])
                )
            valid_w_i = (
                plan.valid_words[i] if plan is not None
                else bitset.pack(msgs.valid)
            )
            if cfg.validation_capacity > 0:
                with stages.part("attrib"):
                    dlv, info, accepted_new, n_thr = apply_validation_throttle(
                        dlv, info, cfg.validation_capacity, m, valid_w_i
                    )
            else:
                accepted_new = info.new_words
                n_thr = None

            # ---- attribution accumulation (ONE stacked OR of word
            # planes when cfg.wire_coalesced, per-plane ORs otherwise, or
            # the direct per-slot count reduction; all exact — each
            # (edge,msg) transmits at most once per phase) ----------------
            if plane_score:
                acc_upd["new"] = info.new_words
                acc_upd["recv"] = info.recv_new_words
                if "trans" in accs:
                    acc_upd["trans"] = info.trans
            if "accepted" in accs:
                acc_upd["accepted"] = accepted_new
            if cfg.score_enabled and (p3_live or count_score):
                # P3 window gate at this arrival's own tick (score.go:
                # 944-974 markDuplicateMessageDelivery window check)
                with stages.part("attrib"):
                    msg_window = wrt[jnp.clip(msgs.topic, 0)]
                    within_i = bitset.pack(
                        (dlv.first_round >= 0)
                        & ((tick_i - dlv.first_round) <= msg_window[None, :])
                    )
            if count_score:
                valid3 = valid_w_i[None, None, :]
                mesh_w = info.trans & valid3 & within_i[:, None, :]
                fa_w = dlv.fe_words & info.new_words[:, None, :] & valid3
                ign_i = (
                    plan.ignored_words[i] if plan is not None
                    else bitset.pack(msgs.ignored)
                )
                inv_w = info.trans & ~(valid_w_i | ign_i)[None, None, :]

                mmd_counts = mmd_counts + per_slot_counts(mesh_w, slotw)
                fmd_counts = fmd_counts + per_slot_counts(fa_w, slotw)
                imd_counts = imd_counts + per_slot_counts(inv_w, slotw)
            elif plane_score and p3_live:
                with stages.part("attrib"):
                    mcw_i = info.trans & within_i[:, None, :]
                    if val_delay > 0:
                        # duplicates arriving while the message sits in
                        # the validation pipeline (score.go:712-718); the
                        # fresh first arrival earns credit at its verdict
                        pend_post = bitset.word_or_reduce(dlv.pending, axis=1)
                        fa_i = dlv.fe_words & info.recv_new_words[:, None, :]
                        mcw_i = mcw_i | (
                            info.trans & pend_post[:, None, :] & ~fa_i
                        )
                acc_upd["mcw"] = mcw_i
            if cfg.gater_enabled:
                with stages.part("gater"):
                    acc_upd["dup"] = info.trans & pre_have[:, None, :]
                    ign_w_i = (
                        plan.ignored_words[i] if plan is not None
                        else bitset.pack(msgs.ignored)
                    )
                    acc_upd["rejw"] = (
                        info.trans & ~(valid_w_i | ign_w_i)[None, None, :]
                    )
                    acc_upd["ignw"] = info.trans & ign_w_i[None, None, :]
                    n_validated_acc = n_validated_acc + bitset.popcount(
                        accepted_new, axis=-1
                    )
                    if n_thr is not None:
                        n_throttled_acc = n_throttled_acc + n_thr
            accs = accs.or_(acc_upd)
            if cfg.count_events:
                for k in cnt:
                    cnt[k] = cnt[k] + getattr(info, k)

            # mcache insertion: validated receipts in joined topics
            put = info.new_words & valid_w_i[None, :] & joined_w
            if not cfg.wire_coalesced:
                mcache = mcache.at[:, 0, :].set(mcache[:, 0, :] | put)

            # publishes for this sub-round + recycled-slot cleanup (the
            # scatter form wins in the phase sub-round from
            # state.SCATTER_FORM_MIN_PEERS on — allocate_publishes'
            # docstring has the measurements)
            if plan is not None:
                # the table half already lives in the head snapshots
                # (msgs_at(i+1) is read at the next iteration's top); only
                # the delivery-state folds run here, fed by the
                # precomputed masks
                _slots, is_pub = plan.sidx[i], plan.is_pub[i]
                keep_w, pub_words = plan.keep_w[i], plan.pub_words[i]
                dlv = plan.apply_to_delivery(
                    dlv, i, tick_i, scatter_form=scatter_form
                )
                origin_w = (origin_w & keep_w[None, :]) | pub_words
            else:
                msgs, dlv, _slots, is_pub, keep_w, pub_words = \
                    allocate_publishes(
                        msgs, dlv, tick_i, pub_origin[i], pub_topic[i],
                        pub_valid[i], scatter_form=scatter_form,
                        pub_holder=(None if pub_holder is None
                                    else pub_holder[i]),
                    )
            # incremental membership-plane maintenance (narrow universes):
            # recycled columns clear, then each publish ORs its one-hot
            # word column where the peer/slot matches the new topic
            p_dim = pub_origin.shape[-1]
            if incr_members and cfg.wire_coalesced:
                # batched form of the per-publish loop below: the P one-hot
                # word columns are built at once and OR-reduced into the
                # planes — ~4 wide kernels instead of ~4 small ones per
                # publish slot (OR is associative: identical bits land)
                slotw, joined_w, mcache = bitset.masked_keep(
                    [slotw, joined_w, mcache], keep_w
                )
                t_p = jnp.clip(pub_topic[i], 0)  # [P]
                warange = jnp.arange(w, dtype=jnp.int32)
                colw = jnp.where(
                    (warange[None, :] == _slots[:, None] // bitset.WORD)
                    & is_pub[:, None],
                    jnp.uint32(1)
                    << (_slots[:, None] % bitset.WORD).astype(jnp.uint32),
                    jnp.uint32(0),
                )  # [P, W] one-hot word columns
                # subscribed[:, t_p] without the [N]-row gather: a compare
                # +any over the narrow (T <= 8) topic axis fuses to vector
                # work (same finding as slot_topic_words)
                t_onehot = (
                    jnp.arange(net.n_topics, dtype=jnp.int32)[None, :, None]
                    == t_p[None, None, :]
                )  # [1, T, P]
                sub_p = jnp.any(
                    net_l.subscribed[:, :, None] & t_onehot, axis=1
                )  # [N, P]
                joined_w = joined_w | bitset.word_or_reduce(
                    jnp.where(sub_p[:, :, None], colw[None], jnp.uint32(0)),
                    axis=1,
                )
                slot_match = (
                    net_l.my_topics[:, :, None] == t_p[None, None, :]
                )  # [N, S, P]
                slotw = slotw | bitset.word_or_reduce(
                    jnp.where(slot_match[..., None], colw[None, None],
                              jnp.uint32(0)),
                    axis=2,
                )
            elif incr_members:
                slotw = slotw & keep_w[None, None, :]
                joined_w = joined_w & keep_w[None, :]
                warange = jnp.arange(w, dtype=jnp.int32)
                for j in range(p_dim):
                    s_j = _slots[j]
                    t_j = jnp.clip(pub_topic[i, j], 0)
                    live_j = is_pub[j]
                    colw = jnp.where(
                        (warange == s_j // bitset.WORD) & live_j,
                        jnp.uint32(1)
                        << (s_j % bitset.WORD).astype(jnp.uint32),
                        jnp.uint32(0),
                    )  # [W] one-hot word column for slot s_j
                    joined_w = joined_w | jnp.where(
                        net_l.subscribed[:, t_j][:, None], colw[None, :],
                        jnp.uint32(0),
                    )
                    slotw = slotw | jnp.where(
                        (net_l.my_topics == t_j)[:, :, None],
                        colw[None, None, :], jnp.uint32(0),
                    )
            if cfg.wire_coalesced:
                if not incr_members:
                    mcache = mcache & keep_w[None, None, :]
                # one window-0 update for this sub-round's put AND the
                # publish stamps: ((m|put)&keep)|pub == (m&keep)|(put&keep)
                # |pub — the mcache clear already ran (masked_keep above /
                # the & keep_w line), so fold put through keep_w here
                mcache = mcache.at[:, 0, :].set(
                    mcache[:, 0, :] | (put & keep_w[None, :]) | pub_words
                )
            else:
                mcache = mcache & keep_w[None, None, :]
                mcache = mcache.at[:, 0, :].set(mcache[:, 0, :] | pub_words)
            # iwant_out / served / promise recycled-slot clears DEFER to
            # the phase tail (keep_acc): nothing inside the loop reads or
            # writes them (asks and service budgets are written at the
            # control head only, promises created at the head only), and
            # a recycled slot is never re-allocated within the same phase
            # (the admission cap bounds publishes at msg_slots // 2), so
            # one tail application of the accumulated mask is exact —
            # saving three [N,K,W] AND passes + a bit_get per sub-round
            # (mcache CANNOT defer: its clear must precede the same
            # sub-round's put of the slot's NEW message)
            keep_acc = keep_acc & keep_w
            # recycled slots drop out of the phase accumulators too — their
            # columns now belong to a different message (the count path
            # needs no clearing: its credits were reduced at arrival time,
            # when the slot still named the right message; the exact-trace
            # dup lane is deliberately NOT cleared — see its comment)
            accs = accs.keep(keep_w)
            if cfg.count_events:
                n_pub = n_pub + jnp.sum(
                    (pub_origin_held[i] >= 0).astype(jnp.int32))

            if cfg.fanout_slots > 0:
                fanout_st, fp_pack = update_fanout_on_publish(
                    cfg, net_l,
                    fanout_st.replace(core=fanout_st.core.replace(tick=tick_i)),
                    pub_origin_held[i], pub_topic[i],
                    jax.random.fold_in(
                        jax.random.fold_in(core.key, tick_i), 0xFA40
                    ),
                    nbr_sub_words_l,
                    fp_pack=fp_pack, thr=thr, msh=msh,
                )

        # ---- phase tail (once) ------------------------------------------
        stage("phase_tail")
        if plan is not None:
            msgs = plan.msgs_at(r)  # the phase-final message table
        # deferred recycled-slot clears (see the loop comment) — one
        # stacked fold over the three [N,K,W] planes on the coalesced path
        if cfg.wire_coalesced:
            iwant_out, served_lo, served_hi = bitset.masked_keep(
                [iwant_out, served_lo, served_hi], keep_acc
            )
        else:
            iwant_out = iwant_out & keep_acc[None, None, :]
            served_lo = served_lo & keep_acc[None, None, :]
            served_hi = served_hi & keep_acc[None, None, :]
        promise_reused = bitset.bit_get(
            (~keep_acc)[None, None, :], promise_mid
        )
        promise_mid = jnp.where(
            (promise_mid >= 0) & promise_reused, -1, promise_mid
        )
        tick_last = tick0 + (r - 1)
        score = st2.score
        if count_score:
            score = apply_delivery_counts(
                score, tp_r, fmd_counts, mmd_counts, imd_counts, mesh2
            )
        elif plane_score:
            score = on_deliveries(
                score, net_l, mesh2, tp_r,
                accs.get("trans", zkw), accs.get("new"),
                dlv.fe_words, dlv.first_round,
                msgs.topic, msgs.valid, tick_last, wrt,
                msg_ignored=msgs.ignored,
                slotw=slot_topic_words(net_l, msgs.topic),
                recv_new_words=accs.get("recv"),
                mesh_credit_words=accs.get("mcw", zkw),
            )
        gater_state = st2.gater
        if cfg.gater_enabled:
            with stages.part("gater"):
                valid_w_end = bitset.pack(msgs.valid)
                first_arrival = (
                    dlv.fe_words & accs.get("accepted")[:, None, :]
                    & valid_w_end[None, None, :]
                )
                deliver_inc = bitset.popcount(
                    first_arrival, axis=-1).astype(jnp.float32)
                gater_state = gater_on_round(
                    gater_state, n_validated_acc, n_throttled_acc,
                    deliver_inc,
                    bitset.popcount(
                        accs.get("dup"), axis=-1).astype(jnp.float32),
                    bitset.popcount(
                        accs.get("rejw"), axis=-1).astype(jnp.float32),
                    tick_last,
                    ignore_inc=bitset.popcount(
                        accs.get("ignw"), axis=-1
                    ).astype(jnp.float32),
                )
        if cfg.count_events:
            # accumulate_round_events consumes only the scalar counters;
            # the plane fields are placeholders (DCE'd when unaccumulated)
            info_sum = RoundInfo(
                trans=zkw, new_words=zw,
                new_bits=bitset.unpack(zw, m), recv_new_words=zw,
                **cnt,
            )
            events = accumulate_round_events(events, info_sum, n_pub)
            if chaos is not None:
                events = events.at[EV.LINK_DOWN].add(n_link_down)
                if n_iwant_rec is not None:
                    events = events.at[EV.IWANT_RECOVER].add(n_iwant_rec)
            if n_adv_drop is not None:
                events = events.at[EV.ADV_DROP].add(n_adv_drop)

        core_next = core.replace(msgs=msgs, dlv=dlv, events=events,
                                 tick=tick_last)
        if chaos is not None and chaos.needs_state:
            core_next = core_next.replace(
                chaos=core.chaos.replace(ge_bad=ge_bad)
            )
        st2 = st2.replace(
            core=core_next,
            mcache=mcache,
            ihave_out=jnp.zeros_like(st2.ihave_out),
            iwant_out=iwant_out,
            served_lo=served_lo,
            served_hi=served_hi,
            promise_mid=promise_mid,
            graft_out=jnp.zeros_like(st2.graft_out),
            prune_out=prune_resp,
            prune_px_out=px_resp,
            edge_live=edge_live_next,
            score=score,
            gater=gater_state,
            fanout_topic=fanout_st.fanout_topic,
            fanout_peers=(
                unpack_fanout_peers(fp_pack, k_dim)
                if fp_pack is not None else fanout_st.fanout_peers
            ),
            fanout_lastpub=fanout_st.fanout_lastpub,
            dup_trans=accs.get("dupt"),
        )

        # congested links suppress this heartbeat's gossip toward them
        # (queue_cap backpressure; last sub-round's saturation, like the
        # per-round step's)
        if cfg.queue_cap > 0:
            sat_recv = bitset.popcount(info.trans, axis=-1) >= cfg.queue_cap
            gossip_suppress = net_l.edge_gather(sat_recv) & net_l.nbr_ok
            st2 = st2.replace(congested_in=sat_recv)
        else:
            gossip_suppress = None

        if do_heartbeat:
            st2 = heartbeat(
                cfg, net_l, st2, tp_r, sp_r, nbr_sub_l,
                gater_params, nbr_sub_words_l, present_ok=net.nbr_ok,
                gossip_suppress=gossip_suppress, app_gathered=app_g,
                adversary=adv, thr=thr, msh=msh,
            )

        # telemetry row — one per phase, recorded LAST (after the
        # heartbeat's GRAFT/PRUNE accounting), at phase-tail state
        if telemetry is not None:
            from ..telemetry import panel as _tele

            core_f = st2.core
            telem = _tele.record_step(
                telemetry, core_f.telem, tick0, ev_prev, core_f.events,
                net_l, core_f.msgs, core_f.dlv, rounds_per_row=r,
                mesh=st2.mesh, my_topics=net_l.my_topics,
                scores=st2.scores,
                backoff_active=(st2.backoff_present
                                & (st2.backoff_expire > tick_last)),
            )
            st2 = st2.replace(core=core_f.replace(telem=telem))
        return st2.replace(core=st2.core.replace(tick=tick0 + r))

    if net.edge_layout == "csr":
        # CSR-resident state tier (round 18): flat planes in the carry,
        # dense views inside the phase — same wrap as the per-round step
        _phase = wrap_csr_resident(net, _phase)

    if lift_scores:
        # lifted call convention (same as the per-round builder): the
        # TRACED score plane is the LAST positional, after up_next /
        # link_deny — ensemble.lift_step vmaps it like any per-sim
        # input (the configs×sims sweep axis)
        def step(st, pub_origin, pub_topic, pub_valid, *rest,
                 do_heartbeat):
            up = rest[0] if dynamic_peers else None
            deny = rest[int(dynamic_peers)] if chaos_sched else None
            return _phase(st, pub_origin, pub_topic, pub_valid, up,
                          do_heartbeat, deny, score_plane=rest[-1])
        return jax.jit(step, donate_argnums=0,
                       static_argnames=("do_heartbeat",))

    # scheduled-chaos builds take the Scenario's forced-down link mask as
    # a REQUIRED trailing positional — ONE [N, K] plane per phase (like
    # the churn plane's one liveness row: partitions land at phase heads)
    if dynamic_peers and chaos_sched:
        def step(st, pub_origin, pub_topic, pub_valid, up_next, link_deny,
                 *, do_heartbeat):
            return _phase(st, pub_origin, pub_topic, pub_valid, up_next,
                          do_heartbeat, link_deny)
    elif dynamic_peers:
        def step(st, pub_origin, pub_topic, pub_valid, up_next, *, do_heartbeat):
            return _phase(st, pub_origin, pub_topic, pub_valid, up_next,
                          do_heartbeat)
    elif chaos_sched:
        def step(st, pub_origin, pub_topic, pub_valid, link_deny,
                 *, do_heartbeat):
            return _phase(st, pub_origin, pub_topic, pub_valid, None,
                          do_heartbeat, link_deny)
    else:
        def step(st, pub_origin, pub_topic, pub_valid, *, do_heartbeat):
            return _phase(st, pub_origin, pub_topic, pub_valid, None,
                          do_heartbeat)
    return jax.jit(step, donate_argnums=0, static_argnames=("do_heartbeat",))
