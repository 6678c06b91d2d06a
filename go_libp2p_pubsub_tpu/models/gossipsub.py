"""GossipSub v1.0/v1.1 router, vectorized (gossipsub.go, 1909 LoC in the
reference) — the centerpiece of the framework (BASELINE.json north_star).

The per-node state machine — mesh maintenance, heartbeat, IHAVE/IWANT lazy
gossip, GRAFT/PRUNE control with backoff, scoring, graylisting — runs for
all N virtual peers at once as masked array ops over the padded neighbor
axis; peer selection is the rank/top-k primitive (ops/select.py).

Round model (survey §7): one jitted `step()` = one network-hop round; the
heartbeat runs every `heartbeat_every` rounds inside the same jit. Control
written to per-edge outboxes in round r is read by the far end in round
r+1 via the reverse-edge gather — the one-RTT control latency of the
reference's wire layer.

Approximations vs the reference (all distributional, per the north star's
CDF comparison):
  * control responses are delayed one round (reference replies in the same
    RPC turn)
  * per-heartbeat GRAFT processing is batched, so Dhi admission checks use
    mesh sizes from the round start
  * one outstanding IWANT promise slot per edge (reference keeps one per
    IWANT batch; AddPromise gossip_tracer.go:48-75). Measured at
    adversarial advertise-never-serve rates (tests/
    test_promise_sensitivity.py): the per-batch model accrues ~2.3x the
    P7 of the per-edge model, but both drive attacker edges under the
    gossip threshold and leave honest edges clean — the protective
    outcome is granularity-insensitive
  * IHAVE truncation to MaxIHaveLength keeps lowest slots (reference
    shuffles; gossipsub.go:655-667). With the cap forced to bind hard
    (budget 4 vs 64-slot windows) the two policies' propagation CDFs
    differ by 0.3% sup — far inside the parity envelope
  * over-subscription outbound bubble-up displaces random-keep members only
    (the reference's rotation can displace score-keep members in corner
    cases, gossipsub.go:1409-1441)
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..chaos import adversary as adversary_mod
from ..chaos import faults as chaos_faults
from ..chaos.faults import ChaosConfig
from ..config import (
    GossipSubParams,
    PeerScoreParams,
    PeerScoreThresholds,
    ticks_for,
)
from ..ops import bitset, edges
from ..ops.select import (
    count_true,
    masked_width_random,
    masked_width_topk,
    median_masked,
    select_random_mask,
    select_topk_mask,
)
from ..perf import spans, stages
from ..routers import (
    RouterConfig,
    choke_decide,
    choke_guard,
    choke_lateness_update,
    choke_suppression,
    dontwant_announcements,
    dontwant_suppression,
    idontwant_sent_count,
    ring_commit,
    ring_keep,
)
from ..score.engine import (
    ScoreState,
    TopicParamsArrays,
    add_penalties,
    clear_edges,
    clear_mesh_status,
    compute_scores,
    ip_colocation_surplus_sq,
    on_deliveries,
    on_graft,
    on_prune,
    refresh_scores,
    slot_topic_words,
)
from ..score.gater import GaterState, gater_accept, gater_decay, gater_on_round
from ..state import (
    Net,
    SimState,
    TopoState,
    allocate_publishes,
    wrap_csr_resident,
)
from ..trace.events import EV
from .common import (
    RoundInfo,
    accumulate_round_events,
    delivery_round,
    origin_msg_words,
    subscribed_msg_words,
)


# ---------------------------------------------------------------------------
# configuration


@dataclasses.dataclass(frozen=True)
class GossipSubConfig:
    """Static (jit-constant) configuration: GossipSubParams with durations
    in ticks, plus the v1.1 thresholds and feature switches."""

    D: int = 6
    Dlo: int = 5
    Dhi: int = 12
    Dscore: int = 4
    Dout: int = 2
    Dlazy: int = 6
    gossip_factor: float = 0.25
    history_length: int = 5
    history_gossip: int = 3
    gossip_retransmission: int = 3
    max_ihave_messages: int = 10
    max_ihave_length: int = 5000
    iwant_followup_ticks: int = 3
    prune_backoff_ticks: int = 60
    graft_flood_ticks: int = 10
    opportunistic_graft_ticks: int = 60
    opportunistic_graft_peers: int = 2
    backoff_clear_ticks: int = 15   # gossipsub.go:1587
    backoff_slack_ticks: int = 2    # gossipsub.go:1596
    direct_connect_ticks: int = 300  # gossipsub.go:1606-1628
    heartbeat_every: int = 1        # rounds per heartbeat tick
    # v1.1 switches
    score_enabled: bool = False
    flood_publish: bool = False
    do_px: bool = False
    # edge-liveness gating without PX: dormant provisioned edges carry
    # nothing until activated (state.edge_live). PX implies it; a build
    # with pre-provisioned dormant pairs (api.Network.connect(dormant=
    # True) — the runtime-connect pool, notify.go:19-75 Connected) sets
    # it so post-start connects flip edges live with no recompile
    edge_liveness: bool = False
    # outbound-queue backpressure: per-link message budget per round; the
    # overflow is genuinely lost and traced DROP_RPC (the reference's
    # 32-deep per-peer writer queue, pubsub.go:240 + comm.go:139-170).
    # 0 = lossless (unmodeled), the default
    queue_cap: int = 0
    # peer gater + validation pipeline model (validation.go front-end queue;
    # 0 capacity = unbounded, gater inert without throttle pressure)
    gater_enabled: bool = False
    # the gater's quiet period in HEARTBEATS (ticks_for(quiet, heartbeat_
    # interval)); ``last_throttle`` and ``tick`` count delivery ROUNDS, so
    # the gate compares against ``gater_quiet_rounds``
    gater_quiet_ticks: int = 60
    validation_capacity: int = 0  # accepted validations per peer per round
    # async validation latency in rounds (survey §7 hard-part (c)): receipts
    # spend this many rounds in the pipeline between arrival (markSeen) and
    # their verdict (forward + Deliver/Reject + CDF timestamp). 0 = inline.
    validation_delay_rounds: int = 0
    # per-topic validation latency (the reference's per-topic async
    # validators complete at different times — NumCPU workers + per-topic
    # throttles, validation.go:123-135,391-438): a static tuple of T
    # per-topic delays, each in [1, validation_delay_rounds]; a message's
    # verdict lands delay[topic] rounds after arrival, so verdicts of
    # different topics interleave out of arrival order. None = uniform
    # validation_delay_rounds for every topic.
    validation_delay_topic: tuple | None = None
    # WithValidatorTimeout analogue (validation.go:522-529): an async
    # validator whose verdict would land more than this many rounds after
    # arrival times out, and the message is IGNORED (dropped without the
    # P4 sender penalty — the reference's expired validation context).
    # Composes with the per-topic delays above: a topic whose effective
    # delay exceeds the timeout never produces an Accept. 0 = no timeout.
    validator_timeout_rounds: int = 0
    # fanout (publishing to unjoined topics, gossipsub.go:981-1002,1517-1554)
    fanout_slots: int = 2         # concurrent unjoined publish topics/peer
    # FanoutTTL in HEARTBEATS (ticks_for(fanout_ttl, heartbeat_interval));
    # ``lastpub`` and ``tick`` count delivery ROUNDS, so expiry compares
    # against ``fanout_ttl_rounds``
    fanout_ttl_ticks: int = 60
    # aggregate trace counters (EventTracer accounting). Tracing is opt-in
    # in the reference (WithEventTracer); False skips the event popcount
    # reductions — per-message delivery state stays exact
    count_events: bool = True
    # coalesced stacked wire exchange (phase engine only): the whole
    # control head — control outboxes, score plane, IWANT-service window,
    # P5 app plane — crosses the edge involution in ONE gather (one halo
    # permute set per phase on the sharded mesh), and the phase's
    # attribution accumulators fold as leading-axis-stacked tensors.
    # False selects the legacy per-plane path (round-3..6 structure) for
    # A/B; the bench fingerprint records the choice
    # (engine.wire_coalesced) and the measured permute_sets_per_phase.
    # Bit-identical either way (tests/test_phase_stacked.py).
    wire_coalesced: bool = True
    # sparse data plane (round 15, ops/csr.py + docs/DESIGN.md §15): the
    # edge-exchange layout — "dense" (the padded [N, K] involution, the
    # default: traces the pre-CSR program bit for bit) or "csr" (the
    # capacity-bounded flat [E] edge space; cross-peer movement is
    # E-sized, the sparse-topology regime's shape). A frozen static: one
    # build traces exactly ONE layout, zero runtime branching; the Net
    # must be built with the same value (prepare_step_consts enforces).
    edge_layout: str = "dense"
    # fused composite kernels (round 21, docs/DESIGN.md §21): statically
    # select the bandwidth-lean forms on the hot path — the sort-form
    # selection (ops/select fused=True: O(K) bytes/row instead of the
    # pairwise form's O(K^2) compare planes) in the heartbeat, fanout
    # and gossip-target blocks, and the capacity-bounded segmented OR in
    # the CSR delivery commit (via the matching Net.build(fused=True)).
    # A frozen static like edge_layout: False traces the pre-fusion
    # program bit for bit (the census gate's contract); True is
    # bit-exact in VALUES (tests/test_fused_composites.py, all four engines)
    # and is what `make cost-audit`'s fusion contract prices.
    fused: bool = False
    # int-packed control counters (round 15 narrowing contract, docs/
    # DESIGN.md §15): store the per-edge IHAVE flood-protection counters
    # (peerhave/iasked) as int16 instead of int32. EXACT by range
    # analysis — both are cleared every heartbeat; iasked saturates at
    # the max_ihave_length cap it gates on, and peerhave grows at most
    # one batch per round so the heartbeat cadence bounds it — and
    # build() refuses configs whose bound (max_ihave_length or
    # heartbeat_every) falls outside int16, so the narrowed build is
    # bit-identical in VALUES (tests/test_csr.py). Off by default (the
    # committed STATE_SCHEMA pins the wide dtypes).
    narrow_counters: bool = False
    # chaos plane (chaos/faults.py): link-fault injection — i.i.d. or
    # Gilbert–Elliott flap generators drawn from the sim PRNG stream,
    # plus (scheduled=True) a per-round link_deny argument fed by the
    # Scenario partition compiler. None (or an all-zero config) elides
    # the plane STATICALLY: the traced program is identical to a build
    # without it (bit-exactness + the PERF_SMOKE kernel census pinned
    # by tests/test_chaos.py and `make chaos-smoke`)
    chaos: "ChaosConfig | None" = None
    # exact per-event tracing support (trace.go:166-194, 341-414): the
    # step additionally records this round's duplicate-arrival plane
    # ([N,K,W] — arrivals beyond the first per (peer,msg)) in
    # state.dup_trans so the drain can expand every DuplicateMessage and
    # control-only RPC into an individual TraceEvent (drain.TraceSession
    # exact mode) instead of aggregate counters. Off by default: costs one
    # [N,K,W] store per round when on, zero when off
    trace_exact: bool = False
    # router plane (routers/, docs/DESIGN.md §24): the post-v1.1
    # protocol frontier — v1.2 IDONTWANT duplicate suppression, the
    # episub-style lazy-choke router, and the per-edge latency ring
    # that consumes topo.link_class_planes. None (the one spelling of
    # v1.1 semantics) elides the plane STATICALLY: the traced program,
    # kernel census and state tree are the pre-router ones, bit for bit
    # (`make choke-smoke`'s router-off census gate).
    router: "RouterConfig | None" = None
    # thresholds (v1.1; zeros for v1.0)
    gossip_threshold: float = 0.0
    publish_threshold: float = 0.0
    graylist_threshold: float = 0.0
    accept_px_threshold: float = 0.0
    opportunistic_graft_threshold: float = 0.0

    @classmethod
    def build(
        cls,
        params: GossipSubParams | None = None,
        thresholds: PeerScoreThresholds | None = None,
        score_enabled: bool = False,
        heartbeat_every: int = 1,
        gater_params: "PeerGaterParams | None" = None,
        validation_capacity: int = 0,
        validation_delay_rounds: int = 0,
        validation_delay_topic: tuple | None = None,
        validator_timeout_rounds: int = 0,
        queue_cap: int = 0,
        trace_exact: bool = False,
        wire_coalesced: bool = True,
        chaos: "ChaosConfig | None" = None,
        edge_layout: str = "dense",
        narrow_counters: bool = False,
        fused: bool = False,
        router: "RouterConfig | None" = None,
    ) -> "GossipSubConfig":
        p = params or GossipSubParams()
        p.validate()
        if router is not None:
            router.validate()
        if edge_layout not in ("dense", "csr"):
            raise ValueError(
                f"edge_layout must be 'dense' or 'csr', got {edge_layout!r}"
            )
        # derived from the counter dtype, not hard-coded — the range
        # auditor (analysis/ranges.py, contract narrow-nonwrap) proves
        # the int16 sites non-wrapping under exactly these caps
        i16_cap = int(np.iinfo(np.int16).max) + 1
        if narrow_counters and p.max_ihave_length >= i16_cap:
            # the iasked counter saturates at the cap it gates on; a cap
            # outside int16 range would overflow before the gate fires
            raise ValueError(
                f"narrow_counters needs max_ihave_length < {i16_cap} "
                f"(got {p.max_ihave_length}) — the int16 iasked counter "
                "must be able to represent its own cap"
            )
        if narrow_counters and heartbeat_every >= i16_cap:
            # peerhave's true bound is the heartbeat clear cadence, not
            # max_ihave_messages: it counts one IHAVE batch per round
            # (handle_ihave) and only clearIHaveCounters resets it, so
            # an edge advertising every round reaches heartbeat_every
            # before the clear
            raise ValueError(
                f"narrow_counters needs heartbeat_every < {i16_cap} "
                f"(got {heartbeat_every}) — the int16 peerhave counter "
                "grows once per round until the heartbeat clear"
            )
        if validator_timeout_rounds < 0:
            raise ValueError(
                f"validator_timeout_rounds must be >= 0, got {validator_timeout_rounds}"
            )
        if validation_delay_topic is not None:
            validation_delay_topic = tuple(int(d) for d in validation_delay_topic)
            if validation_delay_rounds <= 0:
                validation_delay_rounds = max(validation_delay_topic)
            if not all(
                1 <= d <= validation_delay_rounds for d in validation_delay_topic
            ):
                raise ValueError(
                    "validation_delay_topic entries must lie in "
                    f"[1, {validation_delay_rounds}] (the pipeline depth); "
                    f"got {validation_delay_topic}"
                )
        hb = p.heartbeat_interval
        kw = dict(
            D=p.D, Dlo=p.Dlo, Dhi=p.Dhi, Dscore=p.Dscore, Dout=p.Dout,
            Dlazy=p.Dlazy, gossip_factor=p.gossip_factor,
            history_length=p.history_length, history_gossip=p.history_gossip,
            gossip_retransmission=p.gossip_retransmission,
            max_ihave_messages=p.max_ihave_messages,
            max_ihave_length=p.max_ihave_length,
            iwant_followup_ticks=ticks_for(p.iwant_followup_time, hb),
            prune_backoff_ticks=ticks_for(p.prune_backoff, hb),
            graft_flood_ticks=ticks_for(p.graft_flood_threshold, hb),
            opportunistic_graft_ticks=p.opportunistic_graft_ticks,
            opportunistic_graft_peers=p.opportunistic_graft_peers,
            direct_connect_ticks=p.direct_connect_ticks,
            heartbeat_every=heartbeat_every,
            score_enabled=score_enabled,
            flood_publish=p.flood_publish,
            do_px=p.do_px,
            gater_enabled=gater_params is not None,
            gater_quiet_ticks=ticks_for(gater_params.quiet, hb) if gater_params else 60,
            validation_capacity=validation_capacity,
            validation_delay_rounds=validation_delay_rounds,
            validation_delay_topic=validation_delay_topic,
            validator_timeout_rounds=validator_timeout_rounds,
            queue_cap=queue_cap,
            trace_exact=trace_exact,
            wire_coalesced=wire_coalesced,
            chaos=chaos,
            edge_layout=edge_layout,
            narrow_counters=narrow_counters,
            fused=fused,
            router=router,
            fanout_ttl_ticks=ticks_for(p.fanout_ttl, hb),
        )
        if chaos is not None:
            chaos.validate()
        if thresholds is not None:
            thresholds.validate()
            kw.update(
                gossip_threshold=thresholds.gossip_threshold,
                publish_threshold=thresholds.publish_threshold,
                graylist_threshold=thresholds.graylist_threshold,
                accept_px_threshold=thresholds.accept_px_threshold,
                opportunistic_graft_threshold=thresholds.opportunistic_graft_threshold,
            )
        return cls(**kw)

    @property
    def fanout_ttl_rounds(self) -> int:
        """FanoutTTL on the clock ``tick`` counts in: delivery rounds
        (``fanout_ttl_ticks`` heartbeats of ``heartbeat_every`` rounds)."""
        return self.fanout_ttl_ticks * self.heartbeat_every

    @property
    def gater_quiet_rounds(self) -> int:
        """The gater's quiet period on the clock ``tick`` counts in."""
        return self.gater_quiet_ticks * self.heartbeat_every

    def validation_timed_out(self, topic: int) -> bool:
        """True when this topic's async verdict can never land inside the
        validator timeout (effective delay > validator_timeout_rounds):
        its messages resolve to ValidationIgnore, the reference's
        expired-context outcome (validation.go:522-529)."""
        if self.validator_timeout_rounds <= 0:
            return False
        if self.validation_delay_topic is not None:
            delay = self.validation_delay_topic[topic]
        else:
            delay = self.validation_delay_rounds
        return delay > self.validator_timeout_rounds


# ---------------------------------------------------------------------------
# state


@struct.dataclass
class GossipSubState:
    core: SimState
    # mesh overlay (gossipsub.go:441 mesh map)
    mesh: jax.Array             # [N,S,K] bool
    # prune backoff (gossipsub.go:449): expiry tick + presence (presence
    # outlives expiry until the 15-tick clear — gossipsub.go:1585-1604; the
    # heartbeat candidate filter tests presence, graft admission tests expiry)
    backoff_expire: jax.Array   # [N,S,K] i32
    backoff_present: jax.Array  # [N,S,K] bool
    # message cache ring (mcache.go): window 0 = current heartbeat
    mcache: jax.Array           # [N,H,W] u32
    # control outboxes, read by the far end next round
    ihave_out: jax.Array        # [N,K,W] u32
    iwant_out: jax.Array        # [N,K,W] u32
    graft_out: jax.Array        # [N,S,K] bool
    prune_out: jax.Array        # [N,S,K] bool
    # IHAVE flood protection (cleared each heartbeat, gossipsub.go:1566-1576)
    peerhave: jax.Array         # [N,K] i32
    iasked: jax.Array           # [N,K] i32
    # IWANT retransmission 2-bit saturating counters (mcache.peertx,
    # mcache.go:66-80, tracked at the requesting end of the edge)
    served_lo: jax.Array        # [N,K,W] u32
    served_hi: jax.Array        # [N,K,W] u32
    # gossip promises (gossip_tracer.go): one slot per edge
    promise_mid: jax.Array      # [N,K] i32 (-1 none)
    promise_expire: jax.Array   # [N,K] i32
    # v1.1 score plane
    score: ScoreState
    scores: jax.Array           # [N,K] f32 (memoized per heartbeat,
                                # gossipsub.go:1333-1341)
    p6: jax.Array               # [N,K] f32 colocation surplus^2 (static topo)
    app_score: jax.Array        # [N] f32 (P5)
    # peer gater (peer_gater.go)
    gater: GaterState
    # fanout: per-peer slots for topics published to without joining
    # (gossipsub.go:444-447 fanout + lastpub maps)
    fanout_topic: jax.Array    # [N,F] i32, -1 free
    fanout_peers: jax.Array    # [N,F,K] bool
    fanout_lastpub: jax.Array  # [N,F] i32
    # peer lifecycle (dynamic_peers builds): effective liveness + blacklist.
    # up models the notify/dead-peer plane (notify.go:19-75, handleDeadPeers
    # pubsub.go:648-689); blacklist is the global-view blacklist
    # (blacklist.go:12-64, enforced at pubsub.go:1048-1060,636-639) — a
    # blacklisted peer is disconnected everywhere next round
    up: jax.Array              # [N] bool
    blacklist: jax.Array       # [N] bool
    # PX connection plane (do_px only): which provisioned edges are live.
    # Dormant edges (graph.dormant_edges) start False; a PRUNE carrying PX
    # (makePrune gossipsub.go:1814-1850) lets the pruned peer activate
    # dormant edges to suggested peers (pxConnect :861-941). Kept symmetric
    # over the edge involution.
    edge_live: jax.Array       # [N,K] bool
    # PX flag riding this round's PRUNEs (parallel outbox to prune_out)
    prune_px_out: jax.Array    # [N,S,K] bool
    # inbound-link saturation observed last round (queue_cap only; zeros
    # otherwise): congested_in[i,k] = the sender nbr[i,k]'s outbound queue
    # toward i was full. The host's announce-retry model reads it — a
    # SubOpts announcement riding a full queue is dropped and retried
    # with jitter (pubsub.go:861-901)
    congested_in: jax.Array    # [N,K] bool
    # exact-trace duplicate plane (cfg.trace_exact only, else None):
    # this round's arrivals beyond the first per (peer, msg), per edge —
    # the drain expands them to DuplicateMessage events (trace.go:186-194)
    dup_trans: jax.Array | None = None  # [N,K,W] u32
    # router plane (cfg.router, routers/, docs/DESIGN.md §24) — every
    # leaf None on v1.1 builds (the elision contract: the state TREE is
    # the pre-router one, which is what the smoke's bit-exact census
    # compares). dontwant ⊆ dlv.have by construction (fed from the
    # round's post-throttle new receipts); choked ⊆ mesh with at least
    # Dlo unchoked per slot (choke_guard, re-applied at every mesh
    # mutation site); inflight is the delayed-commit ring — edge axes
    # leading so it rides the CSR-resident tier flat as [E, L, W]
    dontwant: jax.Array | None = None    # [N,W] u32 announced ids
    choked: jax.Array | None = None      # [N,S,K] bool lazy-demoted mesh links
    choke_ema: jax.Array | None = None   # [N,K] f32 lateness EMA
    inflight: jax.Array | None = None    # [N,K,L,W] u32 ([E,L,W] flat)

    @classmethod
    @spans.span("setup.state_init")
    def init(
        cls,
        net: Net,
        msg_slots: int,
        cfg: GossipSubConfig,
        score_params: PeerScoreParams | None = None,
        seed: int = 0,
        app_score: np.ndarray | None = None,
        dormant: np.ndarray | None = None,
        wire_block: bool = False,
        telemetry=None,
        dynamic_topo: bool = False,
    ) -> "GossipSubState":
        n, k = net.nbr.shape
        s = net.n_slots
        w = bitset.n_words(msg_slots)
        h = cfg.history_length
        if score_params is not None and cfg.score_enabled:
            p6 = ip_colocation_surplus_sq(
                net,
                score_params.ip_colocation_factor_threshold,
                score_params.ip_colocation_factor_whitelist,
            )
        else:
            p6 = jnp.zeros((n, k), jnp.float32)
        # CSR-resident tier (round 18): against an edge_layout="csr" Net
        # the per-edge planes allocate FLAT — fe_words/served_* as
        # [E, W], peerhave/iasked as [E] — dead padded slots are not
        # resident (MEM_AUDIT.json's csr rows; the steps densify them
        # transiently, state.wrap_csr_resident)
        e = net.n_edges  # None on dense builds
        ph_shape = (n, k) if e is None else (e,)
        sv_shape = (n, k, w) if e is None else (e, w)
        return cls(
            core=SimState.init(n, msg_slots, seed, k=k,
                               val_delay=cfg.validation_delay_rounds,
                               wire_block=wire_block,
                               chaos_ge=(cfg.chaos is not None
                                         and cfg.chaos.needs_state),
                               telemetry=telemetry,
                               n_edges=e,
                               # the state-resident mutable overlay
                               # (dynamic_topo builds): seeded from the
                               # build topology, mutated in place by the
                               # step's write batches
                               topo=(TopoState.from_net(net)
                                     if dynamic_topo else None)),
            mesh=jnp.zeros((n, s, k), bool),
            backoff_expire=jnp.zeros((n, s, k), jnp.int32),
            backoff_present=jnp.zeros((n, s, k), bool),
            mcache=jnp.zeros((n, h, w), jnp.uint32),
            ihave_out=jnp.zeros((n, k, w), jnp.uint32),
            iwant_out=jnp.zeros((n, k, w), jnp.uint32),
            graft_out=jnp.zeros((n, s, k), bool),
            prune_out=jnp.zeros((n, s, k), bool),
            # IHAVE flood-protection counters: int16 under the round-15
            # narrowing contract (cfg.narrow_counters — exact: heartbeat-
            # cleared, cap-bounded; build() refuses caps outside range)
            peerhave=jnp.zeros(
                ph_shape, jnp.int16 if cfg.narrow_counters else jnp.int32),
            iasked=jnp.zeros(
                ph_shape, jnp.int16 if cfg.narrow_counters else jnp.int32),
            served_lo=jnp.zeros(sv_shape, jnp.uint32),
            served_hi=jnp.zeros(sv_shape, jnp.uint32),
            promise_mid=jnp.full((n, k), -1, jnp.int32),
            promise_expire=jnp.zeros((n, k), jnp.int32),
            score=ScoreState.empty(n, s, k),
            scores=jnp.zeros((n, k), jnp.float32),
            p6=p6,
            app_score=jnp.zeros((n,), jnp.float32)
            if app_score is None
            else jnp.asarray(app_score, jnp.float32),
            gater=GaterState.empty(n, k),
            fanout_topic=jnp.full((n, cfg.fanout_slots), -1, jnp.int32),
            fanout_peers=jnp.zeros((n, cfg.fanout_slots, k), bool),
            fanout_lastpub=jnp.zeros((n, cfg.fanout_slots), jnp.int32),
            up=jnp.ones((n,), bool),
            blacklist=jnp.zeros((n,), bool),
            # copy, never alias: the step donates state buffers, and an
            # aliased net.nbr_ok would be deleted with them
            edge_live=net.nbr_ok & ~jnp.asarray(dormant, bool)
            if dormant is not None
            else jnp.copy(net.nbr_ok),
            prune_px_out=jnp.zeros((n, s, k), bool),
            congested_in=jnp.zeros((n, k), bool),
            dup_trans=(
                jnp.zeros((n, k, w), jnp.uint32) if cfg.trace_exact else None
            ),
            dontwant=(
                jnp.zeros((n, w), jnp.uint32)
                if cfg.router is not None and cfg.router.idontwant else None
            ),
            choked=(
                jnp.zeros((n, s, k), bool)
                if cfg.router is not None and cfg.router.choke else None
            ),
            choke_ema=(
                jnp.zeros((n, k), jnp.float32)
                if cfg.router is not None and cfg.router.choke else None
            ),
            inflight=(
                jnp.zeros(
                    (((n, k) if e is None else (e,))
                     + (cfg.router.latency_rounds, w)), jnp.uint32)
                if cfg.router is not None and cfg.router.latency_rounds > 0
                else None
            ),
        )


def msg_slot_of(net: Net, msg_topic: jax.Array) -> jax.Array:
    """[N, M] receiver topic-slot per message (-1 when not subscribed)."""
    t = jnp.clip(msg_topic, 0)
    s = net.slot_of[:, t]
    return jnp.where(msg_topic[None, :] >= 0, s, -1)


def joined_msg_words(net: Net, msgs) -> jax.Array:
    """[N, W]: messages in topics peer n has joined (mesh exists <=>
    subscribed in the sim) — the alias documents that equivalence."""
    return subscribed_msg_words(net, msgs)


# ---------------------------------------------------------------------------
# control-plane handlers (per round)


def handle_graft_prune(cfg: GossipSubConfig, net: Net, st: GossipSubState, tp: dict,
                       acc_ok: jax.Array, graft_in_raw: jax.Array,
                       prune_in_raw: jax.Array, px_in_raw, thr=None,
                       msh=None):
    """Process GRAFT/PRUNE received this round (handleGraft
    gossipsub.go:718-809, handlePrune :811-843). Returns updated state plus
    next round's PRUNE responses. `*_raw` are the pre-gathered edge views
    from the step's merged wire exchange (already nbr_ok-masked).
    ``thr`` is the threshold source — cfg (static floats, the default)
    or the traced ScoreParams plane of a lifted build (round 16).
    ``msh`` is the mesh-degree source — cfg, or the traced MeshParams
    plane of a candidate-lifted build (round 20)."""
    thr = cfg if thr is None else thr
    msh = cfg if msh is None else msh
    tick = st.core.tick

    graft_in = graft_in_raw & acc_ok[:, None, :]
    prune_in = prune_in_raw & acc_ok[:, None, :]

    # PX ingest (handlePrune gossipsub.go:834-841): a PRUNE carrying PX is
    # honored only if the pruner's score clears AcceptPXThreshold
    if cfg.do_px:
        px_in = px_in_raw & prune_in
        px_ok = jnp.any(px_in, axis=1) & (st.scores >= thr.accept_px_threshold)  # [N,K]
    else:
        px_ok = None

    # handlePrune: drop from mesh, obey backoff, sticky P3b
    pruned = prune_in & st.mesh
    score = on_prune(st.score, pruned, tp) if cfg.score_enabled else st.score
    mesh = st.mesh & ~prune_in
    backoff_expire = jnp.where(
        prune_in, jnp.maximum(st.backoff_expire, tick + cfg.prune_backoff_ticks),
        st.backoff_expire,
    )
    backoff_present = st.backoff_present | prune_in

    # handleGraft — a floodsub-only node doesn't speak meshsub and ignores
    # GRAFTs entirely (gossipsub_feat.go)
    want = graft_in & ~mesh & net.nbr_ok[:, None, :] & (net.protocol >= 1)[:, None, None]

    rej_direct = want & net.direct[:, None, :]  # gossipsub.go:742-750

    backoff_active = backoff_present & (tick < backoff_expire)
    rej_backoff = want & backoff_active          # gossipsub.go:753-770
    flood_cutoff = backoff_expire + (cfg.graft_flood_ticks - cfg.prune_backoff_ticks)
    flood = rej_backoff & (tick < flood_cutoff)  # gossipsub.go:760-765
    penalty_counts = jnp.sum(
        rej_backoff.astype(jnp.float32) + flood.astype(jnp.float32), axis=1
    )  # [N,K]

    if cfg.score_enabled:
        rej_score = want & (st.scores[:, None, :] < 0)  # gossipsub.go:772-783
    else:
        rej_score = jnp.zeros_like(want)

    mesh_deg = count_true(mesh)  # [N,S]
    rej_full = (
        want & (mesh_deg[:, :, None] >= msh.Dhi) & ~net.outbound[:, None, :]
    )  # gossipsub.go:785-792

    rejected = rej_direct | rej_backoff | rej_score | rej_full
    accepted = want & ~rejected

    mesh = mesh | accepted
    if cfg.score_enabled:
        score = on_graft(score, accepted, tick)
        score = add_penalties(score, penalty_counts)

    re_back = rej_backoff | rej_score | rej_full  # refresh/add backoff
    backoff_expire = jnp.where(
        re_back, jnp.maximum(backoff_expire, tick + cfg.prune_backoff_ticks), backoff_expire
    )
    backoff_present = backoff_present | re_back

    st = st.replace(
        mesh=mesh,
        backoff_expire=backoff_expire,
        backoff_present=backoff_present,
        score=score,
    )
    # graft-rejection PRUNEs carry PX for decently-scored peers (handleGraft
    # calls makePrune with doPX && score-ok, gossipsub.go:796-806); score-
    # rejections get none
    if cfg.do_px:
        # rejected & ~rej_score already implies score >= 0 (rej_score covers
        # every negative-score rejection)
        px_resp = rejected & ~rej_score
    else:
        px_resp = jnp.zeros_like(rejected)
    if cfg.count_events:
        n_graft = jnp.sum(accepted.astype(jnp.int32))
        n_prune = jnp.sum(pruned.astype(jnp.int32))
    else:
        n_graft = n_prune = jnp.int32(0)
    return st, rejected, px_resp, px_ok, n_graft, n_prune


_prefix_cap_bits = bitset.prefix_cap_bits


def handle_ihave(cfg: GossipSubConfig, net: Net, st: GossipSubState,
                 joined_words: jax.Array, acc_ok: jax.Array,
                 ihave_in_raw: jax.Array, thr=None) -> GossipSubState:
    """IHAVE received this round -> IWANT requests + a promise
    (handleIHave gossipsub.go:615-677). `ihave_in_raw` is the pre-gathered
    edge view from the step's merged wire exchange. ``thr`` is the
    threshold source (cfg, or a lifted build's traced plane)."""
    thr = cfg if thr is None else thr
    m = st.core.msgs.capacity
    tick = st.core.tick
    ihave_in = jnp.where(acc_ok[:, :, None], ihave_in_raw, jnp.uint32(0))

    got = bitset.popcount(ihave_in, axis=-1) > 0  # [N,K] one batch per round
    peerhave = st.peerhave + got.astype(st.peerhave.dtype)

    ok = got
    if cfg.score_enabled:
        ok = ok & (st.scores >= thr.gossip_threshold)  # gossipsub.go:616-621
    ok = ok & (peerhave <= cfg.max_ihave_messages)     # gossipsub.go:624-628
    ok = ok & (st.iasked < cfg.max_ihave_length)       # gossipsub.go:630-633

    wants = ihave_in & ~st.core.dlv.have[:, None, :] & joined_words[:, None, :]
    wants = jnp.where(ok[:, :, None], wants, jnp.uint32(0))

    # the MaxIHaveLength ask budget (gossipsub.go:655-658) can only bind if
    # one heartbeat's asks could exceed it; with msg_slots far below the cap
    # (the iasked >= cap gate above already ran) skip the prefix-cap pass
    if m * (cfg.heartbeat_every + 1) > cfg.max_ihave_length:
        budget = jnp.maximum(cfg.max_ihave_length - st.iasked, 0).astype(
            jnp.int32)  # the prefix-cap cumsum compares in int32
        asks = _prefix_cap_bits(wants, budget, m)
    else:
        asks = wants
    n_asked = bitset.popcount(asks, axis=-1)
    iasked = st.iasked + n_asked.astype(st.iasked.dtype)

    # adopt one promised mid per edge when none is outstanding
    first_ask, _has = bitset.lowest_bit(asks)
    adopt = (n_asked > 0) & (st.promise_mid < 0)
    promise_mid = jnp.where(adopt, first_ask, st.promise_mid)
    promise_expire = jnp.where(adopt, tick + cfg.iwant_followup_ticks, st.promise_expire)

    return st.replace(
        peerhave=peerhave,
        iasked=iasked,
        iwant_out=asks,
        promise_mid=promise_mid,
        promise_expire=promise_expire,
    )


def _served_capped(cfg: GossipSubConfig, lo: jax.Array, hi: jax.Array) -> jax.Array:
    """Word-mask of slots whose 2-bit served count has reached the
    retransmission cap (cap clamps to the counter range 0..3)."""
    cap = min(max(cfg.gossip_retransmission, 0), 3)
    if cap >= 3:
        return hi & lo
    if cap == 2:
        return hi
    if cap == 1:
        return hi | lo
    return jnp.full_like(lo, np.uint32(0xFFFFFFFF))


def iwant_responses(cfg: GossipSubConfig, net: Net, st: GossipSubState,
                    nbr_score_of_me, window_g: jax.Array | None = None,
                    thr=None):
    """The IWANT-response carry for this round's delivery + retransmission
    counter update (handleIWant gossipsub.go:679-716). `st.iwant_out` holds
    what I asked each neighbor last round; the neighbor serves from its full
    mcache history window subject to the per-(edge,msg) cap.
    `nbr_score_of_me` [N,K] comes from the step's merged wire exchange
    (None only when scoring is disabled). ``window_g`` is the neighbors'
    gathered mcache-window plane when the coalesced wire exchange already
    carried it (None: gather here, the legacy extra permute set).
    ``thr`` is the threshold source (cfg, or a lifted plane)."""
    thr = cfg if thr is None else thr
    asked = st.iwant_out
    if window_g is None:
        sender_window = bitset.word_or_reduce(st.mcache, axis=1)   # [N,W]
        window_g = jnp.where(
            net.nbr_ok[:, :, None],
            net.peer_gather(sender_window),                         # [N,K,W]
            jnp.uint32(0),
        )
    capped = _served_capped(cfg, st.served_lo, st.served_hi)
    resp = asked & window_g & ~capped

    if cfg.score_enabled:
        # responder ignores requesters below the gossip threshold
        # (gossipsub.go:681-685): the score the neighbor holds of me
        resp = jnp.where(
            (nbr_score_of_me >= thr.gossip_threshold)[:, :, None], resp, jnp.uint32(0)
        )

    # 2-bit saturating increment on served slots
    sat = st.served_hi & st.served_lo
    inc = resp & ~sat
    carry = st.served_lo & inc
    lo = st.served_lo ^ inc
    hi = st.served_hi | carry
    return st.replace(served_lo=lo, served_hi=hi), resp


# ---------------------------------------------------------------------------
# delivery-edge selection


def sender_carry_words(mesh: jax.Array, slotw: jax.Array) -> jax.Array:
    """[N,K,W] sender-side: words each peer would push on edge k — the OR
    over its topic slots of (slot's topic messages) where the edge is in
    that slot's mesh. Word algebra only."""
    contrib = jnp.where(mesh[:, :, :, None], slotw[:, :, None, :], jnp.uint32(0))
    return bitset.word_or_reduce(contrib, axis=1)  # [N,K,W]


def fanout_topic_words(fanout_topic: jax.Array, msg_topic: jax.Array) -> jax.Array:
    """[N,F,W] packed: messages in the topic of fanout slot f. Direct
    compare+pack — the [N,F]-row gather from the tiny [T,W] table lowers
    to a slow TPU gather (same finding as slot_topic_words)."""
    bits = (
        msg_topic[None, None, :] == fanout_topic[:, :, None]
    ) & (msg_topic >= 0)[None, None, :]
    return bitset.pack(bits)


@stages.part("fanout")
def fanout_carry_words(fanout_peers: jax.Array, fanout_topic: jax.Array,
                       msg_topic: jax.Array) -> jax.Array:
    """[N,K,W]: words each peer pushes on edge k for its fanout topics
    (gossipsub.go:1000-1002 — fanout peers receive published messages of
    unjoined topics)."""
    ftw = fanout_topic_words(fanout_topic, msg_topic)  # [N,F,W]
    contrib = jnp.where(fanout_peers[:, :, :, None], ftw[:, :, None, :], jnp.uint32(0))
    return bitset.word_or_reduce(contrib, axis=1)


# -- packed fanout-peer form (phase-loop internal) --------------------------
# The [N, F, K] bool peers plane is a pathological write target on TPU —
# bit-packed pred tiles make every sub-round update a read-modify-write
# over layout-padded tiles (the 2-axis scatter measured 670 us/round at
# eth2 N=100k, the P-step where-chain still 226 us at K = 16 and 4,551 us
# at K = 65: PERF.md §6, PR 31). So the K axis packs into ceil(K/32) u32
# words per (peer, slot): updates become [N, F, Wk] u32 selects and the
# carry consumer extracts bits on the fly. The phase engine packs at its
# head and unpacks at its tail, so the state dataclass, the heartbeat,
# peer transitions, and every external consumer keep the bool plane.

@stages.part("fanout")
def pack_fanout_peers(fanout_peers: jax.Array) -> jax.Array:
    """[..., K] bool -> [..., ceil(K/32)] u32 edge bitmask words."""
    return bitset.pack(fanout_peers)


@stages.part("fanout")
def unpack_fanout_peers(fp_pack: jax.Array, k: int) -> jax.Array:
    """[N,F,ceil(K/32)] u32 -> [N,F,K] bool."""
    return bitset.unpack(fp_pack, k)


@stages.part("fanout")
def fanout_carry_words_packed(fp_pack: jax.Array, k: int,
                              fanout_topic: jax.Array,
                              msg_topic: jax.Array) -> jax.Array:
    """fanout_carry_words on the packed [N,F,Wk] u32 peers form (the
    on-the-fly unpack fuses into the carry fold — same XLA graph, but
    the loop reads the packed words instead of the padded bool plane)."""
    return fanout_carry_words(
        unpack_fanout_peers(fp_pack, k), fanout_topic, msg_topic
    )


def gossip_edge_mask(cfg: GossipSubConfig, net: Net, st: GossipSubState,
                     joined_words: jax.Array, acc_ok: jax.Array,
                     slotw: jax.Array, msg_topic: jax.Array,
                     flood_edges: jax.Array, nbr_score_of_me,
                     thr=None) -> jax.Array:
    """[N,K,W] edge-carry mask: mesh push (forwarding along the sender's
    mesh, gossipsub.go:981-1002) + fanout push + floodsub-peer edges
    (protocol negotiation, gossipsub.go:973-978) + v1.1 flood-publish for
    origin-sent messages (gossipsub.go:957-963), gated by the receiver's
    graylist/gater.

    Sender-side packed outbox + word gather (no [N,K,M] traffic)."""
    thr = cfg if thr is None else thr
    carry_out = sender_carry_words(st.mesh, slotw)
    if cfg.fanout_slots > 0:
        carry_out = carry_out | fanout_carry_words(
            st.fanout_peers, st.fanout_topic, msg_topic
        )
    mask = jnp.where(
        net.nbr_ok[:, :, None],
        net.edge_gather(carry_out),
        jnp.uint32(0),
    )

    # floodsub-semantics edges (either endpoint is a floodsub peer): the
    # sender forwards everything; the receiver's joined filter applies below
    mask = mask | jnp.where(flood_edges[:, :, None], jnp.uint32(0xFFFFFFFF), jnp.uint32(0))

    if cfg.flood_publish:
        # origin floods to every topic peer it scores above publishThreshold;
        # elementwise compare fused into the pack
        origin_is_sender = st.core.msgs.origin[None, :] == net.nbr[..., None]  # [N,K,M]
        if cfg.score_enabled:
            flood_ok = nbr_score_of_me >= thr.publish_threshold
        else:
            flood_ok = net.nbr_ok
        mask = mask | (
            bitset.pack(origin_is_sender) & jnp.where(
                flood_ok[:, :, None], jnp.uint32(0xFFFFFFFF), jnp.uint32(0)
            )
        )

    mask = jnp.where(acc_ok[:, :, None], mask, jnp.uint32(0))
    return mask & joined_words[:, None, :]


@stages.part("fanout")
def update_fanout_on_publish(
    cfg: GossipSubConfig,
    net: Net,
    st: "GossipSubState",
    pub_origin: jax.Array,  # [P] i32, -1 pad
    pub_topic: jax.Array,   # [P] i32
    key: jax.Array,
    nbr_sub_words: jax.Array,  # [N,K,Wt] static: neighbors' topic-bit subs
    fp_pack: jax.Array | None = None,
    thr=None,                  # threshold source (cfg | lifted plane)
    msh=None,                  # mesh-degree source (cfg | MeshParams)
):
    """Publishing to an unjoined topic creates/refreshes a fanout slot with
    D random eligible peers (gossipsub.go:983-998) and stamps lastpub.

    Returns the updated state — or, when ``fp_pack`` (the phase loop's
    packed [N,F,Wk] u32 peers form) is given, ``(state, fp_pack)`` with
    ``state.fanout_peers`` left untouched (stale; the phase tail unpacks
    the packed form back into it)."""
    thr = cfg if thr is None else thr
    msh = cfg if msh is None else msh
    tick = st.core.tick
    p_dim = pub_origin.shape[0]
    f_dim = cfg.fanout_slots
    o = jnp.clip(pub_origin, 0)
    t = jnp.clip(pub_topic, 0)
    is_pub = pub_origin >= 0
    joined = net.subscribed[o, t]
    # floodsub-only origins flood instead of tracking fanout
    need = is_pub & ~joined & (net.protocol[o] >= 1)

    # find a slot: existing topic match, else the oldest slot. Several
    # same-round fresh publishes by one origin must land on *different*
    # slots: offset each by its rank among that origin's earlier fresh
    # entries (pairwise over the small P axis).
    ftop_o = st.fanout_topic[o]  # [P,F]
    match = ftop_o == t[:, None]
    has_match = jnp.any(match & need[:, None], axis=1)
    match_slot = jnp.argmax(match, axis=1)
    oldest_slot = jnp.argmin(st.fanout_lastpub[o] + jnp.where(ftop_o >= 0, 0, -(2**30)), axis=1)
    fresh = need & ~has_match
    idx_p = jnp.arange(p_dim)
    earlier_same_origin = (
        fresh[None, :] & fresh[:, None]
        & (o[None, :] == o[:, None]) & (idx_p[None, :] < idx_p[:, None])
    )  # [j, i]: i is an earlier fresh publish of j's origin
    # a fresh publish that repeats an earlier one of its round (same
    # origin AND topic) shares that one's slot and keeps its peers: the
    # reference's fanout is a map by topic (gossipsub.go:444), one entry
    # a topic
    twin = earlier_same_origin & (t[None, :] == t[:, None])
    has_twin = jnp.any(twin, axis=1)
    first_twin = jnp.argmax(twin, axis=1)
    fresh_rank = jnp.sum(
        (earlier_same_origin & ~has_twin[None, :]).astype(jnp.int32), axis=1
    )  # [P]
    slot = jnp.where(has_match, match_slot, (oldest_slot + fresh_rank) % f_dim)
    slot = jnp.where(has_twin, slot[first_twin], slot)
    fresh = fresh & ~has_twin

    # a matched slot whose peer set has emptied (churn, threshold filtering)
    # is repopulated like a fresh one (gossipsub.go:983-989: empty fanout
    # map entry => select peers anew)
    if fp_pack is not None:
        match_empty = has_match & jnp.all(
            jnp.take_along_axis(fp_pack[o], slot[:, None, None], axis=1)
            [:, 0, :] == 0, axis=-1
        )
    else:
        match_empty = has_match & (
            count_true(jnp.take_along_axis(st.fanout_peers[o], slot[:, None, None], axis=1)[:, 0, :]) == 0
        )
    fresh = fresh | match_empty

    # candidates for a fresh slot: connected, mesh-capable, subscribed to
    # the topic, not direct, score >= publishThreshold
    nbr_subbed = bitset.bit_get(
        nbr_sub_words[o], jnp.broadcast_to(t[:, None], (p_dim, net.max_degree))
    )
    cand = (
        nbr_subbed
        & net.nbr_ok[o]
        & (net.protocol[jnp.clip(net.nbr[o], 0)] >= 1)
        & ~net.direct[o]
    )
    if cfg.score_enabled:
        cand = cand & (st.scores[o] >= thr.publish_threshold)
    sel = masked_width_random(key, cand, msh.D, net.max_degree,
                              fused=cfg.fused)  # [P,K]

    # commit: new slots take the fresh selection; matched slots keep
    # theirs. A static fold of P masked selects over the [N, F] planes —
    # NOT a 2-axis scatter: .at[po, slot].set lowered to ~670 us/round
    # on the real chip at N=100k (47% of the whole eth2 phase round,
    # round-5 profile) to write <=P rows, while the P fused where-passes
    # cost plane bandwidth (~3 MB) once. Ascending-j overwrite keeps the
    # scatter's last-update-wins semantics for duplicate (origin, slot)
    # pairs in one batch.
    rows = jnp.arange(net.n_peers, dtype=jnp.int32)
    fslots = jnp.arange(f_dim, dtype=jnp.int32)
    fanout_topic = st.fanout_topic
    fanout_lastpub = st.fanout_lastpub
    # (a winner-index fold that touches the [N, F, K] plane once was
    # tried and measured WORSE — eth2 961 -> 555 rounds/s: the extra
    # [N, F] winner plane + two-chain combine broke the single loop
    # fusion XLA builds for this direct P-step where-chain)
    packed = fp_pack is not None
    sel_pack = pack_fanout_peers(sel) if packed else None  # [P,Wk] u32
    fanout_peers = st.fanout_peers
    for j in range(p_dim):
        mask = ((rows == jnp.where(need[j], o[j], net.n_peers))[:, None]
                & (fslots == slot[j])[None, :])  # [N, F]
        fanout_topic = jnp.where(mask, t[j], fanout_topic)
        fanout_lastpub = jnp.where(mask, tick, fanout_lastpub)
        if packed:
            fp_pack = jnp.where((mask & fresh[j])[:, :, None],
                                sel_pack[j][None, None, :], fp_pack)
        else:
            fanout_peers = jnp.where(
                (mask & fresh[j])[:, :, None], sel[j][None, None, :],
                fanout_peers,
            )
    if packed:
        return st.replace(
            fanout_topic=fanout_topic,
            fanout_lastpub=fanout_lastpub,
        ), fp_pack
    return st.replace(
        fanout_topic=fanout_topic,
        fanout_peers=fanout_peers,
        fanout_lastpub=fanout_lastpub,
    )


@stages.scope("deliver")
def merge_extra_tx(net: Net, msgs, dlv, info, extra: jax.Array, tick,
                   count_events: bool = True, queue_cap: int = 0,
                   val_delay_topic: tuple | None = None):
    """Fold IWANT-response transmissions (not part of senders' fwd sets)
    into the round's delivery results. With the async-validation pipeline
    these receipts enter stage 0 like any other arrival; their verdict
    (forward/Deliver/first_round) happens at pipeline exit.

    With `queue_cap` the responses share the link's outbound budget with
    the mesh push already in `info.trans` — overflow is dropped and
    counted (IWANT responses are ordinary messages in the reference's
    per-peer writer queue, comm.go:139-170)."""
    m = msgs.capacity
    val_delay = 0 if dlv.pending is None else dlv.pending.shape[1]
    extra = extra & ~origin_msg_words(net, msgs)[:, None, :]
    if msgs.wire_block is not None:
        # IWANT responses for oversized messages die at the wire too — but
        # only after the retransmission counter ticked (mcache.GetForPeer
        # counts the attempt before sendRPC drops it, mcache.go:66-80 ->
        # gossipsub.go:1126-1140), which iwant_responses already did
        extra = extra & ~bitset.pack(msgs.wire_block)[None, None, :]
    if queue_cap > 0:
        used = bitset.popcount(info.trans, axis=-1)  # [N,K]
        budget = jnp.maximum(queue_cap - used, 0)
        want = extra
        extra = _prefix_cap_bits(want, budget, m)
        info = info.replace(
            n_drop=info.n_drop
            + bitset.popcount(want & ~extra, axis=None).sum().astype(jnp.int32)
        )

    recv = bitset.word_or_reduce(extra, axis=1)
    new_words = recv & ~dlv.have
    new_bits = bitset.unpack(new_words, m)

    fa_words = bitset.first_set_per_bit(extra, axis=1) & new_words[:, None, :]
    valid_words = bitset.pack(msgs.valid)

    dlv = dlv.replace(
        have=dlv.have | new_words,
        fe_words=(dlv.fe_words & ~new_words[:, None, :]) | fa_words,
    )
    if val_delay > 0:
        from .common import pipeline_insert

        dlv = dlv.replace(
            pending=pipeline_insert(
                dlv.pending, new_words, msgs.topic, val_delay_topic
            )
        )
    else:
        dlv = dlv.replace(
            fwd=dlv.fwd | (new_words & valid_words[None, :]),
            first_round=jnp.where(new_bits, tick, dlv.first_round),
        )

    info = info.replace(
        trans=info.trans | extra,
        recv_new_words=info.recv_new_words | new_words,
    )
    if val_delay == 0:
        info = info.replace(
            new_words=info.new_words | new_words,
            new_bits=info.new_bits | new_bits,
        )
    if count_events:
        n_extra = bitset.popcount(extra, axis=-1).sum().astype(jnp.int32)
        n_new = bitset.popcount(new_words, axis=-1).sum().astype(jnp.int32)
        info = info.replace(
            n_duplicate=info.n_duplicate + (n_extra - n_new),
            n_rpc=info.n_rpc + n_extra,
        )
        if val_delay == 0:
            n_deliver = bitset.popcount(
                new_words & valid_words[None, :], axis=-1
            ).sum().astype(jnp.int32)
            info = info.replace(
                n_deliver=info.n_deliver + n_deliver,
                n_reject=info.n_reject + (n_new - n_deliver),
            )
    return dlv, info


# ---------------------------------------------------------------------------
# the heartbeat (gossipsub.go:1303-1564)


@stages.scope("heartbeat")
def heartbeat(cfg: GossipSubConfig, net: Net, st: GossipSubState, tp: dict,
              score_params: PeerScoreParams | None,
              nbr_sub: jax.Array, gater_params=None,
              nbr_sub_words: jax.Array | None = None,
              present_ok: jax.Array | None = None,
              gossip_suppress: jax.Array | None = None,
              app_gathered: jax.Array | None = None,
              adversary=None, thr=None, msh=None) -> GossipSubState:
    """`net` is the live view (nbr_ok masked by churn/edge-liveness);
    `present_ok` is the static edge-presence mask, needed by directConnect
    to re-dial edges that are currently dormant (defaults to net.nbr_ok).
    `gossip_suppress` [N,K] marks congested outbound links whose IHAVE
    batch is dropped this heartbeat (queue_cap backpressure).
    ``app_gathered`` is the pre-gathered P5 plane when the coalesced wire
    exchange carried it (app_score is phase-invariant, so the head gather
    equals the tail gather bit-for-bit).
    ``adversary`` (a chaos.adversary.AdversaryConsts, None = elided)
    applies the heartbeat-cadence attacker behaviors: self-promotion
    pins sybil-held scores of fellow sybils, graft-spam overwrites the
    GRAFT outbox ignoring backoff (and zeroes the attackers' own
    backoff bookkeeping — raw-wire fakes keep no router state), and
    lie-in-IHAVE advertises every live message id on every edge.
    ``thr`` is the threshold source (cfg, or a lifted build's traced
    ScoreParams plane — score_params is then that same plane).
    ``msh`` is the mesh-degree source (cfg, or a candidate-lifted
    build's traced MeshParams plane, round 20): every degree width it
    feeds goes through ops/select's masked-width kernels with the
    padded neighbor axis as the static ceiling."""
    thr = cfg if thr is None else thr
    msh = cfg if msh is None else msh
    tick = st.core.tick
    n, s_dim, k_dim = st.mesh.shape
    m = st.core.msgs.capacity
    key = jax.random.fold_in(st.core.key, tick)
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    events = st.core.events

    # applyIwantPenalties: broken promises -> P7 (gossipsub.go:1578-1583)
    # (one-hot word pick instead of an [N,K,M] compare-reduce)
    promised_have = bitset.bit_get(st.core.dlv.have[:, None, :], st.promise_mid)
    live = st.promise_mid >= 0
    fulfilled = live & promised_have
    broken = live & ~promised_have & (tick > st.promise_expire)
    score = st.score
    if cfg.score_enabled:
        score = add_penalties(score, broken.astype(jnp.float32))
    promise_mid = jnp.where(fulfilled | broken, -1, st.promise_mid)

    # clearIHaveCounters (gossipsub.go:1566-1576)
    peerhave = jnp.zeros_like(st.peerhave)
    iasked = jnp.zeros_like(st.iasked)

    # clearBackoff every 15 ticks with slack (gossipsub.go:1585-1604)
    clear_now = (tick % cfg.backoff_clear_ticks) == 0
    expired = (st.backoff_expire + cfg.backoff_slack_ticks) < tick
    backoff_present = jnp.where(clear_now, st.backoff_present & ~expired, st.backoff_present)
    # adversary graft-spam: attackers keep NO backoff bookkeeping (the
    # reference attacker is a raw-wire fake with no router state), and
    # the clear must land BEFORE the candidate filter below — a spam
    # attacker pruned last round re-grafts its victims immediately
    # (clearing only at the tail would leave the heartbeat's candidate
    # set backoff-excluded while the post-step state reads clear, a
    # decision/check mismatch the degree-bound oracle would flag)
    if adversary is not None and adversary.has("graft_spam"):
        spam_a = adversary.active_self("graft_spam", tick)
        backoff_present = jnp.where(spam_a[:, None, None], False,
                                    backoff_present)

    # refreshScores + memoized score cache (gossipsub.go:1333-1341)
    if cfg.score_enabled:
        score = refresh_scores(score, st.mesh, tick, tp, score_params)
        scores = compute_scores(score, st.mesh, tp, score_params, st.p6,
                                st.app_score, net, app_gathered=app_gathered)
        # adversary self-promotion (chaos/adversary.py): cooperating
        # sybils pin their held scores of FELLOW sybils at the promo
        # value — applied to the memoized plane at refresh, so every
        # consumer (mesh maintenance, gossip targeting, accept gates,
        # the wire score column) sees the faction's cohesion; honest
        # peers' scoring of sybils (the defense) is untouched
        if adversary is not None and adversary.has("self_promo"):
            promo = adversary.active_self("self_promo", tick)
            scores = jnp.where(promo[:, None] & adversary.sybil_nbr,
                               adversary.promo_score, scores)
    else:
        scores = st.scores

    # gater counter decay (peer_gater.go:204-216; DecayInterval default ==
    # the heartbeat interval)
    gater_state = st.gater
    if cfg.gater_enabled:
        gater_state = gater_decay(gater_state, gater_params)

    # ---- mesh maintenance per (peer, topic-slot) ------------------------
    # floodsub-only nodes run no mesh/gossip machinery at all
    mesh = st.mesh
    slot_live = (net.my_topics >= 0) & (net.protocol >= 1)[:, None]
    connected = net.nbr_ok[:, None, :] & slot_live[:, :, None]
    scores_b = jnp.broadcast_to(scores[:, None, :], mesh.shape)

    tograft = jnp.zeros_like(mesh)
    toprune = jnp.zeros_like(mesh)

    # drop negative-score mesh members, no PX (gossipsub.go:1361-1368)
    if cfg.score_enabled:
        bad = mesh & (scores_b < 0)
        toprune = toprune | bad
        mesh = mesh & ~bad

    # candidate filter (gossipsub.go:1374-1380): backoff *presence*
    cand = connected & nbr_sub & ~mesh & ~backoff_present & ~net.direct[:, None, :]
    if cfg.score_enabled:
        cand = cand & (scores_b >= 0)

    # Each maintenance sub-pass below is lax.cond-gated on "any row needs
    # it": in a converged mesh the low-degree/over-subscription/quota cases
    # are rare, and skipping their selection ranks most ticks is pure win
    # (both branches produce identical results to the unconditional code —
    # a selection with an all-zero need-vector is the empty mask).

    # |mesh| < Dlo -> graft to D (gossipsub.go:1371-1385)
    deg = count_true(mesh)
    ineed = jnp.where(deg < msh.Dlo, msh.D - deg, 0)
    grafts = jax.lax.cond(
        jnp.any(ineed > 0),
        lambda: masked_width_random(k1, cand, ineed, k_dim, fused=cfg.fused),
        lambda: jnp.zeros_like(mesh),
    )
    mesh = mesh | grafts
    tograft = tograft | grafts

    # |mesh| > Dhi -> keep Dscore best + random to D, Dout outbound
    # (gossipsub.go:1388-1448)
    deg = count_true(mesh)
    over = (deg > msh.Dhi)[:, :, None]
    outb = jnp.broadcast_to(net.outbound[:, None, :], mesh.shape)

    def _over_subscribed():
        noise = jax.random.uniform(k2, mesh.shape)
        if cfg.score_enabled:
            topscore = masked_width_topk(scores_b, mesh, msh.Dscore, k_dim,
                                         key=k3, fused=cfg.fused)
        else:
            topscore = masked_width_random(k3, mesh, msh.Dscore, k_dim,
                                           fused=cfg.fused)
        rest_rand = masked_width_topk(noise, mesh & ~topscore,
                                      msh.D - msh.Dscore, k_dim,
                                      fused=cfg.fused)
        keep = topscore | rest_rand
        x_need = jnp.maximum(msh.Dout - count_true(keep & outb), 0)
        bring = select_topk_mask(noise, mesh & outb & ~keep, x_need,
                                 fused=cfg.fused)
        drop = select_topk_mask(-noise, keep & ~outb & ~topscore,
                                count_true(bring), fused=cfg.fused)
        keep = (keep & ~drop) | bring
        pruned_over = mesh & ~keep & over
        return jnp.where(over, mesh & keep, mesh), pruned_over

    mesh, pruned_over = jax.lax.cond(
        jnp.any(over),
        _over_subscribed,
        lambda: (mesh, jnp.zeros_like(mesh)),
    )
    toprune = toprune | pruned_over
    # over-subscription prunes carry PX; score-prunes (`bad` above) are
    # noPX (gossipsub.go:1365 vs :1446 — makePrune's doPX argument)
    if cfg.do_px:
        px_prune = pruned_over & (scores_b >= 0 if cfg.score_enabled else True)
    else:
        px_prune = jnp.zeros_like(pruned_over)

    # outbound quota top-up at Dlo <= |mesh| (gossipsub.go:1451-1476)
    deg = count_true(mesh)
    need_out = jnp.where(
        deg >= msh.Dlo, jnp.maximum(msh.Dout - count_true(mesh & outb), 0), 0
    )
    grafts2 = jax.lax.cond(
        jnp.any(need_out > 0),
        lambda: masked_width_random(k4, cand & outb & ~mesh, need_out, k_dim,
                                    fused=cfg.fused),
        lambda: jnp.zeros_like(mesh),
    )
    mesh = mesh | grafts2
    tograft = tograft | grafts2

    # opportunistic grafting (gossipsub.go:1479-1510)
    if cfg.score_enabled and cfg.opportunistic_graft_ticks > 0:
        def _oppo_grafts():
            med = median_masked(scores_b, mesh)  # [N,S]
            low = (med < thr.opportunistic_graft_threshold) & (count_true(mesh) > 1)
            cand3 = cand & ~mesh & (scores_b > med[:, :, None])
            return select_random_mask(
                k5, cand3, jnp.where(low, cfg.opportunistic_graft_peers, 0),
                fused=cfg.fused,
            )

        grafts3 = jax.lax.cond(
            (tick % cfg.opportunistic_graft_ticks) == 0,
            _oppo_grafts,
            lambda: jnp.zeros_like(mesh),
        )
        mesh = mesh | grafts3
        tograft = tograft | grafts3

    new_grafts = tograft & ~st.mesh
    if cfg.score_enabled:
        score = on_graft(score, new_grafts, tick)
        score = on_prune(score, toprune, tp)
    backoff_expire = jnp.where(
        toprune, jnp.maximum(st.backoff_expire, tick + cfg.prune_backoff_ticks),
        st.backoff_expire,
    )
    backoff_present = backoff_present | toprune

    # ---- fanout maintenance (gossipsub.go:1517-1554) --------------------
    ft = st.fanout_topic
    fpeers = st.fanout_peers
    flastpub = st.fanout_lastpub
    if nbr_sub_words is not None and cfg.fanout_slots > 0:
        with stages.part("fanout"):
            # expire by FanoutTTL since last publish (gossipsub.go:1518-1524)
            expired = (ft >= 0) & (flastpub + cfg.fanout_ttl_rounds < tick)
            ft = jnp.where(expired, -1, ft)
            f_live = ft >= 0
            fpeers = fpeers & f_live[:, :, None]
            # drop peers below the publish threshold (gossipsub.go:1528-1534)
            if cfg.score_enabled:
                fpeers = fpeers & (scores[:, None, :] >= thr.publish_threshold)
            # neighbor-subscribes-fanout-topic via topic-bit extraction
            n_f, f_dim = ft.shape
            nbr_sub_f = bitset.bit_get(
                jnp.broadcast_to(
                    nbr_sub_words[:, None, :, :], (n_f, f_dim) + nbr_sub_words.shape[1:]
                ),
                jnp.broadcast_to(jnp.clip(ft, 0)[:, :, None], fpeers.shape),
            )
            mesh_capable = (net.peer_gather(net.protocol) >= 1) & net.nbr_ok
            base_f = (
                nbr_sub_f
                & mesh_capable[:, None, :]
                & ~net.direct[:, None, :]
                & f_live[:, :, None]
            )
            cand_f = base_f & ~fpeers
            if cfg.score_enabled:
                cand_f = cand_f & (scores[:, None, :] >= thr.publish_threshold)
            ineed_f = jnp.where(f_live, msh.D - count_true(fpeers), 0)
            kf1, kf2 = jax.random.split(jax.random.fold_in(key, 11))
            fpeers = fpeers | masked_width_random(kf1, cand_f, ineed_f, k_dim,
                                                  fused=cfg.fused)

    # ---- choke/unchoke decision (routers/choke.py, DESIGN.md §24b) ------
    # after mesh maintenance (the guard must see the post-maintenance
    # mesh), before emitGossip (whose targets fold the choked links in).
    # The sender learns it is choked via ONE extra edge gather — the
    # choke annotation piggybacks the heartbeat's control batch (an
    # instant-knowledge approximation of the one-RTT outbox model,
    # documented in §24b; the suppression itself is receiver-local).
    router = cfg.router
    choked_by = None
    if router is not None and router.choke:
        choked_next = choke_guard(msh.Dlo, mesh, st.choked)
        choked_next, n_choke, n_unchoke = choke_decide(
            router, msh.Dlo, mesh, choked_next, st.choke_ema,
            fused=cfg.fused,
        )
        choked_by = net.edge_gather(jnp.any(choked_next, axis=1)) & net.nbr_ok
        if cfg.count_events:
            events = (
                events.at[EV.CHOKE].add(n_choke)
                .at[EV.UNCHOKE].add(n_unchoke)
            )

    # ---- emitGossip (gossipsub.go:1669-1723) ----------------------------
    gwin = bitset.word_or_reduce(st.mcache[:, : cfg.history_gossip, :], axis=1)  # [N,W]
    gossip_cand = connected & nbr_sub & ~mesh & ~net.direct[:, None, :]
    if gossip_suppress is not None:
        gossip_cand = gossip_cand & ~gossip_suppress[:, None, :]
    if cfg.score_enabled:
        gossip_cand = gossip_cand & (scores_b >= thr.gossip_threshold)
    n_cand = count_true(gossip_cand)
    target = jnp.maximum(
        msh.Dlazy,
        (jnp.asarray(msh.gossip_factor, jnp.float32)
         * n_cand.astype(jnp.float32)).astype(jnp.int32),
    )
    chosen = masked_width_random(k6, gossip_cand, target, k_dim,
                                 fused=cfg.fused)  # [N,S,K]
    if choked_by is not None:
        # a choked mesh link is IHAVE-only: the choked sender ALWAYS
        # gossips to the choking neighbor (not a lottery entry — episub's
        # lazy links carry every id), so ids keep flowing and IWANT
        # service keeps working while eager data is suppressed
        chosen = chosen | (
            connected & nbr_sub & choked_by[:, None, :]
            & ~net.direct[:, None, :]
        )

    slot_tw = slot_topic_words(net, st.core.msgs.topic)  # [N,S,W]
    adv = jnp.where(
        chosen[..., None], (gwin[:, None, :] & slot_tw)[:, :, None, :], jnp.uint32(0)
    )  # [N,S,K,W]
    ihave_out = bitset.word_or_reduce(adv, axis=1)  # [N,K,W]

    # fanout-topic gossip (gossipsub.go:1551-1553; fanout peers excluded)
    if nbr_sub_words is not None and cfg.fanout_slots > 0:
        with stages.part("fanout"):
            gossip_cand_f = base_f & ~fpeers
            if gossip_suppress is not None:
                gossip_cand_f = gossip_cand_f & ~gossip_suppress[:, None, :]
            if cfg.score_enabled:
                gossip_cand_f = gossip_cand_f & (scores[:, None, :] >= thr.gossip_threshold)
            n_cand_f = count_true(gossip_cand_f)
            target_f = jnp.where(
                (ft >= 0),
                jnp.maximum(
                    msh.Dlazy,
                    (jnp.asarray(msh.gossip_factor, jnp.float32)
                     * n_cand_f.astype(jnp.float32)).astype(jnp.int32),
                ),
                0,
            )
            chosen_f = masked_width_random(kf2, gossip_cand_f, target_f, k_dim,
                                           fused=cfg.fused)  # [N,F,K]
            ftw = fanout_topic_words(ft, st.core.msgs.topic)
            adv_f = jnp.where(
                chosen_f[..., None], (gwin[:, None, :] & ftw)[:, :, None, :], jnp.uint32(0)
            )
            ihave_out = ihave_out | bitset.word_or_reduce(adv_f, axis=1)

    # mcache.Shift (gossipsub.go:1563)
    mcache = jnp.concatenate(
        [jnp.zeros_like(st.mcache[:, :1, :]), st.mcache[:, :-1, :]], axis=1
    )

    # directConnect (gossipsub.go:1606-1628): every DirectConnectTicks,
    # re-dial direct peers — in the PX edge-liveness model, a dormant
    # direct edge reactivates (both directions)
    edge_live = st.edge_live
    if cfg.do_px and cfg.direct_connect_ticks > 0:
        direct_sym = net.direct | net.edge_gather(net.direct)
        # tick 0 is skipped: the reference delays the first dial
        # (DirectConnectInitialDelay) past connection setup
        redial = ((tick % cfg.direct_connect_ticks) == 0) & (tick > 0)
        ok = net.nbr_ok if present_ok is None else present_ok
        edge_live = jnp.where(redial, edge_live | (direct_sym & ok), edge_live)

    # ---- adversary heartbeat behaviors (chaos/adversary.py §13) ---------
    graft_out_next = new_grafts
    if adversary is not None:
        if adversary.has("graft_spam"):
            # GRAFT every eligible (live slot, edge) ignoring backoff
            # (the GRAFT-flood attacker, gossipsub_spam_test.go:365);
            # spam attackers keep no backoff bookkeeping of their own —
            # the reference attacker is a raw-wire fake with no router
            # state — so their planes zero (the oracle plane's backoff
            # properties quantify over peers that RUN the router)
            spam_a = adversary.active_self("graft_spam", tick)
            spam = (spam_a[:, None, None] & slot_live[:, :, None]
                    & adversary.spam_edges[:, None, :])
            graft_out_next = graft_out_next | spam
            backoff_present = jnp.where(spam_a[:, None, None], False,
                                        backoff_present)
            backoff_expire = jnp.where(spam_a[:, None, None], 0,
                                       backoff_expire)
            if cfg.count_events:
                events = events.at[EV.ADV_GRAFT_SPAM].add(
                    jnp.sum(spam.astype(jnp.int32)))
        if adversary.has("lie_ihave"):
            # advertise EVERY live message id on every present edge,
            # held or not (IHAVE spam, gossipsub_spam_test.go:290) —
            # the victims' IWANTs go unserved (the attacker's real
            # mcache lacks the ids), breaking gossip promises → P7
            lie_a = adversary.active_self("lie_ihave", tick)
            live_w = bitset.pack(st.core.msgs.birth >= 0)     # [W]
            lie = jnp.where((lie_a[:, None] & net.nbr_ok)[:, :, None],
                            live_w[None, None, :], jnp.uint32(0))
            if cfg.count_events:
                events = events.at[EV.ADV_IHAVE_LIE].add(
                    bitset.popcount(lie & ~ihave_out, axis=None)
                    .sum().astype(jnp.int32))
            ihave_out = ihave_out | lie

    if cfg.count_events:
        events = (
            events.at[EV.GRAFT].add(jnp.sum(new_grafts.astype(jnp.int32)))
            .at[EV.PRUNE].add(jnp.sum(toprune.astype(jnp.int32)))
        )

    return st.replace(
        core=st.core.replace(events=events),
        mesh=mesh,
        edge_live=edge_live,
        backoff_expire=backoff_expire,
        backoff_present=backoff_present,
        mcache=mcache,
        ihave_out=ihave_out,
        graft_out=graft_out_next,
        prune_out=st.prune_out | toprune,
        prune_px_out=st.prune_px_out | px_prune,
        peerhave=peerhave,
        iasked=iasked,
        promise_mid=promise_mid,
        score=score,
        scores=scores,
        gater=gater_state,
        fanout_topic=ft,
        fanout_peers=fpeers,
        fanout_lastpub=flastpub,
        **({"choked": choked_next}
           if router is not None and router.choke else {}),
    )


def gather_nbr_subscribed(net: Net) -> jax.Array:
    """[N,S,K]: neighbor k subscribes the topic of my slot s."""
    n, s_dim = net.my_topics.shape
    k_dim = net.nbr.shape[1]
    sub_nbr = net.subscribed[jnp.clip(net.nbr, 0)]  # [N,K,T]
    out = jnp.take_along_axis(
        sub_nbr, jnp.broadcast_to(jnp.clip(net.my_topics, 0)[:, None, :], (n, k_dim, s_dim)),
        axis=2,
    ).transpose(0, 2, 1)
    return out & net.nbr_ok[:, None, :] & (net.my_topics >= 0)[:, :, None]


# ---------------------------------------------------------------------------
# the full per-round step


def apply_validation_throttle(dlv, info, cap: int, m: int, valid_words):
    """Model the validation front-end queue (validation.go:230-244 Push with
    a full queue => RejectValidationThrottled): each peer admits at most
    `cap` new receipts per round; overflow receipts are refused — not marked
    seen, not forwarded, no score attribution (score.go:745-749,761-767).
    The cap applies at queue admission (this round's fresh receipts), so
    with the async pipeline it clears stage 0 instead of the verdict state.

    Returns (dlv, info, accepted_new_words, n_throttled[N])."""
    val_delay = 0 if dlv.pending is None else dlv.pending.shape[1]
    entry = info.recv_new_words
    # static cap: the clear-lowest-bit chain, not the unpack+cumsum form
    # (this runs per SUB-ROUND under the phase engine — the cumsum was
    # 55% of the sybil phase round, bitset.keep_lowest_bits docstring)
    accepted = bitset.keep_lowest_bits(entry, cap, m)
    refused = entry & ~accepted
    n_throttled = bitset.popcount(refused, axis=-1)
    n_ref = n_throttled.sum().astype(jnp.int32)

    if val_delay > 0:
        # refused receipts are fresh this round, so they sit in exactly
        # their entry stage; clearing every stage is equivalent and works
        # for any per-topic entry pattern
        dlv = dlv.replace(
            have=dlv.have & ~refused,
            fe_words=dlv.fe_words & ~refused[:, None, :],
            pending=dlv.pending & ~refused[:, None, :],
        )
        # this round's verdicts (pipeline exits) are unaffected; throttled
        # receipts trace Reject now
        info = info.replace(
            recv_new_words=accepted,
            n_reject=info.n_reject + n_ref,
        )
        return dlv, info, info.new_words, n_throttled

    refused_bits = bitset.unpack(refused, m)
    dlv = dlv.replace(
        have=dlv.have & ~refused,
        fwd=dlv.fwd & ~refused,
        first_round=jnp.where(refused_bits, -1, dlv.first_round),
        fe_words=dlv.fe_words & ~refused[:, None, :],
    )
    info = info.replace(
        new_words=accepted,
        new_bits=bitset.unpack(accepted, m),
        recv_new_words=accepted,
        # accepted-valid deliver; accepted-invalid + throttled trace Reject
        n_deliver=bitset.popcount(accepted & valid_words[None, :], axis=-1).sum().astype(jnp.int32),
        n_reject=bitset.popcount(accepted & ~valid_words[None, :], axis=-1).sum().astype(jnp.int32) + n_ref,
    )
    return dlv, info, accepted, n_throttled


class StepConsts:
    """Static per-topology jit constants shared by the per-round step
    (`make_gossipsub_step`) and the multi-round phase step
    (`gossipsub_phase.make_gossipsub_phase_step`). Computed eagerly once
    at build time."""

    __slots__ = (
        "score_params", "tp", "tpa", "window_rounds_t", "nbr_sub_const",
        "flood_from", "i_am_floodsub", "nbr_sub_words", "sender_fwd_ok",
        "adv",
    )

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, kw[k])


def topology_views(net: Net):
    """The neighbor-derived topology views the step reads every round:
    (nbr_sub, flood_from, nbr_sub_words). Static builds compute them
    once, eagerly, in `prepare_step_consts`; dynamic-overlay builds
    (``dynamic_topo=True``) recompute them on device each round from the
    mutated edge planes — same expressions, traced instead of baked, so
    the two paths can never drift apart.

    ``i_am_floodsub`` is NOT here: a peer's protocol never changes
    across mutations (death + replacement revives the same peer id with
    its protocol), so it stays a jit constant even under dynamics."""
    # mesh candidates require a mesh-capable far end (gossipsub_feat.go
    # GossipSubFeatureMesh; checked at gossipsub.go:1374,1692)
    mesh_capable = (net.protocol[jnp.clip(net.nbr, 0)] >= 1) & net.nbr_ok
    nbr_sub = gather_nbr_subscribed(net) & mesh_capable[:, None, :]
    # floodsub-semantics edges: the far end only speaks /floodsub/1.0.0
    flood_from = (net.protocol[jnp.clip(net.nbr, 0)] == 0) & net.nbr_ok
    # neighbors' full subscriptions as topic-bit words (for fanout checks)
    subscribed_words_t = bitset.pack(net.subscribed)  # [N, Wt]
    nbr_sub_words = jnp.where(
        net.nbr_ok[:, :, None],
        subscribed_words_t[jnp.clip(net.nbr, 0)],
        jnp.uint32(0),
    )  # [N,K,Wt]
    return nbr_sub, flood_from, nbr_sub_words


def prepare_step_consts(
    cfg: GossipSubConfig,
    net: Net,
    score_params: PeerScoreParams | None,
    heartbeat_interval: float,
    gater_params,
    sub_knowledge_holes: np.ndarray | None,
    adversary_no_forward: np.ndarray | None,
    adversary=None,
) -> StepConsts:
    """Validate the configuration and build the static topology constants
    (see the field comments inline — each maps a reference-side check)."""
    if cfg.edge_layout != net.edge_layout:
        # the layout is a FROZEN static: one engine build traces exactly
        # one layout (docs/DESIGN.md §15) — a config/net mismatch would
        # silently trace the net's layout while the fingerprint records
        # the config's
        raise ValueError(
            f"cfg.edge_layout={cfg.edge_layout!r} but the Net was built "
            f"with edge_layout={net.edge_layout!r} — build both with the "
            "same layout (Net.build(..., edge_layout=...))"
        )
    if cfg.fused != net.fused:
        # same frozen-static contract as edge_layout (round 21): the
        # fused flag selects one kernel set per build — the config
        # drives the selection/heartbeat blocks, the net drives the
        # shared delivery seam, and a mismatch would trace half of each
        raise ValueError(
            f"cfg.fused={cfg.fused!r} but the Net was built with "
            f"fused={net.fused!r} — build both with the same flag "
            "(Net.build(..., fused=...))"
        )
    if cfg.gater_enabled:
        assert gater_params is not None
        gater_params.validate()
    if cfg.validation_delay_topic is not None and (
        len(cfg.validation_delay_topic) != net.n_topics
    ):
        # the engine's per-message delay gather would silently clamp
        # out-of-range topic ids; reject the mismatch at build time
        raise ValueError(
            f"validation_delay_topic has {len(cfg.validation_delay_topic)} "
            f"entries for a {net.n_topics}-topic universe"
        )
    if cfg.score_enabled:
        assert score_params is not None
        score_params.validate()
        tpa = TopicParamsArrays.build(score_params, net.n_topics,
                                      heartbeat_interval, cfg.heartbeat_every)
    else:
        score_params = PeerScoreParams(topics={}, skip_app_specific=True)
        tpa = TopicParamsArrays.build(score_params, net.n_topics)
    tp = tpa.gather(net.my_topics)
    window_rounds_t = jnp.asarray(tpa.window_rounds)
    nbr_sub_const, flood_from, nbr_sub_words = topology_views(net)
    # announce-visibility holes (pubsub.go:842-901): sub_knowledge_holes
    # [N,K,T] marks (receiver i, edge k, topic t) triples whose SubOpts
    # announcement has not yet arrived — the unannounced subscriber is
    # invisible to mesh-candidate selection, gossip targeting, and fanout
    # (the host's announce-retry model under queue_cap supplies the mask
    # and recompiles as announcements land; api.Network._process_announces)
    if sub_knowledge_holes is not None:
        _holes = np.asarray(sub_knowledge_holes, bool)  # [N,K,T]
        _mt = np.asarray(net.my_topics)                 # [N,S]
        _hs = np.take_along_axis(
            _holes, np.clip(_mt, 0, None)[:, None, :], axis=2
        ).transpose(0, 2, 1)                            # [N,S,K]
        _hs = _hs & (_mt >= 0)[:, :, None]
        nbr_sub_const = nbr_sub_const & ~jnp.asarray(_hs)
    i_am_floodsub = net.protocol == 0
    if sub_knowledge_holes is not None:
        # unannounced subscriptions are invisible to fanout selection too
        nbr_sub_words = nbr_sub_words & ~bitset.pack(
            jnp.asarray(np.asarray(sub_knowledge_holes, bool))
        )
    # adversary behavior vector: edge (j,k) carries data only if its sender
    # nbr[j,k] forwards (static jit constant; None => all-honest fast path)
    if adversary_no_forward is not None:
        adv = jnp.asarray(adversary_no_forward, bool)
        sender_fwd_ok = ~adv[jnp.clip(net.nbr, 0)] & net.nbr_ok  # [N,K]
    else:
        sender_fwd_ok = None
    # adversary plane (chaos/adversary.py): None elides it statically;
    # when live, every per-peer plane and its neighbor view is an EAGER
    # jit constant here, so per-round activity tests are elementwise
    # compares against the tick — zero extra halo permutes
    adversary = adversary_mod.resolve(adversary)
    adv_consts = (
        adversary_mod.AdversaryConsts(adversary, net)
        if adversary is not None else None
    )
    return StepConsts(
        score_params=score_params, tp=tp, tpa=tpa,
        window_rounds_t=window_rounds_t, nbr_sub_const=nbr_sub_const,
        flood_from=flood_from, i_am_floodsub=i_am_floodsub,
        nbr_sub_words=nbr_sub_words, sender_fwd_ok=sender_fwd_ok,
        adv=adv_consts,
    )


def liveness_code(st: GossipSubState, up_next: jax.Array):
    """The sender side of a phase head's peer transitions, local arithmetic
    alone: ``(eff_next, down_tr, code)``, ``eff_next`` who is up after the
    head (a blacklisted peer stays down), ``down_tr`` who goes down, and
    the ``[N]`` ``uint32`` code that carries both across the edges (bit 0
    ``down_tr``, bit 1 the new ``up``)."""
    eff_next = up_next & ~st.blacklist
    down_tr = st.up & ~eff_next
    code = down_tr.astype(jnp.uint32) | (eff_next.astype(jnp.uint32) << 1)
    return eff_next, down_tr, code


def apply_peer_transitions(cfg: GossipSubConfig, net: Net, st: GossipSubState,
                           up_next: jax.Array, tp: dict,
                           code_nbr: jax.Array | None = None):
    """Peer lifecycle transitions (dynamic_peers builds): disconnect
    down/blacklisted peers with full dead-peer cleanup (handleDeadPeers
    pubsub.go:648-689 + router RemovePeer gossipsub.go:545-562 + score
    retention score.go:604-689). Returns (st, live-edge mask).

    ``code_nbr`` is ``liveness_code``'s code already across the edges
    (``[N, K]``, unmasked), where the caller's own wire exchange carried
    it: the phase engine's coalesced control head does. Given it, the
    function gathers nothing."""
    eff_next, down_tr, code = liveness_code(st, up_next)
    up_tr = ~st.up & eff_next
    if code_nbr is None:
        # both neighbour views cross the edges ONCE, as the 2-bit code: a
        # plane constant along K comes out of the edge involution as its
        # neighbour view (x[n,k] = v[n] => out[j,k] = v[nbr[j,k]],
        # whatever ``rev`` is), so the crossing takes what the net's
        # layout gives an edge gather (plan, rolls, flat). An absent slot
        # reads its own junk where a peer gather reads v[0]: both views
        # are under ``nbr_ok``. The plane is two words wide (the code
        # twice): XLA drops a unit word axis and gathers element by
        # element, which a v5e charges 6.6 ns a row where a row gather of
        # two words costs 5.2 (PERF.md §6)
        code_nbr = net.edge_gather(jnp.broadcast_to(
            code[:, None, None], net.nbr.shape + (2,)))[..., 0]
    down_nbr = ((code_nbr & 1) != 0) & net.nbr_ok
    # every edge touching a down peer dies (both directions; a
    # restarting node comes back with fresh soft state)
    down_edge = (down_nbr | down_tr[:, None]) & net.nbr_ok
    de3 = down_edge[:, None, :]
    score0 = st.score
    if cfg.score_enabled:
        # removePeer (score.go:604-637): first convert any standing
        # P3 deficit on mesh edges of the departing peer into the
        # one-shot sticky P3b penalty, then drop in-mesh status on
        # every dead edge; only then delete stats — except retained
        # (negative-score) neighbors, whose counters keep decaying
        score0 = on_prune(score0, st.mesh & down_nbr[:, None, :], tp)
        score0 = clear_mesh_status(score0, down_nbr)
        clear_mask = (down_nbr & (st.scores >= 0)) | down_tr[:, None]
        score0 = clear_edges(score0, clear_mask)
        # a RETAINED neighbour's first-delivery counter is reset too
        # (removePeer zeroes firstMessageDeliveries where it keeps the
        # stats, so a returning peer cannot cash in old credit)
        score0 = score0.replace(
            fmd=jnp.where(down_nbr[:, None, :], 0.0, score0.fmd))
    # a crashing node loses all soft state: seen-cache, forward set,
    # receipt history (it will re-receive after restart), mcache
    dlv0 = st.core.dlv.replace(
        have=jnp.where(down_tr[:, None], jnp.uint32(0), st.core.dlv.have),
        fwd=jnp.where(down_tr[:, None], jnp.uint32(0), st.core.dlv.fwd),
        first_round=jnp.where(down_tr[:, None], -1, st.core.dlv.first_round),
        fe_words=jnp.where(
            down_tr[:, None, None], jnp.uint32(0), st.core.dlv.fe_words
        ),
        pending=jnp.where(
            down_tr[:, None, None], jnp.uint32(0), st.core.dlv.pending
        ) if st.core.dlv.pending is not None else None,
    )
    ev0 = st.core.events
    if cfg.count_events:
        ev0 = (
            ev0
            .at[EV.REMOVE_PEER].add(jnp.sum(down_tr.astype(jnp.int32)))
            .at[EV.ADD_PEER].add(jnp.sum(up_tr.astype(jnp.int32)))
        )
    # router plane cleanup (cfg.router builds): a crashing announcer
    # forgets its IDONTWANT set with the rest of its soft state; choke
    # state and in-flight delayed commits die with their edges (the
    # guard re-establishes choked ⊆ mesh and the Dlo floor against the
    # post-churn mesh — a death that took an unchoked link fails open)
    router_clear = {}
    if st.dontwant is not None:
        router_clear["dontwant"] = jnp.where(
            down_tr[:, None], jnp.uint32(0), st.dontwant)
    if st.choked is not None:
        router_clear["choked"] = choke_guard(
            cfg.Dlo, st.mesh & ~de3, st.choked & ~de3)
        router_clear["choke_ema"] = jnp.where(down_edge, 0.0, st.choke_ema)
    if st.inflight is not None:
        router_clear["inflight"] = jnp.where(
            down_edge[:, :, None, None], jnp.uint32(0), st.inflight)
    st = st.replace(
        core=st.core.replace(dlv=dlv0, events=ev0),
        mcache=jnp.where(down_tr[:, None, None], jnp.uint32(0), st.mcache),
        mesh=st.mesh & ~de3,
        fanout_peers=st.fanout_peers & ~de3,
        **router_clear,
        graft_out=st.graft_out & ~de3,
        prune_out=st.prune_out & ~de3,
        ihave_out=jnp.where(down_edge[:, :, None], jnp.uint32(0), st.ihave_out),
        iwant_out=jnp.where(down_edge[:, :, None], jnp.uint32(0), st.iwant_out),
        served_lo=jnp.where(down_edge[:, :, None], jnp.uint32(0), st.served_lo),
        served_hi=jnp.where(down_edge[:, :, None], jnp.uint32(0), st.served_hi),
        peerhave=jnp.where(down_edge, 0, st.peerhave),
        iasked=jnp.where(down_edge, 0, st.iasked),
        promise_mid=jnp.where(down_edge, -1, st.promise_mid),
        # the backoff a crashing node holds of OTHERS goes with its router
        # (a restarted process has an empty gs.backoff); its neighbours'
        # entries for it stay: the map is keyed by peer id and RemovePeer
        # leaves it to the lazy clear (gossipsub.go:545-562, 1596 ff.)
        backoff_present=jnp.where(
            down_tr[:, None, None], False, st.backoff_present),
        backoff_expire=jnp.where(
            down_tr[:, None, None], 0, st.backoff_expire),
        score=score0,
        up=eff_next,
    )
    live = net.nbr_ok & st.up[:, None] & ((code_nbr & 2) != 0)
    return st, live


def clear_mutated_edges(cfg: GossipSubConfig, st: GossipSubState,
                        wr_edge: jax.Array, tp: dict) -> GossipSubState:
    """Dead-edge cleanup for mutated slots (dynamic_topo builds): a
    written slot names a NEW connection — whatever edge occupied it
    before (possibly nothing) is gone, so every per-edge soft-state
    plane clears exactly the way `apply_peer_transitions` clears the
    edges of a departing peer: score retention converts standing mesh
    deficits into the sticky P3b penalty before the stats drop, and the
    control outboxes / promise / gossip counters reset.

    Two deliberate differences from peer departure. Backoff ALSO clears
    here: the reference's backoff map is keyed by peer id, and a rewired
    slot is a different peer — keeping the old slot's backoff would
    wrongly embargo the new connection (while the genuinely-backed-off
    old peer, if re-attached later, re-earns backoff on its next PRUNE).
    And per-peer planes (seen-cache, mcache, forward set) do NOT clear:
    both endpoints stay up across a rewire — only the edge died.

    ``wr_edge`` is the [N, K] written-slot mask from
    `topo.dynamics.written_edge_mask` (padding rows excluded)."""
    we3 = wr_edge[:, None, :]
    score0 = st.score
    if cfg.score_enabled:
        score0 = on_prune(score0, st.mesh & we3, tp)
        score0 = clear_mesh_status(score0, wr_edge)
        score0 = clear_edges(score0, wr_edge)
    # first-arrival attribution credits the OLD far end of the slot;
    # the new edge starts with a clean delivery record
    dlv0 = st.core.dlv.replace(
        fe_words=jnp.where(
            wr_edge[:, :, None], jnp.uint32(0), st.core.dlv.fe_words
        ),
    )
    return st.replace(
        core=st.core.replace(dlv=dlv0),
        mesh=st.mesh & ~we3,
        fanout_peers=st.fanout_peers & ~we3,
        graft_out=st.graft_out & ~we3,
        prune_out=st.prune_out & ~we3,
        ihave_out=jnp.where(wr_edge[:, :, None], jnp.uint32(0), st.ihave_out),
        iwant_out=jnp.where(wr_edge[:, :, None], jnp.uint32(0), st.iwant_out),
        served_lo=jnp.where(wr_edge[:, :, None], jnp.uint32(0), st.served_lo),
        served_hi=jnp.where(wr_edge[:, :, None], jnp.uint32(0), st.served_hi),
        peerhave=jnp.where(wr_edge, 0, st.peerhave).astype(st.peerhave.dtype),
        iasked=jnp.where(wr_edge, 0, st.iasked).astype(st.iasked.dtype),
        promise_mid=jnp.where(wr_edge, -1, st.promise_mid),
        backoff_present=jnp.where(we3, False, st.backoff_present),
        backoff_expire=jnp.where(we3, 0, st.backoff_expire),
        congested_in=st.congested_in & ~wr_edge,
        score=score0,
    )


def live_step_views(cfg: GossipSubConfig, net: Net, st: GossipSubState,
                    live: jax.Array | None, consts: StepConsts):
    """Apply the churn/PX edge-liveness mask to the static topology views.
    Returns (net_l, nbr_sub_l, flood_from_l, nbr_sub_words_l)."""
    if cfg.do_px or cfg.edge_liveness:
        # edge-liveness plane: dormant edges carry nothing until
        # activated (edge_live kept symmetric, so one side suffices) —
        # by PX (pxConnect) or by a runtime connect() activation
        live = (net.nbr_ok if live is None else live) & st.edge_live
    if live is not None:
        net_l = net.replace(nbr_ok=live)
        nbr_sub_l = consts.nbr_sub_const & live[:, None, :]
        flood_from_l = consts.flood_from & live
        nbr_sub_words_l = jnp.where(
            live[:, :, None], consts.nbr_sub_words, jnp.uint32(0)
        )
    else:
        net_l = net
        nbr_sub_l = consts.nbr_sub_const
        flood_from_l = consts.flood_from
        nbr_sub_words_l = consts.nbr_sub_words
    return net_l, nbr_sub_l, flood_from_l, nbr_sub_words_l


def accept_gates(cfg: GossipSubConfig, net_l: Net, st: GossipSubState,
                 gater_params, key, tick, thr=None):
    """AcceptFrom gate (gossipsub.go:583-594): direct always accepted;
    graylisted dropped entirely; the gater's RED decision drops only
    the message plane (AcceptControl, peer_gater.go:362).
    Returns (acc_ok, acc_msg) [N,K] bool. ``thr`` is the threshold
    source (cfg, or a lifted build's traced ScoreParams plane)."""
    thr = cfg if thr is None else thr
    if cfg.score_enabled:
        acc_ok = (st.scores >= thr.graylist_threshold) | net_l.direct
    else:
        acc_ok = net_l.nbr_ok
    if cfg.gater_enabled:
        # per-subsystem streams: double fold with a distinct tag so no
        # round's stream collides with another subsystem's at any tick
        # (heartbeat consumes fold_in(key, tick) directly)
        gkey = jax.random.fold_in(jax.random.fold_in(key, tick), 0x6A7E)
        acc_msg = acc_ok & (
            gater_accept(st.gater, net_l, gater_params, cfg.gater_quiet_rounds,
                         tick, gkey)
            | net_l.direct
        )
    else:
        acc_msg = acc_ok
    return acc_ok, acc_msg


def control_parts(cfg: GossipSubConfig, net: Net, st: GossipSubState):
    """The control-plane outboxes as named packed word tensors — the wire
    format both engines' exchanges transmit (``control_exchange`` here, the
    phase engine's stacked wire), kept single-source so they cannot
    drift."""
    named_parts = [
        ("graft", edges.topic_pack(st.graft_out, net.my_topics, net.n_topics)),
        ("prune", edges.topic_pack(st.prune_out, net.my_topics, net.n_topics)),
        ("ihave", st.ihave_out),
    ]
    if cfg.do_px:
        named_parts.append(
            ("px", edges.topic_pack(st.prune_px_out, net.my_topics, net.n_topics))
        )
    if cfg.score_enabled:
        named_parts.append(
            ("score",
             jax.lax.bitcast_convert_type(st.scores, jnp.uint32)[..., None])
        )
    return named_parts


def control_unpack(cfg: GossipSubConfig, net: Net, net_l: Net, w_seg):
    """Receiver-side split of the gathered control words (w_seg(i) = the
    i-th part's edge view, ordered as control_parts lists them)."""
    ok_slots = net_l.nbr_ok[:, None, :]
    graft_in_raw = edges.topic_unpack(w_seg(0), net.my_topics) & ok_slots
    prune_in_raw = edges.topic_unpack(w_seg(1), net.my_topics) & ok_slots
    ihave_in_raw = w_seg(2)
    px_in_raw = (
        edges.topic_unpack(w_seg(3), net.my_topics) & ok_slots
        if cfg.do_px else None
    )
    return graft_in_raw, prune_in_raw, ihave_in_raw, px_in_raw


def control_exchange(cfg: GossipSubConfig, net: Net, net_l: Net,
                     st: GossipSubState):
    """Merged control-plane wire exchange: every per-edge outbox
    crosses the edge involution in as few gathers as the measured
    gather-merge policy allows — the vectorized analogue of the reference
    piggybacking all control into one RPC (gossipsub.go:1096-1141 sendRPC +
    piggyback). Returns (graft_in_raw, prune_in_raw, ihave_in_raw,
    px_in_raw, nbr_score_of_me)."""
    named_parts = control_parts(cfg, net, st)
    parts = [p for _, p in named_parts]
    # Gather-merge policy (measured on the real chip, round 3).
    # Each gathered tensor = one set of rolled halo permutes on
    # the sharded mesh (test_collectives pins the total), so fewer
    # gathers is better — UNLESS merging parts whose consumers
    # want different layouts, which re-creates the monolithic
    # relayout copy (1.2 ms/round when the f32-bitcast score
    # column rode along in round 2; eth2 210 -> 168 when ihave
    # merged with the 2-word topic parts). Measured policy: at
    # wt == 1 ALL control words share one gather ([N,K,4] merged,
    # 408 vs 400 ticks/s); at wt > 1 only the topic_unpack
    # consumers (graft/prune/px) merge and ihave rides alone; the
    # score plane ALWAYS rides alone. Grouping is by part name so
    # the policy cannot drift from the parts list above.
    ctrl_names = [nm for nm, _ in named_parts if nm != "score"]
    wt_t = parts[0].shape[-1]
    if wt_t == 1:
        groups = [list(range(len(ctrl_names)))]
    else:
        topicish = [
            i for i, nm in enumerate(ctrl_names) if nm != "ihave"
        ]
        groups = [topicish, [ctrl_names.index("ihave")]]
    gathered = [None] * len(ctrl_names)
    for grp in groups:
        g = (
            jnp.concatenate([parts[i] for i in grp], axis=-1)
            if len(grp) > 1 else parts[grp[0]]
        )
        gg = jnp.where(
            net_l.nbr_ok[:, :, None], net_l.edge_gather(g), jnp.uint32(0)
        )
        off = 0
        for i in grp:
            pw = parts[i].shape[-1]
            gathered[i] = gg[..., off : off + pw]
            off += pw
    if cfg.score_enabled:
        # the score plane always rides alone: its f32-bitcast
        # consumer's layout caused the round-2 relayout copy
        score_g = jnp.where(
            net_l.nbr_ok[:, :, None],
            net_l.edge_gather(dict(named_parts)["score"]),
            jnp.uint32(0),
        )
        nbr_score_of_me = jnp.where(
            net_l.nbr_ok,
            jax.lax.bitcast_convert_type(score_g[..., 0], jnp.float32),
            0.0,
        )
    else:
        nbr_score_of_me = None
    return (*control_unpack(cfg, net, net_l, lambda i: gathered[i]),
            nbr_score_of_me)


class ControlWire(NamedTuple):
    """The phase control head's stacked wire: the sender side's parts by
    name, in plane order, and — once ``gather_wire`` ran — their stacked
    plane across the edges, unmasked."""
    names: list
    parts: list
    raw: jax.Array | None = None


def control_wire(cfg: GossipSubConfig, net: Net, st: GossipSubState,
                 include_app: bool = False,
                 code: jax.Array | None = None) -> ControlWire:
    """The sender side of ONE stacked wire exchange for the whole phase
    control head (round-7 tentpole): every control outbox, the score plane,
    the IWANT-service mcache window — and, when ``include_app``, the P5
    app-score plane the heartbeat tail consumes; with ``code``, the ``[N]``
    liveness code of a dynamic build (``liveness_code``), last. They cross
    the edge involution in a single gather, so the sharded lowering emits
    ONE halo-permute set for the entire control head instead of
    three-plus-one (16·(r+4) → 16·(r+1) permutes per phase;
    perf/projection.py charges 1–5 µs launch latency per permute).

    The [N]-shaped planes (mcache window, app score, code) broadcast over
    the edge axis before the concat, turning their peer gather into the
    same edge involution (x[n,k] = v[n] ⇒ gathered[j,k] = v[nbr[j,k]]) —
    byte-wasteful per direction but launch-free, the right trade in the
    launch-dominated halo regime the projection models."""
    named_parts = control_parts(cfg, net, st)
    names = [nm for nm, _ in named_parts]
    parts = [p for _, p in named_parts]
    # control parts lead the concat in control_parts order (score is
    # always appended last by control_parts), so the plain index view
    # feeds control_unpack directly
    n_ctrl = len([nm for nm in names if nm != "score"])
    assert "score" not in names[:n_ctrl]
    n_peers, k_dim = net.nbr.shape
    sender_window = bitset.word_or_reduce(st.mcache, axis=1)       # [N,W]
    w = sender_window.shape[-1]
    names.append("window")
    parts.append(jnp.broadcast_to(
        sender_window[:, None, :], (n_peers, k_dim, w)))
    if include_app:
        names.append("app")
        parts.append(jnp.broadcast_to(
            jax.lax.bitcast_convert_type(st.app_score, jnp.uint32)[:, None, None],
            (n_peers, k_dim, 1)))
    if code is not None:
        names.append("code")
        parts.append(jnp.broadcast_to(code[:, None, None], (n_peers, k_dim, 1)))
    return ControlWire(names, parts)


def gather_wire(net: Net, wire: ControlWire) -> ControlWire:
    """The stacked plane across the edges: the static net's gather,
    UNMASKED (``Net.edge_gather`` reads no ``nbr_ok``), so it may run
    before a head's peer transitions have said which edges live."""
    return wire._replace(
        raw=net.edge_gather(jnp.concatenate(wire.parts, axis=-1)))


def control_exchange_coalesced(cfg: GossipSubConfig, net: Net, net_l: Net,
                               wire: ControlWire):
    """The receiver side of ``control_wire``: the stacked plane across the
    edges (gathered here unless ``wire`` holds it already) under ``net_l``'s
    edge mask — the live edges, and under chaos the head round's links —
    split into what the control head consumes.

    The round-3 measured merge policy (control_exchange above) deliberately
    kept the score column and the ihave words on separate gathers: their
    consumers' layouts forced a relayout copy per ROUND on the real chip.
    The phase engine pays the control head once per PHASE, so a once-per-
    phase relayout buys r rounds of avoided launches — the opposite
    tradeoff; the per-round step keeps the round-3 policy, and the legacy
    phase path stays selectable (cfg.wire_coalesced=False) for A/B.

    Returns (graft_in_raw, prune_in_raw, ihave_in_raw, px_in_raw,
    nbr_score_of_me, window_g, app_g)."""
    names = wire.names
    sizes = np.cumsum([0] + [p.shape[-1] for p in wire.parts])
    # ``ok`` is traced before the gather: a build without the code keeps
    # the order of its lowered text, which PERF.md §6 pins by sha256
    ok = net_l.nbr_ok[:, :, None]
    raw = gather_wire(net, wire).raw if wire.raw is None else wire.raw
    gg = jnp.where(ok, raw, jnp.uint32(0))

    def seg(i):
        return gg[..., int(sizes[i]) : int(sizes[i + 1])]

    def seg_named(nm):
        return seg(names.index(nm))

    graft_in_raw, prune_in_raw, ihave_in_raw, px_in_raw = control_unpack(
        cfg, net, net_l, seg
    )
    if cfg.score_enabled:
        nbr_score_of_me = jnp.where(
            net_l.nbr_ok,
            jax.lax.bitcast_convert_type(seg_named("score")[..., 0], jnp.float32),
            0.0,
        )
    else:
        nbr_score_of_me = None
    window_g = seg_named("window")
    app_g = (
        jax.lax.bitcast_convert_type(seg_named("app")[..., 0], jnp.float32)
        if "app" in names else None
    )
    return (graft_in_raw, prune_in_raw, ihave_in_raw, px_in_raw,
            nbr_score_of_me, window_g, app_g)


def transitions_on_wire(cfg: GossipSubConfig, net: Net, st: GossipSubState,
                        up_next: jax.Array, tp: dict,
                        include_app: bool = False):
    """A dynamic build's phase head with a coalesced wire exchange: the
    liveness code rides the stacked wire as its last word, so the peer
    transitions make no gather of their own. The wire is built and
    gathered from the state BEFORE the transitions, which is exact: every
    outbox they clear (graft, prune and IHAVE on a dead edge, the mcache
    window of a peer that goes down) lies on an edge with a down end, which
    the receiver's ``live`` mask in ``control_exchange_coalesced`` drops.
    The code is read off the gather before any mask: liveness is no wire
    message, and a chaos link outage must not drop it.
    Returns ``(st, live, wire)``, the wire gathered."""
    with stages.part("churn"):
        code = liveness_code(st, up_next)[2]
    wire = gather_wire(net, control_wire(cfg, net, st, include_app, code))
    with stages.part("churn"):
        st, live = apply_peer_transitions(cfg, net, st, up_next, tp,
                                          code_nbr=wire.raw[..., -1])
    return st, live, wire


def px_connect(cfg: GossipSubConfig, net: Net, net_l: Net, st: GossipSubState,
               px_ok, live: jax.Array | None) -> jax.Array:
    """PX connect (pxConnect gossipsub.go:861-941): a peer pruned with PX
    activates its dormant provisioned edges to peers the pruner suggested —
    the pruner's current mesh members for the topic (makePrune/getPeers
    :1814-1872; here the union over the pruner's topics, one-round-stale by
    the outbox model). The id match runs per prune-edge over the small K
    axis. `net_l` is the live view (suggestions ride live edges); `net` the
    static topology (dormant slots live there); `live` the peer-liveness
    plane `apply_peer_transitions` returned (both ends up; None in a static
    build), before `edge_live` masks it. Returns next edge_live."""
    if not cfg.do_px:
        return st.edge_live
    sugg_ids = jnp.where(
        jnp.any(st.mesh, axis=1) & net_l.nbr_ok, net_l.nbr, -1
    )  # [N,C] each peer's suggestion list
    sugg_g = net.peer_gather(sugg_ids)  # [N,K,C] per-edge pruner rows
    dormant_avail = net.nbr_ok & ~st.edge_live & (net.nbr >= 0)
    if live is not None:
        dormant_avail = dormant_avail & live
    act = jnp.zeros_like(dormant_avail)
    for kk in range(net.max_degree):
        hit = jnp.any(
            net.nbr[:, :, None] == sugg_g[:, kk, :][:, None, :], axis=-1
        )  # [N,K']: my dormant-slot peer is among pruner kk's suggestions
        act = act | (hit & px_ok[:, kk : kk + 1])
    act = act & dormant_avail
    act_sym = (act | net.edge_gather(act)) & net.nbr_ok
    return st.edge_live | act_sym


def make_gossipsub_step(
    cfg: GossipSubConfig,
    net: Net,
    score_params: PeerScoreParams | None = None,
    heartbeat_interval: float = 1.0,
    gater_params=None,
    dynamic_peers: bool = False,
    adversary_no_forward: np.ndarray | None = None,
    static_heartbeat: bool = False,
    sub_knowledge_holes: np.ndarray | None = None,
    telemetry=None,
    adversary=None,
    lift_scores: bool = False,
    dynamic_topo: bool = False,
    link_delay: np.ndarray | None = None,
):
    """Build the jitted per-round step for a fixed config + topology.

    ``link_delay`` is the router plane's static [N, K] i32 per-edge
    delay in rounds (docs/DESIGN.md §24c — ``topo.link_class_planes``
    normalized so the fastest class is 0, ``topo.link_delay_plane``),
    REQUIRED iff ``cfg.router.latency_rounds > 0``; values must lie in
    [0, latency_rounds]. It is a jit constant like the topology — the
    latency classes are as static as the graph they annotate.

    step(state, pub_origin[P], pub_topic[P], pub_valid[P]) -> state

    With ``lift_scores=True`` (round 16, docs/DESIGN.md §16) the step
    takes a trailing TRACED ``score_plane`` argument (a
    ``score.params.ScoreParams`` pytree): every score weight, decay
    factor and v1.1 threshold the liftability audit proves VALUE-only
    (LIFT_AUDIT.json) is read from the plane instead of the baked
    statics, so two calls with different weight sets share ONE
    compiled program (the recompile-free A/B sentinel) and a vmapped
    plane axis sweeps weight populations. Matched values reproduce the
    static build bit for bit (tests/test_score_lift.py). Requires
    ``cfg.score_enabled``.

    With ``static_heartbeat=True`` (and ``cfg.heartbeat_every > 1``) the
    step takes a trailing *static* python bool ``do_heartbeat`` instead of
    deciding via ``tick % heartbeat_every`` on device. A driver that knows
    the cadence at trace time (any fixed-schedule scan does) should use
    this: the ``lax.cond`` form carries every state array through both
    branches, and the branch-materialization copies measured 407 -> 113
    ticks/s at heartbeat_every=2 on the bench (BASELINE.md round 3). The
    caller owns the contract do_heartbeat == (tick % heartbeat_every == 0).

    ``pub_valid`` is either bool (True = accept, False = reject) or an
    integer array of state.VERDICT_* codes — ACCEPT / REJECT / IGNORE
    with the reference's ValidationResult numbering (validation.go:40-52).
    Ignored messages are dropped without the P4 penalty and trace
    REJECT with reason "validation ignored" (score.go:768-774).

    With ``dynamic_peers=True`` the step takes an extra ``up_next [N] bool``
    argument (the notify plane, notify.go:19-75): peers transitioning down
    — or blacklisted via ``state.blacklist`` — are disconnected with full
    dead-peer cleanup (handleDeadPeers pubsub.go:648-689 + router
    RemovePeer gossipsub.go:545-562 + score retention score.go:604-689),
    and every edge touching a down peer carries nothing until it returns.

    ``adversary_no_forward`` is a static [N] bool behavior vector (survey
    §7 stage 6): marked peers run the full control plane — subscribe,
    GRAFT/PRUNE, IHAVE gossip — but never transmit message data (mesh
    push, flood-publish, fanout, IWANT service). This is the vectorized
    analogue of the reference test suite's ``sybilSquatter`` attacker
    (gossipsub_test.go:1777-1811): grafted-but-silent peers that starve
    their mesh neighbors, to be caught by the P3 mesh-delivery deficit and
    IWANT-promise (P7) machinery.

    ``telemetry`` (a telemetry.TelemetryConfig) appends the time-series
    recorder as the step's LAST operation: one ``[N_METRICS]`` f32 panel
    row per round — EV-counter deltas covering everything this round
    accumulated (delivery, control, churn, heartbeat), delivery ratio,
    mesh/score stats — written into ``state.core.telem`` on device
    (docs/DESIGN.md §11). The state must be built with the same config
    (``GossipSubState.init(telemetry=...)``). None (the default) elides
    the plane statically: the traced program and the state tree are the
    pre-telemetry ones, bit for bit.

    ``adversary`` (a chaos.adversary.Adversary) arms the vectorized
    attack suite (docs/DESIGN.md §13): per-peer sybil/behavior masks
    drive drop-on-forward, lie-in-IHAVE, graft-spam, self-promotion
    and censorship as masked variants of this step's own math, with
    per-peer onset/stop schedules compared against the tick on device
    (stateless — checkpoints resume the exact attack sequence). None
    (or an all-off population) elides the plane statically: the traced
    program is the pre-adversary one, bit for bit
    (tests/test_adversary.py).

    With ``dynamic_topo=True`` (round 22, docs/DESIGN.md §22) the step
    takes an extra REQUIRED ``mut_writes [B, 4] i32`` trailing positional
    (after ``up_next`` and the scheduled-chaos ``link_deny`` when
    present, before the lifted ``score_plane``): a padded batch of edge
    writes ``(slot, peer, rev, ok)`` from a host-compiled
    `topo.MutationSchedule` — applied device-side to the state-resident
    `TopoState` overlay at round entry (join / death-replacement /
    rewire with zero recompiles across a window; padding rows carry
    ``topo.dynamics.PAD_SLOT`` and drop). Requires ``dynamic_peers=True``
    (death/replacement rides the up plane), a net built with
    ``Net.build(..., dynamic=True)``, and none of the planes that bake
    neighbor identity into jit constants (adversary, announce holes,
    PX / edge-liveness, banded rolls, the fused composites). No schedule — i.e.
    ``dynamic_topo=False``, the default — elides the plane statically:
    the traced program, kernel census and state tree are the pre-dynamics
    ones, bit for bit (tests/test_dynamics.py).
    """
    if lift_scores and not cfg.score_enabled:
        raise ValueError(
            "lift_scores=True needs cfg.score_enabled — the lifted "
            "plane parameterizes the v1.1 score machinery"
        )
    if dynamic_topo:
        # every rejected combination below bakes neighbor identity (or
        # the banded edge geometry) into an eager jit constant that a
        # device-side mutation could not update without a recompile —
        # exactly what dynamic_topo exists to avoid
        if not dynamic_peers:
            raise ValueError(
                "dynamic_topo=True requires dynamic_peers=True — node "
                "death/replacement rides the up_next plane"
            )
        if net.band_off is not None or net.fused or cfg.fused:
            raise ValueError(
                "dynamic_topo=True needs an unbanded net "
                "(Net.build(..., dynamic=True)) — the banded rolls and the "
                "fused composites bake the edge geometry at trace time"
            )
        if net.edge_layout == "csr" and (
            not net.csr_identity
            or net.n_edges != net.n_peers * net.max_degree
        ):
            raise ValueError(
                "dynamic_topo=True on CSR needs the full-capacity "
                "identity plane (Net.build(..., edge_layout='csr', "
                "dynamic=True)) — a degree-compacted CSR cannot gain "
                "edges without a rebuild"
            )
        if adversary is not None or adversary_no_forward is not None:
            raise ValueError(
                "dynamic_topo=True is incompatible with the adversary "
                "planes — their behavior masks and neighbor views are "
                "eager jit constants over the static topology"
            )
        if sub_knowledge_holes is not None:
            raise ValueError(
                "dynamic_topo=True is incompatible with "
                "sub_knowledge_holes — the announce-hole mask is indexed "
                "by static (receiver, slot) edge identity"
            )
        if cfg.do_px or cfg.edge_liveness:
            raise ValueError(
                "dynamic_topo=True is incompatible with do_px/"
                "edge_liveness — the edge_live plane binds activation to "
                "static slot identity; topology changes go through the "
                "mutation schedule instead"
            )
    router = cfg.router
    if router is not None:
        router.validate()
        if dynamic_topo:
            raise ValueError(
                "cfg.router is incompatible with dynamic_topo — the "
                "link_delay plane and the choke guard's edge views are "
                "static over the build topology; mutate topology on a "
                "v1.1 build or rebuild the router step"
            )
    if router is not None and router.latency_rounds > 0:
        if link_delay is None:
            raise ValueError(
                "cfg.router.latency_rounds > 0 needs the static link_delay "
                "plane (make_gossipsub_step(..., link_delay=...) — see "
                "topo.link_delay_plane)"
            )
        link_delay = np.asarray(link_delay, np.int32)
        if link_delay.shape != net.nbr.shape:
            raise ValueError(
                f"link_delay shape {link_delay.shape} does not match the "
                f"topology's [N, K] = {net.nbr.shape}"
            )
        if link_delay.min() < 0 or link_delay.max() > router.latency_rounds:
            raise ValueError(
                "link_delay values must lie in [0, "
                f"{router.latency_rounds}] (the ring depth); got "
                f"[{link_delay.min()}, {link_delay.max()}]"
            )
        link_delay_c = jnp.asarray(link_delay)
    else:
        if link_delay is not None:
            raise ValueError(
                "link_delay given but cfg.router.latency_rounds == 0 — "
                "the delay plane would be silently unread"
            )
        link_delay_c = None
    consts = prepare_step_consts(
        cfg, net, score_params, heartbeat_interval, gater_params,
        sub_knowledge_holes, adversary_no_forward, adversary,
    )
    score_params = consts.score_params
    tp = consts.tp
    window_rounds_t = consts.window_rounds_t
    nbr_sub_const = consts.nbr_sub_const
    flood_from = consts.flood_from
    i_am_floodsub = consts.i_am_floodsub
    nbr_sub_words = consts.nbr_sub_words
    sender_fwd_ok = consts.sender_fwd_ok

    # chaos plane (chaos/faults.py): None elides it statically — every
    # chaos branch below disappears from the trace and the program is
    # the pre-chaos one, bit for bit (tests/test_chaos.py)
    chaos = chaos_faults.resolve(cfg.chaos)
    chaos_sched = chaos is not None and chaos.scheduled
    adv = consts.adv

    if dynamic_topo:
        # lazy import: the static build's module graph (and trace) stays
        # byte-identical to the pre-dynamics one
        from ..topo import dynamics as topo_dynamics

    # `net=net, consts=consts` are default-bound parameters, NOT closure
    # reads: the dynamic_topo block below rebinds them to the mutated
    # overlay, and a closure variable assigned anywhere in the body
    # would be local EVERYWHERE in it (UnboundLocalError on the static
    # path). Callers never pass them.
    def _round(st: GossipSubState, pub_origin, pub_topic, pub_valid, up_next,
               do_heartbeat: bool = True,
               link_deny=None, score_plane=None, mut_writes=None,
               *, net=net, consts=consts) -> GossipSubState:
        # lifted score plane (round 16): the VALUE-proved score fields
        # read from the traced plane — per-topic rows gathered to the
        # same [N, S] views TopicParamsArrays.gather bakes, thresholds
        # and scalar params from the plane's leaves. score_plane=None
        # is the static path, byte-identical to the pre-lift program
        # (thr=cfg routes every threshold read to the same Python
        # floats it always read).
        # a combined candidate plane (round 20, score.params.
        # CandidateParams) nests the score plane with a traced MeshParams
        # — detect it by its `mesh` attribute; a bare ScoreParams keeps
        # the score-only semantics unchanged
        mesh_plane = getattr(score_plane, "mesh", None)
        if score_plane is not None:
            sc = score_plane.score if mesh_plane is not None else score_plane
            tp_r = sc.gather(net.my_topics)
            sp_r, thr, wrt = sc, sc, sc.window_rounds
        else:
            tp_r, sp_r, thr, wrt = tp, score_params, cfg, window_rounds_t
        msh = cfg if mesh_plane is None else mesh_plane
        # ---- dynamic overlay mutation (dynamic_topo builds) -------------
        # the round's write batch lands FIRST: the whole step — peer
        # transitions, control exchange, delivery, heartbeat — runs on
        # the post-mutation topology, so a round that rewires an edge and
        # a round that merely uses it trace the same program (recompile-
        # free by construction: writes are a traced [B, 4] operand)
        if dynamic_topo:
            topo1 = topo_dynamics.apply_mutation(st.core.topo, mut_writes)
            wr_edge = topo_dynamics.written_edge_mask(
                mut_writes, net.n_peers, net.max_degree
            )
            net = net.with_overlay(topo1)
            nsc, ffr, nsw = topology_views(net)
            consts = StepConsts(
                score_params=consts.score_params, tp=consts.tp,
                tpa=consts.tpa, window_rounds_t=consts.window_rounds_t,
                nbr_sub_const=nsc, flood_from=ffr,
                i_am_floodsub=consts.i_am_floodsub, nbr_sub_words=nsw,
                sender_fwd_ok=consts.sender_fwd_ok, adv=consts.adv,
            )
            st = clear_mutated_edges(cfg, st, wr_edge, tp_r)
            st = st.replace(core=st.core.replace(topo=topo1))
        else:
            topo1 = None
        # telemetry: counters at step ENTRY (before the churn plane's
        # ADD/REMOVE_PEER accounting), so the row's EV deltas cover the
        # whole step and the panel sums telescope to the drained totals
        ev_prev = st.core.events if telemetry is not None else None
        # ---- peer lifecycle transitions (dynamic_peers only) ------------
        if dynamic_peers:
            # the churn part (perf/stages.py): the transitions, the traced
            # liveness views, and the publish gate: a publish whose origin
            # is down does not happen (its slot is allocated, nobody holds
            # it: state.allocate_publishes), and the fanout update and the
            # publish count read it as padding
            with stages.part("churn"):
                st, live = apply_peer_transitions(cfg, net, st, up_next, tp_r)
                net_l, nbr_sub_l, flood_from_l, nbr_sub_words_l = (
                    live_step_views(cfg, net, st, live, consts))
                pub_holder = jnp.where(
                    st.up[jnp.clip(pub_origin, 0)], pub_origin, -1)
        else:
            live = None
            net_l, nbr_sub_l, flood_from_l, nbr_sub_words_l = live_step_views(
                cfg, net, st, None, consts
            )
            pub_holder = None
        pub_origin_held = pub_origin if pub_holder is None else pub_holder

        core = st.core
        tick = core.tick
        m = core.msgs.capacity

        acc_ok, acc_msg = accept_gates(cfg, net_l, st, gater_params,
                                       core.key, tick, thr=thr)

        # ---- chaos plane: this round's link outages ---------------------
        # TCP-flap semantics — the WHOLE link (control head + data, both
        # directions) drops for the round, with no endpoint state cleanup
        # (the peers don't learn the link flapped; outboxes written into
        # the outage are genuinely lost, which is exactly the loss the
        # IHAVE/IWANT machinery exists to recover). net_w is the wire
        # view: the one-round-masked net_l every receiver gather uses.
        if chaos is not None:
            ge_bad0 = core.chaos.ge_bad if core.chaos is not None else None
            link_ok, ge_bad_next = chaos_faults.round_link_ok(
                chaos, chaos_faults.chaos_seed(core.key), net.nbr, tick,
                ge_bad0, link_deny, topo=topo1,
            )
            net_w = net_l.replace(nbr_ok=net_l.nbr_ok & link_ok)
            # data-plane gate: acc_msg feeds gossip_edge_mask and the
            # IWANT-response mask — one AND covers every data transmit
            acc_msg = acc_msg & link_ok
        else:
            link_ok = ge_bad_next = None
            net_w = net_l

        # 0b. merged wire exchange: every per-edge outbox crosses the edge
        # involution in ONE gather. Separate gathers each pay a fixed
        # dispatch cost on TPU, so the control plane ships as a single
        # concatenated word tensor (graft | prune | ihave [| px] [| score])
        # and is split receiver-side — the vectorized analogue of the
        # reference piggybacking all control into one RPC (gossipsub.go:
        # 1096-1141 sendRPC + piggyback).
        (graft_in_raw, prune_in_raw, ihave_in_raw, px_in_raw,
         nbr_score_of_me) = control_exchange(cfg, net, net_w, st)

        # 1. GRAFT/PRUNE ingest
        st2, prune_resp, px_resp, px_ok, n_graft, n_prune = handle_graft_prune(
            cfg, net_l, st, tp_r, acc_ok, graft_in_raw, prune_in_raw,
            px_in_raw, thr=thr, msh=msh,
        )
        events = st.core.events
        if cfg.count_events:
            events = events.at[EV.GRAFT].add(n_graft).at[EV.PRUNE].add(n_prune)

        # router choke guard at the GRAFT/PRUNE mutation site: the ingest
        # may have pruned an unchoked link or grafted a fresh one, and the
        # no-choke-below-Dlo invariant holds at every round boundary
        # (oracle/invariants.py), so re-establish choked ⊆ mesh here
        if router is not None and router.choke:
            st2 = st2.replace(choked=choke_guard(msh.Dlo, st2.mesh, st2.choked))

        # 1b. PX connect (see px_connect)
        edge_live_next = px_connect(cfg, net, net_l, st, px_ok, live)

        joined_words = joined_msg_words(net_l, core.msgs)
        slotw = slot_topic_words(net_l, core.msgs.topic)
        pre_have = core.dlv.have
        n_adv_drop = None
        # 2. IWANT service (requests sent to me last round -> delivery
        # carry) — the mcache-window gather rides the wire view, so a
        # flapped link's responses are lost (and its retransmission
        # counters don't tick: the data never arrived)
        st2, iwant_resp = iwant_responses(cfg, net_w, st2,
                                          nbr_score_of_me, thr=thr)

        # 3. IHAVE ingest (advertisements -> next round's requests)
        st2 = handle_ihave(cfg, net_l, st2, joined_words, acc_ok,
                           ihave_in_raw, thr=thr)

        # 4. delivery: mesh/fanout push + flood edges + IWANT responses
        # floodsub-peer edges: sender floodsub => flood; receiver floodsub
        # => gossipsub sender still sends everything (score-gated,
        # gossipsub.go:973-978)
        if cfg.score_enabled:
            recv_ok = nbr_score_of_me >= thr.publish_threshold
        else:
            recv_ok = net_l.nbr_ok
        flood_edges = flood_from_l | (i_am_floodsub[:, None] & recv_ok & net_l.nbr_ok)
        edge_mask = gossip_edge_mask(
            cfg, net_l, st2, joined_words, acc_msg, slotw,
            core.msgs.topic, flood_edges,
            nbr_score_of_me, thr=thr,
        )
        if sender_fwd_ok is not None:
            edge_mask = jnp.where(sender_fwd_ok[:, :, None], edge_mask, jnp.uint32(0))
            iwant_resp = jnp.where(sender_fwd_ok[:, :, None], iwant_resp, jnp.uint32(0))
        # adversary data plane (chaos/adversary.py): drop-on-
        # forward / censorship suppress bits on edges from ACTIVE
        # attackers — one AND into the receiver gathers the step
        # already performs, zero extra halo permutes (the behavior
        # masks and their neighbor views are eager jit constants)
        if adv is not None and adv.data_plane:
            edge_mask, rem_mask = adv.mask_transmit_nbr(
                tick, edge_mask, core.msgs)
            iwant_resp, rem_resp = adv.mask_transmit_nbr(
                tick, iwant_resp, core.msgs)
            if cfg.count_events:
                # withheld-transmission attribution: suppressed
                # carry bits ∩ the senders' forward sets (the same
                # fwd gather delivery_round performs — XLA CSE
                # merges the two); IWANT-response bits are actual
                # serves, counted whole
                fwd_g = net_l.peer_gather(core.dlv.fwd)
                n_adv_drop = (
                    bitset.popcount(rem_mask & fwd_g, axis=None).sum()
                    + bitset.popcount(rem_resp, axis=None).sum()
                ).astype(jnp.int32)
        # ---- router plane (docs/DESIGN.md §24) ----------------------
        # receiver-side data suppression: both IDONTWANT (§24a) and
        # choke (§24b) land as ANDs on edge_mask BEFORE delivery_round,
        # so the dense and the flat-[E] CSR layouts (which pack
        # edge_mask internally) are covered identically, with zero
        # extra halo permutes — the sender's view of "I was told not
        # to" is receiver-indexed, exactly like the adversary masks
        n_dup_sup = None
        ring_tx = None
        if router is not None:
            mesh_edge = jnp.any(st2.mesh, axis=1)
            suppress = jnp.zeros_like(edge_mask)
            if router.idontwant_eligible:
                suppress = suppress | dontwant_suppression(
                    st.dontwant, mesh_edge
                )
            if router.choke:
                ch_edge = choke_suppression(st2.choked)
                suppress = suppress | jnp.where(
                    ch_edge[:, :, None], jnp.uint32(0xFFFFFFFF),
                    jnp.uint32(0),
                )
            removed = edge_mask & suppress
            edge_mask = edge_mask & ~suppress
            if cfg.count_events:
                # suppressed-transmission attribution: withheld carry
                # bits ∩ the senders' forward sets — the n_adv_drop
                # convention above (same fwd gather delivery_round
                # performs; XLA CSE merges them)
                fwd_g = net_l.peer_gather(core.dlv.fwd)
                n_dup_sup = bitset.popcount(
                    removed & fwd_g, axis=None
                ).sum().astype(jnp.int32)
            if router.latency_rounds > 0:
                # §24c latency ring — store-and-forward: the sender's
                # fwd plane is a ONE-round window (this round's
                # validated cohort, models/common.py), so a commit
                # landing d rounds later would find it already empty.
                # The decision therefore resolves against the
                # sender's fwd window and the echo exclusion AT SEND
                # TIME (what's on the wire was valid when it left),
                # and the ring carries the resolved transmission
                # words; slot-0 pops commit below via merge_extra_tx,
                # the path built for transmissions outside senders'
                # current fwd sets (IWANT responses). Delay-0 edges
                # never enter the ring: they keep the v1.1
                # delivery_round path bit-for-bit.
                d0w = jnp.where(
                    (link_delay_c == 0)[:, :, None],
                    jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
                eager = (edge_mask & net_l.peer_gather(core.dlv.fwd)
                         & ~net_l.edge_gather(core.dlv.fe_words)
                         & ~d0w)
                ring_tx, inflight_next = ring_commit(
                    st.inflight, eager, link_delay_c
                )
                edge_mask = edge_mask & d0w
        dlv, info = delivery_round(
            net_l, core.msgs, core.dlv, edge_mask, tick,
            count_events=cfg.count_events, queue_cap=cfg.queue_cap,
            val_delay_topic=cfg.validation_delay_topic,
        )
        if ring_tx is not None:
            # latency-ring arrivals land this round (merged before
            # the IWANT responses so the recovery attribution below
            # stays IWANT-only)
            dlv, info = merge_extra_tx(
                net_l, core.msgs, dlv, info, ring_tx, tick,
                count_events=cfg.count_events, queue_cap=cfg.queue_cap,
                val_delay_topic=cfg.validation_delay_topic)
        iwant_resp = jnp.where(acc_msg[:, :, None], iwant_resp, jnp.uint32(0))
        have_pre_merge = dlv.have
        dlv, info = merge_extra_tx(net_l, core.msgs, dlv, info, iwant_resp, tick,
                                   count_events=cfg.count_events,
                                   queue_cap=cfg.queue_cap,
                                   val_delay_topic=cfg.validation_delay_topic)
        if chaos is not None and cfg.count_events:
            # IWANT-recovery attribution: receipts whose FIRST arrival
            # rode the IWANT service rather than an eager push (the
            # chaos metrics' recovery-efficacy numerator; valid-plane
            # membership read at arrival — under async validation the
            # verdict lands later, same arrival-cohort convention as
            # the duplicate counter)
            n_iwant_rec = bitset.popcount(
                (dlv.have & ~have_pre_merge)
                & bitset.pack(core.msgs.valid)[None, :], axis=None,
            ).sum().astype(jnp.int32)

        # exact-trace duplicate plane: arrivals beyond the first per
        # (peer, msg) — captured pre-throttle (throttled receipts are
        # fresh, traced Reject, and the dup counter excludes them) and
        # arrival-based under async validation (recv_new_words)
        if cfg.trace_exact:
            dup_plane = info.trans & ~(
                dlv.fe_words & info.recv_new_words[:, None, :]
            )
        else:
            dup_plane = None

        # router choke signal: fold this round's per-edge lateness into
        # the EMA (arrival-based, pre-throttle — the same cohort the dup
        # counter uses).
        if router is not None and router.choke:
            choke_ema_next = choke_lateness_update(
                router, st2.choke_ema, info.trans, dlv.fe_words,
                info.new_words,
            )

        # 4b. validation front-end throttle (validation.go:230-244)
        valid_words_all = bitset.pack(core.msgs.valid)
        if cfg.validation_capacity > 0:
            dlv, info, accepted_new, n_throttled = apply_validation_throttle(
                dlv, info, cfg.validation_capacity, m, valid_words_all
            )
        else:
            accepted_new = info.new_words
            n_throttled = jnp.zeros((net.n_peers,), jnp.int32)

        # 5. score delivery attribution (packed)
        score = st2.score
        if cfg.score_enabled:
            score = on_deliveries(
                score, net_l, st2.mesh, tp_r, info.trans, info.new_words,
                dlv.fe_words, dlv.first_round,
                core.msgs.topic, core.msgs.valid, tick, wrt,
                msg_ignored=core.msgs.ignored,
                slotw=slotw,
                pending_words=(
                    bitset.word_or_reduce(dlv.pending, axis=1)
                    if cfg.validation_delay_rounds > 0 else None
                ),
                recv_new_words=info.recv_new_words,
            )

        # 5b. gater outcome counters (the RawTracer hooks,
        # peer_gater.go:365-443)
        gater_state = st2.gater
        if cfg.gater_enabled:
            fe_words_post = dlv.fe_words
            # fe ⊆ arrivals, so the packed first-arrival plane restricted
            # to the validated cohort is the attribution mask directly
            first_arrival = (
                fe_words_post & accepted_new[:, None, :]
                & valid_words_all[None, None, :]
            )
            deliver_inc = bitset.popcount(first_arrival, axis=-1).astype(jnp.float32)
            dup_inc = bitset.popcount(
                info.trans & pre_have[:, None, :], axis=-1
            ).astype(jnp.float32)
            # reject vs ignore split (peer_gater.go:416-432: ignored
            # verdicts land on the `ignore` counter, not `reject`)
            ignored_words = bitset.pack(core.msgs.ignored)
            rej_inc = bitset.popcount(
                info.trans & ~valid_words_all[None, None, :]
                & ~ignored_words[None, None, :], axis=-1
            ).astype(jnp.float32)
            ign_inc = bitset.popcount(
                info.trans & ignored_words[None, None, :], axis=-1
            ).astype(jnp.float32)
            n_validated = bitset.popcount(accepted_new, axis=-1)
            gater_state = gater_on_round(
                gater_state, n_validated, n_throttled, deliver_inc, dup_inc,
                rej_inc, tick, ignore_inc=ign_inc,
            )

        # 6. mcache put: validated new receipts in joined topics
        valid_words = bitset.pack(core.msgs.valid)
        put = info.new_words & valid_words[None, :] & joined_words
        mcache = st2.mcache.at[:, 0, :].set(st2.mcache[:, 0, :] | put)

        # 7. publishes + slot-recycle cleanup
        msgs, dlv, _slots, is_pub, keep_words, pub_words = allocate_publishes(
            core.msgs, dlv, tick, pub_origin, pub_topic, pub_valid,
            stacked_clears=cfg.wire_coalesced, pub_holder=pub_holder,
        )
        # recycled-slot clearing must precede the put: the fresh publishes
        # land on exactly the recycled slots, and clearing after the OR
        # would erase them — leaving the origin without its own message in
        # mcache (it must serve IWANTs and advertise IHAVE for it from the
        # publish round on; mcache.Put in Publish, gossipsub.go:946)
        mcache = mcache & keep_words[None, None, :]
        mcache = mcache.at[:, 0, :].set(mcache[:, 0, :] | pub_words)
        # IHAVE outboxes were gathered by the far end this round (step 3);
        # clear so a batch is received exactly once per heartbeat emission
        # (the reference sends IHAVE once, at the heartbeat) — emitGossip
        # below repopulates on heartbeat rounds
        ihave_out = jnp.zeros_like(st2.ihave_out)
        if cfg.wire_coalesced:
            iwant_out, served_lo, served_hi = bitset.masked_keep(
                [st2.iwant_out, st2.served_lo, st2.served_hi], keep_words
            )
        else:
            iwant_out = st2.iwant_out & keep_words[None, None, :]
            served_lo = st2.served_lo & keep_words[None, None, :]
            served_hi = st2.served_hi & keep_words[None, None, :]
        # one-hot word pick instead of an [N,K,M] compare-reduce
        promise_reused = bitset.bit_get((~keep_words)[None, None, :], st2.promise_mid)
        promise_mid = jnp.where(
            (st2.promise_mid >= 0) & promise_reused, -1, st2.promise_mid
        )

        # 7b. fanout slots for publishes to unjoined topics
        if cfg.fanout_slots > 0:
            st2 = update_fanout_on_publish(
                cfg, net_l, st2, pub_origin_held, pub_topic,
                jax.random.fold_in(jax.random.fold_in(core.key, tick), 0xFA40),
                nbr_sub_words_l, thr=thr, msh=msh,
            )

        # ---- router plane state roll (docs/DESIGN.md §24) ---------------
        # announcements accumulate at round END from this round's
        # post-throttle first receipts and are consumed NEXT round — the
        # one-RTT control latency every other outbox pays. Every per-edge
        # and per-id router plane gets the same keep-words recycle the
        # mcache gets.
        router_next = {}
        if router is not None:
            if router.idontwant_eligible:
                ann = dontwant_announcements(
                    router, info.recv_new_words, joined_words
                )
                router_next["dontwant"] = (
                    (st.dontwant | ann) & keep_words[None, :]
                )
            if router.choke:
                router_next["choke_ema"] = choke_ema_next
            if router.latency_rounds > 0:
                router_next["inflight"] = ring_keep(inflight_next, keep_words)

        if cfg.count_events:
            events = accumulate_round_events(
                events, info, jnp.sum((pub_origin_held >= 0).astype(jnp.int32))
            )
            if router is not None:
                if router.idontwant_eligible:
                    events = events.at[EV.IDONTWANT_SENT].add(
                        idontwant_sent_count(ann, mesh_edge)
                    )
                if n_dup_sup is not None:
                    events = events.at[EV.DUP_SUPPRESSED].add(n_dup_sup)
            if chaos is not None:
                events = events.at[EV.LINK_DOWN].add(
                    chaos_faults.count_links_down(net.nbr, net_l.nbr_ok,
                                                  link_ok)
                ).at[EV.IWANT_RECOVER].add(n_iwant_rec)
            if n_adv_drop is not None:
                events = events.at[EV.ADV_DROP].add(n_adv_drop)
        core_next = core.replace(msgs=msgs, dlv=dlv, events=events)
        if chaos is not None and chaos.needs_state:
            core_next = core_next.replace(
                chaos=core.chaos.replace(ge_bad=ge_bad_next)
            )
        st2 = st2.replace(
            core=core_next,
            mcache=mcache,
            ihave_out=ihave_out,
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
            # NOT keep-masked: a dup bit always names the message the slot
            # held when the arrival happened, so the drain attributes the
            # plane against the PRE-publish slot->mid mapping — including
            # arrivals in a message's own death round (which the device
            # counter also counted)
            dup_trans=dup_plane,
            **router_next,
        )

        # congested links suppress next heartbeat's gossip toward them:
        # a full writer queue drops the IHAVE batch and gossip is never
        # retried (gossipsub.go:1757-1764 flush drops, :1155-1160)
        if cfg.queue_cap > 0:
            sat_recv = bitset.popcount(info.trans, axis=-1) >= cfg.queue_cap
            gossip_suppress = net_l.edge_gather(sat_recv) & net_l.nbr_ok
            st2 = st2.replace(congested_in=sat_recv)
        else:
            gossip_suppress = None

        # 8. heartbeat — inline when it runs every round (the default tick
        # model); lax.cond otherwise. The cond carries the whole state
        # through both branches, which costs real copies of the big arrays.
        def hb(s):
            return heartbeat(
                cfg, net_l, s, tp_r, sp_r, nbr_sub_l, gater_params,
                nbr_sub_words_l, present_ok=net.nbr_ok,
                gossip_suppress=gossip_suppress, adversary=adv, thr=thr,
                msh=msh,
            )

        if cfg.heartbeat_every == 1:
            st2 = hb(st2)
        elif static_heartbeat:
            # trace-time decision: the driver asserts the cadence; the
            # non-heartbeat trace contains no heartbeat code at all (no
            # lax.cond branch-materialization copies of the state)
            if do_heartbeat:
                st2 = hb(st2)
        else:
            st2 = jax.lax.cond((tick % cfg.heartbeat_every) == 0, hb, lambda s: s, st2)

        # telemetry row — the step's LAST operation, after the heartbeat
        # (whose GRAFT/PRUNE accounting the EV deltas must cover)
        if telemetry is not None:
            from ..telemetry import panel as _tele

            core_f = st2.core
            telem = _tele.record_step(
                telemetry, core_f.telem, tick, ev_prev, core_f.events,
                net_l, core_f.msgs, core_f.dlv,
                mesh=st2.mesh, my_topics=net_l.my_topics,
                scores=st2.scores,
                backoff_active=(st2.backoff_present
                                & (st2.backoff_expire > tick)),
            )
            st2 = st2.replace(core=core_f.replace(telem=telem))

        return st2.replace(core=st2.core.replace(tick=tick + 1))

    if net.edge_layout == "csr":
        # CSR-resident state tier (round 18, docs/DESIGN.md §18): the
        # per-edge planes live FLAT in the carry (fe_words/served_*/
        # peerhave/iasked as [E, ...]); densify at entry, re-pack at
        # exit — the step body above stays the dense-written program,
        # bit-exact, while checkpoints/scan carries hold the flat tier
        _round = wrap_csr_resident(net, _round)

    use_static_hb = static_heartbeat and cfg.heartbeat_every > 1
    if lift_scores:
        # lifted call convention: the TRACED score plane rides as the
        # LAST positional, after the per-round arrays (up_next /
        # link_deny keep their usual slots) — so ensemble.lift_step
        # vmaps it like any other per-sim input, which is exactly the
        # configs×sims sweep axis the ROADMAP parameter search needs
        def _dispatch(st, pub_origin, pub_topic, pub_valid, rest,
                      do_heartbeat=True):
            up = rest[0] if dynamic_peers else None
            deny = rest[int(dynamic_peers)] if chaos_sched else None
            writes = (
                rest[int(dynamic_peers) + int(chaos_sched)]
                if dynamic_topo else None
            )
            return _round(st, pub_origin, pub_topic, pub_valid, up,
                          do_heartbeat, deny, score_plane=rest[-1],
                          mut_writes=writes)

        if use_static_hb:
            def step(st, pub_origin, pub_topic, pub_valid, *rest,
                     do_heartbeat):
                return _dispatch(st, pub_origin, pub_topic, pub_valid,
                                 rest, do_heartbeat)
            return jax.jit(step, donate_argnums=0,
                           static_argnames=("do_heartbeat",))

        def step(st, pub_origin, pub_topic, pub_valid, *rest):
            return _dispatch(st, pub_origin, pub_topic, pub_valid, rest)
        return jax.jit(step, donate_argnums=0)
    if use_static_hb:
        # do_heartbeat is REQUIRED here: a default would let a driver
        # silently heartbeat every round (or never) against the cadence.
        # A scheduled-chaos build likewise takes the Scenario's forced-
        # down link mask as a REQUIRED trailing positional ([N, K] bool,
        # True = link down this round) — a default would silently run
        # the scenario with no partitions.
        if dynamic_topo and chaos_sched:
            # mut_writes is REQUIRED for the same reason link_deny is: a
            # default would silently run the window with no mutations
            def step(st, pub_origin, pub_topic, pub_valid, up_next,
                     link_deny, mut_writes, *, do_heartbeat):
                return _round(st, pub_origin, pub_topic, pub_valid, up_next,
                              do_heartbeat, link_deny,
                              mut_writes=mut_writes)
        elif dynamic_topo:
            def step(st, pub_origin, pub_topic, pub_valid, up_next,
                     mut_writes, *, do_heartbeat):
                return _round(st, pub_origin, pub_topic, pub_valid, up_next,
                              do_heartbeat, mut_writes=mut_writes)
        elif dynamic_peers and chaos_sched:
            def step(st, pub_origin, pub_topic, pub_valid, up_next,
                     link_deny, *, do_heartbeat):
                return _round(st, pub_origin, pub_topic, pub_valid, up_next,
                              do_heartbeat, link_deny)
        elif dynamic_peers:
            def step(st, pub_origin, pub_topic, pub_valid, up_next, *, do_heartbeat):
                return _round(st, pub_origin, pub_topic, pub_valid, up_next,
                              do_heartbeat)
        elif chaos_sched:
            def step(st, pub_origin, pub_topic, pub_valid, link_deny,
                     *, do_heartbeat):
                return _round(st, pub_origin, pub_topic, pub_valid, None,
                              do_heartbeat, link_deny)
        else:
            def step(st, pub_origin, pub_topic, pub_valid, *, do_heartbeat):
                return _round(st, pub_origin, pub_topic, pub_valid, None,
                              do_heartbeat)
        return jax.jit(step, donate_argnums=0,
                       static_argnames=("do_heartbeat",))

    if dynamic_topo and chaos_sched:
        def step(st, pub_origin, pub_topic, pub_valid, up_next, link_deny,
                 mut_writes):
            return _round(st, pub_origin, pub_topic, pub_valid, up_next,
                          link_deny=link_deny, mut_writes=mut_writes)
    elif dynamic_topo:
        def step(st, pub_origin, pub_topic, pub_valid, up_next, mut_writes):
            return _round(st, pub_origin, pub_topic, pub_valid, up_next,
                          mut_writes=mut_writes)
    elif dynamic_peers and chaos_sched:
        def step(st, pub_origin, pub_topic, pub_valid, up_next, link_deny):
            return _round(st, pub_origin, pub_topic, pub_valid, up_next,
                          link_deny=link_deny)
    elif dynamic_peers:
        def step(st, pub_origin, pub_topic, pub_valid, up_next):
            return _round(st, pub_origin, pub_topic, pub_valid, up_next)
    elif chaos_sched:
        def step(st, pub_origin, pub_topic, pub_valid, link_deny):
            return _round(st, pub_origin, pub_topic, pub_valid, None,
                          link_deny=link_deny)
    else:
        def step(st, pub_origin, pub_topic, pub_valid):
            return _round(st, pub_origin, pub_topic, pub_valid, None)

    return jax.jit(step, donate_argnums=0)


def no_publish(p: int = 4):
    """Empty publish buffers."""
    z = jnp.full((p,), -1, jnp.int32)
    return z, z, jnp.zeros((p,), bool)


def set_blacklist(st: GossipSubState, mask) -> GossipSubState:
    """BlacklistPeer (pubsub.go:590-605): host-side toggle; takes effect on
    the next dynamic_peers step with full disconnect cleanup, and keeps the
    peer disconnected for as long as the flag is set (the blacklist checks
    at pubsub.go:1048-1060 and connection-time :636-639)."""
    return st.replace(blacklist=jnp.asarray(mask, bool))
