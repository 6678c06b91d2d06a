"""Batched peer-score engine — the v1.1 security plane (score.go:1-1074).

Every peer n scores each of its neighbor slots k; topic-local counters live
at [N, S, K] (S = topic slots, survey topic-slot compression). The weighted
P1..P7 sum (score.go:258-335), the decay pass (refreshScores,
score.go:497-558) and the delivery-attribution updates (score.go:892-974)
are all elementwise/batched-matmul passes — the "embarrassingly parallel
elementwise pass" the survey §2 checklist names.

Time is integer ticks; durations are converted with ticks_for at
TopicParamsArrays build time. The P3 "mesh delivery window" becomes
window_rounds (default 0: only same-round-as-validation duplicates count,
matching the reference's 10ms window vs 1s heartbeat scale — survey §7
hard-part (e)). The P3 window and activation are compared against
``tick``, which counts delivery ROUNDS, so both are built in rounds:
heartbeats times ``heartbeat_every`` (the same at ``heartbeat_every`` 1).
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..config import PeerScoreParams, ticks_for
from ..ops import bitset
from ..perf import stages
from ..state import Net


@dataclasses.dataclass(frozen=True)
class TopicParamsArrays:
    """Per-topic score params as dense [T] numpy arrays (row t zeroed when
    topic t is unscored — unscored topics contribute nothing and track no
    counters, score.go:269-273, 881-884)."""

    scored: np.ndarray        # [T] bool
    topic_weight: np.ndarray  # [T] f32
    w1: np.ndarray
    quantum_ticks: np.ndarray  # [T] f32 (>=1)
    cap1: np.ndarray
    w2: np.ndarray
    decay2: np.ndarray
    cap2: np.ndarray
    w3: np.ndarray
    decay3: np.ndarray
    cap3: np.ndarray
    thr3: np.ndarray
    window_rounds: np.ndarray     # [T] i32, delivery rounds
    activation_ticks: np.ndarray  # [T] i32, delivery rounds too
    w3b: np.ndarray
    decay3b: np.ndarray
    w4: np.ndarray
    decay4: np.ndarray

    @classmethod
    def build(cls, params: PeerScoreParams, n_topics: int, heartbeat_interval: float = 1.0,
              heartbeat_every: int = 1):
        he = int(heartbeat_every)

        def arr(fn, dtype=np.float32):
            out = np.zeros((n_topics,), dtype)
            for t, tp in params.topics.items():
                if 0 <= t < n_topics:
                    out[t] = fn(tp)
            return out

        scored = np.zeros((n_topics,), bool)
        for t in params.topics:
            if 0 <= t < n_topics:
                scored[t] = True
        return cls(
            scored=scored,
            topic_weight=arr(lambda p: p.topic_weight),
            w1=arr(lambda p: p.time_in_mesh_weight),
            quantum_ticks=arr(lambda p: max(1, ticks_for(p.time_in_mesh_quantum, heartbeat_interval))),
            cap1=arr(lambda p: p.time_in_mesh_cap),
            w2=arr(lambda p: p.first_message_deliveries_weight),
            decay2=arr(lambda p: p.first_message_deliveries_decay),
            cap2=arr(lambda p: p.first_message_deliveries_cap),
            w3=arr(lambda p: p.mesh_message_deliveries_weight),
            decay3=arr(lambda p: p.mesh_message_deliveries_decay),
            cap3=arr(lambda p: p.mesh_message_deliveries_cap),
            thr3=arr(lambda p: p.mesh_message_deliveries_threshold),
            # both are read against ``tick - first_round`` / ``tick -
            # graft_tick``, which count ROUNDS: heartbeats x rounds a
            # heartbeat (the window keeps its "less one" at the round's
            # grain: an arrival w rounds after the first is inside)
            window_rounds=arr(
                lambda p: ticks_for(p.mesh_message_deliveries_window, heartbeat_interval) * he - 1
                if p.mesh_message_deliveries_window >= heartbeat_interval
                else 0,
                np.int32,
            ),
            # (a topic whose P3 and P3b are both weightless has no reader
            # of the activation latch, and its row keeps the heartbeat
            # count it had: the honest cells' windows keep their text)
            activation_ticks=arr(
                lambda p: ticks_for(p.mesh_message_deliveries_activation, heartbeat_interval)
                * (he if p.mesh_message_deliveries_weight != 0.0
                   or p.mesh_failure_penalty_weight != 0.0 else 1),
                np.int32,
            ),
            w3b=arr(lambda p: p.mesh_failure_penalty_weight),
            decay3b=arr(lambda p: p.mesh_failure_penalty_decay),
            w4=arr(lambda p: p.invalid_message_deliveries_weight),
            decay4=arr(lambda p: p.invalid_message_deliveries_decay),
        )

    def gather(self, my_topics: jax.Array):
        """Gather all per-topic arrays to per-(peer, slot) [N, S] views;
        slots with no topic (-1) come out zeroed/unscored."""
        t = jnp.clip(my_topics, 0)
        live = my_topics >= 0

        def g(a, fill=0):
            v = jnp.asarray(a)[t]
            return jnp.where(live, v, jnp.asarray(fill, v.dtype))

        return {f.name: g(getattr(self, f.name)) for f in dataclasses.fields(self)}


@struct.dataclass
class ScoreState:
    """Counters the score is computed from (peerStats/topicStats,
    score.go:17-62), per (peer, topic-slot, neighbor-slot)."""

    fmd: jax.Array          # [N,S,K] f32 firstMessageDeliveries
    mmd: jax.Array          # [N,S,K] f32 meshMessageDeliveries
    mfp: jax.Array          # [N,S,K] f32 meshFailurePenalty (P3b, sticky)
    imd: jax.Array          # [N,S,K] f32 invalidMessageDeliveries
    graft_tick: jax.Array   # [N,S,K] i32 tick of last graft (-1 = never)
    mesh_time: jax.Array    # [N,S,K] i32 ticks in mesh (updated on refresh)
    mmd_active: jax.Array   # [N,S,K] bool P3 activation latch
    bp: jax.Array           # [N,K]  f32 behaviourPenalty (P7)

    @classmethod
    def empty(cls, n: int, s: int, k: int) -> "ScoreState":
        f = lambda: jnp.zeros((n, s, k), jnp.float32)
        return cls(
            fmd=f(), mmd=f(), mfp=f(), imd=f(),
            graft_tick=jnp.full((n, s, k), -1, jnp.int32),
            mesh_time=jnp.zeros((n, s, k), jnp.int32),
            mmd_active=jnp.zeros((n, s, k), bool),
            bp=jnp.zeros((n, k), jnp.float32),
        )


def _attrib(tp: dict, *weights: str):
    """``stages.part("attrib")`` around a P3 / P3b / P4 term, where one of
    the gathered ``weights`` of ``tp`` can be non-zero: a concrete plane is
    looked at while the step is traced, a traced one (the lifted build's)
    counts as live. Weightless terms (an honest net's) get no scope, so
    such a program carries no part."""
    for name in weights:
        w = tp[name]
        if isinstance(w, jax.core.Tracer) or np.any(jax.device_get(w) != 0.0):
            return stages.part("attrib")
    return contextlib.nullcontext()


# ---------------------------------------------------------------------------
# P6: IP colocation


def ip_colocation_surplus_sq(net: Net, threshold: int, whitelist=()) -> jax.Array:
    """[N, K] f32: (peersInIP - threshold)^2 where the count of my connected
    neighbors sharing neighbor k's ip-group exceeds the threshold
    (score.go:337-381). Static for a static topology — precompute once."""
    groups = net.peer_gather(net.ip_group)  # [N,K]
    same = (groups[:, :, None] == groups[:, None, :]) & net.nbr_ok[:, None, :]
    count = jnp.sum(same.astype(jnp.int32), axis=-1)  # [N,K]
    surplus = (count - threshold).astype(jnp.float32)
    p6 = jnp.where(count > threshold, surplus * surplus, 0.0)
    if len(whitelist):
        wl = jnp.isin(groups, jnp.asarray(list(whitelist), dtype=groups.dtype))
        p6 = jnp.where(wl, 0.0, p6)
    return jnp.where(net.nbr_ok, p6, 0.0)


# ---------------------------------------------------------------------------
# the score function (score.go:258-335)


@stages.scope("score")
def compute_scores(
    st: ScoreState,
    in_mesh: jax.Array,   # [N,S,K] bool — router mesh membership
    tp: dict,             # gathered TopicParamsArrays ([N,S] views)
    params: PeerScoreParams,
    p6: jax.Array,        # [N,K] precomputed colocation surplus^2
    app_score: jax.Array,  # [N] per-peer P5 value (gathered at nbr)
    net: Net,
    app_gathered: jax.Array | None = None,  # [N,K] pre-gathered P5 plane
) -> jax.Array:
    """[N, K] f32 — peer n's score of neighbor slot k."""
    e = lambda a: a[..., None]  # [N,S] -> [N,S,1] broadcast over K

    # P1: time in mesh (score.go:279-285)
    p1 = jnp.minimum(st.mesh_time.astype(jnp.float32) / e(tp["quantum_ticks"]), e(tp["cap1"]))
    topic = jnp.where(in_mesh, p1 * e(tp["w1"]), 0.0)

    # P2 (score.go:288-289)
    topic = topic + st.fmd * e(tp["w2"])

    with _attrib(tp, "w3", "w3b", "w4"):
        # P3: deficit^2 when active and below threshold (score.go:292-298)
        deficit = e(tp["thr3"]) - st.mmd
        p3 = jnp.where(st.mmd_active & (deficit > 0), deficit * deficit, 0.0)
        topic = topic + p3 * e(tp["w3"])

        # P3b + P4 (score.go:302-308)
        topic = topic + st.mfp * e(tp["w3b"])
        topic = topic + st.imd * st.imd * e(tp["w4"])

    score = jnp.sum(topic * e(tp["topic_weight"]), axis=1)  # [N,K]

    # topic score cap (score.go:315-317). The lifted plane (round 16,
    # score/params.py) carries the cap as a TRACED scalar, so the
    # static elision becomes a jnp.where — value-identical at matched
    # values (cap > 0: both paths apply the same minimum; cap == 0:
    # the where selects the unclamped score, exactly what skipping the
    # minimum produced). LIFT_AUDIT.json records this site as the
    # guarded elision it is.
    if getattr(params, "lifted", False):
        score = jnp.where(params.topic_score_cap > 0,
                          jnp.minimum(score, params.topic_score_cap), score)
    elif params.topic_score_cap > 0:
        score = jnp.minimum(score, params.topic_score_cap)

    # P5 (score.go:320-321) — statically elided when the weight is zero
    # everywhere (the same build-time zero-weight elision the phase engine
    # applies to P3/P4 planes: the term multiplies finite app scores by
    # 0.0, so scores are bit-identical and the cross-peer gather — one
    # full halo-permute set on the sharded mesh — never lowers). When
    # live, the phase engine's coalesced wire exchange pre-gathers the
    # plane at its control head (app_score is phase-invariant) and passes
    # it as ``app_gathered`` so the heartbeat tail adds no extra halo.
    if params.app_specific_weight != 0.0:
        app_g = (app_gathered if app_gathered is not None
                 else net.peer_gather(app_score))
        score = score + app_g * params.app_specific_weight

    # P6 (score.go:324-325)
    score = score + p6 * params.ip_colocation_factor_weight

    # P7 (score.go:328-332)
    excess = st.bp - params.behaviour_penalty_threshold
    p7 = jnp.where(excess > 0, excess * excess, 0.0)
    score = score + p7 * params.behaviour_penalty_weight

    return jnp.where(net.nbr_ok, score, 0.0)


# ---------------------------------------------------------------------------
# decay pass (refreshScores, score.go:497-558)


@stages.scope("score")
def refresh_scores(st: ScoreState, in_mesh: jax.Array, tick, tp: dict, params: PeerScoreParams) -> ScoreState:
    dtz = params.decay_to_zero
    e = lambda a: a[..., None]

    def dec(x, d):
        y = x * d
        return jnp.where(y < dtz, 0.0, y)

    fmd = dec(st.fmd, e(tp["decay2"]))
    mmd = dec(st.mmd, e(tp["decay3"]))
    mfp = dec(st.mfp, e(tp["decay3b"]))
    imd = dec(st.imd, e(tp["decay4"]))

    # mesh time + P3 activation (score.go:543-549)
    mesh_time = jnp.where(in_mesh, tick - st.graft_tick, st.mesh_time)
    active = st.mmd_active | (in_mesh & (mesh_time > e(tp["activation_ticks"])))

    bp = st.bp * params.behaviour_penalty_decay
    bp = jnp.where(bp < dtz, 0.0, bp)

    return st.replace(fmd=fmd, mmd=mmd, mfp=mfp, imd=imd, mesh_time=mesh_time, mmd_active=active, bp=bp)


# ---------------------------------------------------------------------------
# mesh membership transitions (Graft/Prune tracer hooks, score.go:642-684)


def on_graft(st: ScoreState, graft_mask: jax.Array, tick) -> ScoreState:
    """graft_mask [N,S,K]: newly grafted edges. Resets mesh time and the P3
    activation latch (score.go:642-660)."""
    return st.replace(
        graft_tick=jnp.where(graft_mask, tick, st.graft_tick),
        mesh_time=jnp.where(graft_mask, 0, st.mesh_time),
        mmd_active=jnp.where(graft_mask, False, st.mmd_active),
    )


def clear_edges(st: ScoreState, mask: jax.Array) -> ScoreState:
    """Reset all per-edge score stats where mask [N,K] — the disconnect path
    (score.go:604-637 removePeer): a peer leaving with a *non-negative*
    score has its stats deleted immediately; negative scores are retained so
    disconnect/reconnect can't wash them (the caller computes the mask
    accordingly). Retained stats keep decaying via refresh_scores, which
    matches the reference's decay-to-zero during the retention window."""
    m3 = mask[:, None, :]
    z = lambda a: jnp.where(m3, jnp.zeros_like(a), a)
    return st.replace(
        fmd=z(st.fmd),
        mmd=z(st.mmd),
        mfp=z(st.mfp),
        imd=z(st.imd),
        graft_tick=jnp.where(m3, -1, st.graft_tick),
        mesh_time=jnp.where(m3, 0, st.mesh_time),
        mmd_active=st.mmd_active & ~m3,
        bp=jnp.where(mask, 0.0, st.bp),
    )


def clear_mesh_status(st: ScoreState, mask: jax.Array) -> ScoreState:
    """Clear in-mesh bookkeeping (graft tick, mesh time, P3 activation) on
    every edge in mask [N,K] — the removePeer path's "no longer in any mesh"
    step (score.go:614-625), applied to retained *and* deleted stats alike.
    Without this, a retained (negative-score) peer's mmd_active would stay
    latched while mmd decays, turning the P3 deficit into a permanent
    penalty instead of the one-shot P3b conversion the reference applies."""
    m3 = mask[:, None, :]
    return st.replace(
        graft_tick=jnp.where(m3, -1, st.graft_tick),
        mesh_time=jnp.where(m3, 0, st.mesh_time),
        mmd_active=st.mmd_active & ~m3,
    )


def on_prune(st: ScoreState, prune_mask: jax.Array, tp: dict) -> ScoreState:
    """prune_mask [N,S,K]: edges leaving the mesh. Applies the sticky mesh
    failure penalty when pruned while active and below threshold
    (score.go:662-684)."""
    e = lambda a: a[..., None]
    deficit = e(tp["thr3"]) - st.mmd
    add = jnp.where(prune_mask & st.mmd_active & (deficit > 0), deficit * deficit, 0.0)
    return st.replace(mfp=st.mfp + add)


# ---------------------------------------------------------------------------
# delivery attribution (score.go:892-974), consuming the round's transmit
# tensor


def per_slot_counts(words: jax.Array, slotw: jax.Array) -> jax.Array:
    """[N,K,W] packed words -> [N,S,K] f32 popcounts per topic slot —
    the shared reduction kernel of on_deliveries and the phase engine's
    count-fold path (single-source so the two score paths cannot
    drift)."""
    s_slots = slotw.shape[1]
    return jnp.stack(
        [bitset.popcount(words & slotw[:, s : s + 1, :], axis=-1)
         for s in range(s_slots)], axis=1
    ).astype(jnp.float32)


def slot_topic_words(net: Net, msg_topic: jax.Array) -> jax.Array:
    """[N, S, W] packed: messages belonging to the topic of my slot s.

    For wide topic universes the [N,S]-row gather from the tiny [T,W]
    table lowers to a slow TPU gather (profiled ~0.3-0.6 ms per
    occurrence at N=100k, T=64); the direct per-message topic compare +
    pack is plain fused vector work instead (the [N,S,M] bool never
    materializes — XLA fuses the compare into the pack reduction)."""
    n_topics = net.subscribed.shape[1]
    if n_topics > 8:
        bits = (
            msg_topic[None, None, :] == net.my_topics[:, :, None]
        ) & (msg_topic >= 0)[None, None, :]
        return bitset.pack(bits)
    onehot_t = msg_topic[None, :] == jnp.arange(n_topics, dtype=jnp.int32)[:, None]
    tw = bitset.pack(onehot_t)                      # [T, W]
    stw = tw[jnp.clip(net.my_topics, 0)]            # [N, S, W]
    return jnp.where((net.my_topics >= 0)[:, :, None], stw, jnp.uint32(0))


@stages.scope("score")
def on_deliveries(
    st: ScoreState,
    net: Net,
    in_mesh: jax.Array,       # [N,S,K] bool
    tp: dict,
    trans_words: jax.Array,   # [N,K,W] u32 — this round's per-edge receipts
    new_words: jax.Array,     # [N,W] u32 — first receipts this round
    fe_words: jax.Array,      # [N,K,W] u32 — packed first-arrival edge plane
    first_round: jax.Array,   # [N,M] i32 — validation round of each msg
    msg_topic: jax.Array,     # [M] i32
    msg_valid: jax.Array,     # [M] bool
    tick,
    window_rounds_t: jax.Array,  # [T] i32 — per-topic P3 window (tpa.window_rounds)
    pending_words: jax.Array | None = None,   # [N,W] u32 — msgs in the
                                              # async-validation pipeline
    recv_new_words: jax.Array | None = None,  # [N,W] u32 — fresh receipts
    msg_ignored: jax.Array | None = None,  # [M] bool — ValidationIgnore
    slotw: jax.Array | None = None,  # [N,S,W] — caller's slot_topic_words
                                     # for the same (pre-publish) msg table
    mesh_credit_words: jax.Array | None = None,  # [N,K,W] caller-accumulated
                                     # in-window mesh-credit base (phase mode)
) -> ScoreState:
    """Fold one delivery round into the counters.

    * first receipt of a valid msg: firstMessageDeliveries +1 (capped) on the
      first-arrival edge; meshMessageDeliveries +1 (capped) if that edge is
      in the mesh (markFirstMessageDelivery, score.go:912-939)
    * other same-round arrivals count as near-first mesh deliveries
      (DeliverMessage's drec.peers loop, score.go:712-718), and later
      duplicates within the window also count (markDuplicateMessageDelivery,
      score.go:944-974)
    * every arrival of a *rejected* msg: invalidMessageDeliveries +1
      (markInvalidMessageDelivery via RejectMessage/DuplicateMessage,
      score.go:776-782, 811-813). Ignored messages (ValidationIgnore)
      move no counters at all — their senders are explicitly not
      penalized (validation.go:46-52; score.go:768-774 deliveryIgnored)

    Everything is packed-word algebra: per-(peer,slot,edge) counts are
    popcounts of word-AND — no [N,K,M] gathers, casts, or einsums in the
    hot path."""
    n, s_slots = net.my_topics.shape
    m = msg_topic.shape[0]
    t = jnp.clip(msg_topic, 0)

    if slotw is None:
        slotw = slot_topic_words(net, msg_topic)  # [N,S,W]

    _psc = per_slot_counts

    valid_w = bitset.pack(msg_valid)  # [W]

    # -- P2/P3 credit for valid messages ------------------------------------
    # fe ⊆ arrivals, so the packed first-arrival plane restricted to this
    # round's validated cohort is the attribution mask directly (with async
    # validation the physical arrival was rounds ago; credit lands at the
    # verdict, the reference's DeliverMessage timing, score.go:695-719)
    first_arrival = fe_words & new_words[:, None, :] & valid_w[None, None, :]
    fmd_inc = _psc(first_arrival, slotw)
    e = lambda a: a[..., None]
    fmd = jnp.minimum(st.fmd + fmd_inc, e(tp["cap2"]))

    # mesh delivery credit: first arrivals + near-first (same round) + later
    # duplicates within the window; only on mesh edges, only valid msgs.
    # The window gate requires a set first_round (a message still awaiting
    # its verdict has first_round = -1, which must not pass the compare).
    with _attrib(tp, "w3", "w3b"):
        if mesh_credit_words is not None:
            # phase mode (gossipsub_phase.py): the caller evaluated the window
            # gate per sub-round against each arrival's own tick and OR-folded
            # the result (exact — every (edge,msg) pair transmits at most once,
            # so the fold loses no multiplicity); the pending-duplicate credit
            # is likewise folded in per sub-round. Only the valid mask and the
            # verdict-time first-arrival credit apply at phase end.
            mesh_credit = (
                (mesh_credit_words & valid_w[None, None, :]) | first_arrival
            )
        else:
            msg_window = window_rounds_t[t]  # [M]
            within_w = bitset.pack(
                (first_round >= 0) & ((tick - first_round) <= msg_window[None, :])
            )  # [N,W]
            mesh_credit = trans_words & valid_w[None, None, :] & within_w[:, None, :]
        if mesh_credit_words is None and pending_words is not None:
            # async pipeline (DeliverMessage's drec.peers loop, score.go:712-718):
            #  * the first-arrival edge earns its mesh credit at the verdict —
            #    its physical transmission happened rounds ago, so trans can't
            #    supply it;
            #  * duplicates arriving while the message is pending are in the
            #    delivery record and credited unconditionally (credited here at
            #    arrival; the count matches, only the decay instant differs).
            #    The fresh first arrival itself is excluded — it gets credit at
            #    its own verdict via the first branch.
            exclude_first = (
                fe_words & recv_new_words[:, None, :]
                if recv_new_words is not None else jnp.uint32(0)
            )
            pend_dup = (
                trans_words & pending_words[:, None, :] & valid_w[None, None, :]
                & ~exclude_first
            )
            mesh_credit = mesh_credit | pend_dup | first_arrival
        mmd_inc = _psc(mesh_credit, slotw) * in_mesh.astype(jnp.float32)
        mmd = jnp.minimum(st.mmd + mmd_inc, e(tp["cap3"]))

    # -- P4 penalty for rejected messages -----------------------------------
    with _attrib(tp, "w4"):
        penalize_w = ~valid_w
        if msg_ignored is not None:
            penalize_w = penalize_w & ~bitset.pack(msg_ignored)
        invalid_arrival = trans_words & penalize_w[None, None, :]
        imd = st.imd + _psc(invalid_arrival, slotw)

    # unscored slots track nothing (getTopicStats, score.go:881-884)
    scored = e(tp["scored"])
    return st.replace(
        fmd=jnp.where(scored, fmd, st.fmd),
        mmd=jnp.where(scored, mmd, st.mmd),
        imd=jnp.where(scored, imd, st.imd),
    )


@stages.scope("score")
def apply_delivery_counts(
    st: ScoreState,
    tp: dict,
    fmd_counts: jax.Array,  # [N,S,K] f32 — first-delivery credits
    mmd_counts: jax.Array,  # [N,S,K] f32 — in-window mesh-delivery credits
    imd_counts: jax.Array,  # [N,S,K] f32 — invalid-arrival penalties
    in_mesh: jax.Array,     # [N,S,K] bool
) -> ScoreState:
    """Fold pre-reduced delivery counts into the counters — the phase
    engine's count-accumulation path (gossipsub_phase.py): each sub-round
    reduces its transmit tensor to per-(peer, slot, edge) popcounts at
    arrival time (valid/window/first-arrival masks applied there, exactly
    as on_deliveries would), so no [N,K,W] attribution plane survives the
    loop. Caps apply once per fold like on_deliveries applies them once
    per round; with multi-round folds the cap can bind up to r-1 rounds
    late (caps are sized in the hundreds — parity rows cover it)."""
    e = lambda a: a[..., None]
    fmd = jnp.minimum(st.fmd + fmd_counts, e(tp["cap2"]))
    with _attrib(tp, "w3", "w3b", "w4"):
        mmd = jnp.minimum(
            st.mmd + mmd_counts * in_mesh.astype(jnp.float32), e(tp["cap3"])
        )
        imd = st.imd + imd_counts
    scored = e(tp["scored"])
    return st.replace(
        fmd=jnp.where(scored, fmd, st.fmd),
        mmd=jnp.where(scored, mmd, st.mmd),
        imd=jnp.where(scored, imd, st.imd),
    )


def add_penalties(st: ScoreState, counts: jax.Array) -> ScoreState:
    """behaviourPenalty += counts [N,K] (AddPenalty, score.go:384-398)."""
    return st.replace(bp=st.bp + counts.astype(jnp.float32))
