"""Shared delivery engine: one synchronous message-propagation round.

This is the vectorized core of the reference's hot path (survey §3.2/3.3):
router.Publish -> per-peer RPC queues -> reader -> validation -> forward.
All routers share it; they differ only in *which edges carry* a message
(flood: every topic edge, floodsub.go:76-100; gossipsub: mesh/fanout edges;
randomsub: a random subset chosen at publish).

Gather-only dataflow for all N-sized traffic: each receiver j reads its
senders' forward sets at nbr[j,k] and applies edge/topic masks. (The one
deliberate exception is an M-element scatter marking message origins —
M is the tiny message-slot axis, not a peer-sized tensor.) The
transmit tensor `trans[N, K, W]` (packed words) *is* the round's wire
traffic; aggregate popcounts of it produce the SendRPC/RecvRPC trace
counters, and the score engine later consumes it for delivery attribution.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct

from ..ops import bitset
from ..perf import stages
from ..state import Delivery, MsgTable, Net
from ..trace.events import EV

@struct.dataclass
class RoundInfo:
    """Per-round delivery observables consumed by tracing + scoring.

    With inline validation (val_delay=0) the entry and validated cohorts
    coincide (`recv_new_words is new_words`); with the async-validation
    pipeline, `recv_new_words` is this round's fresh receipts (queue
    admission — the throttle's cohort) while `new_words` is the receipts
    whose validation completed this round (delivery/forwarding/scoring
    cohort, the reference's post-validation publishMessage timing)."""

    trans: jax.Array        # [N, K, W] u32 — words transmitted to j on edge k
    new_words: jax.Array    # [N, W] u32 — receipts validated this round
    new_bits: jax.Array     # [N, M] bool — same, unpacked
    recv_new_words: jax.Array  # [N, W] u32 — first receipts this round
    n_deliver: jax.Array    # i64 — validated receipts of valid messages
    n_reject: jax.Array     # i64 — validated receipts of invalid messages
    n_duplicate: jax.Array  # i64 — arrivals beyond the first per (peer,msg)
    n_rpc: jax.Array        # i64 — total (edge, msg) transmissions
    n_drop: jax.Array = struct.field(default_factory=lambda: jnp.int32(0))
    # ^ transmissions lost to the outbound-queue cap (doDropRPC,
    #   gossipsub.go:1153-1160; comm.go:139-170) — 0 when queue_cap is off


def member_msg_words(member: jax.Array, msg_topic: jax.Array) -> jax.Array:
    """[N, W] packed mask: messages whose topic satisfies member[n, topic]
    (member is an [N, T] bool relation; padding topics (-1) match nothing).

    For wide topic universes this is an MXU matmul rather than an [N, M]
    per-message gather (which profiled ~0.8 ms/round at T=64, N=100k):
    per-topic packed words have disjoint bits — each message slot has
    exactly one topic — so OR equals SUM, and splitting words into bytes
    keeps every partial sum exact in f32 (byte sums of disjoint bits are
    <= 255, far inside the 24-bit mantissa)."""
    n, n_topics = member.shape
    onehot_t = msg_topic[None, :] == jnp.arange(n_topics, dtype=jnp.int32)[:, None]
    tw = bitset.pack(onehot_t)  # [T, W], disjoint bits across T
    if n_topics <= 8:
        # narrow universe: masked OR over T is cheaper than an MXU trip
        contrib = jnp.where(member[:, :, None], tw[None, :, :], jnp.uint32(0))
        return bitset.word_or_reduce(contrib, axis=1)
    w = tw.shape[-1]
    tb = jnp.stack(
        [(tw >> jnp.uint32(8 * i)) & jnp.uint32(0xFF) for i in range(4)], axis=-1
    ).reshape(n_topics, w * 4).astype(jnp.float32)
    jb = jnp.dot(member.astype(jnp.float32), tb)  # [N, W*4]
    jb = jb.astype(jnp.uint32).reshape(n, w, 4)
    return (
        jb[..., 0] | (jb[..., 1] << jnp.uint32(8))
        | (jb[..., 2] << jnp.uint32(16)) | (jb[..., 3] << jnp.uint32(24))
    )


def subscribed_msg_words(net: Net, msgs: MsgTable) -> jax.Array:
    """[N, W] packed mask: messages whose topic peer n subscribes to."""
    return member_msg_words(net.subscribed, msgs.topic)


def origin_msg_words(net: Net, msgs: MsgTable) -> jax.Array:
    """[N, W] packed mask: messages peer n originated (never sent back to the
    origin — the `pid == peer.ID(msg.GetFrom())` check, floodsub.go:87,
    gossipsub.go:1007).

    Each message has exactly one origin, so this is an M-element scatter of
    single-bit words — not an [N, M] one-hot compare+pack (which costs
    N*M work per round just to mark M bits)."""
    n = net.n_peers
    m = msgs.capacity
    w = bitset.n_words(m)
    slot = jnp.arange(m, dtype=jnp.int32)
    upd = jnp.uint32(1) << (slot % 32).astype(jnp.uint32)
    row = jnp.where(msgs.origin >= 0, msgs.origin, n)  # OOB-drop padding
    # distinct bit positions per (row, word) pair make add equivalent to or
    return jnp.zeros((n, w), jnp.uint32).at[row, slot // 32].add(upd, mode="drop")


def pipeline_entry_masks(msg_topic: jax.Array, delay_topic: tuple, v: int) -> jax.Array:
    """[V, W] u32 stage-entry masks for the per-topic validation-latency
    pipeline: a receipt of a topic with delay d enters shift stage V - d,
    so its verdict lands d rounds after arrival (the reference's per-topic
    async validators complete at different times, validation.go:391-438).
    Padding topics (-1) never match a stage — their bits can't arrive."""
    import numpy as np

    dt = jnp.asarray(np.asarray(delay_topic, np.int32))[jnp.clip(msg_topic, 0)]
    stage = jnp.where(msg_topic >= 0, v - dt, -1)  # [M]
    return bitset.pack(stage[None, :] == jnp.arange(v, dtype=jnp.int32)[:, None])


def pipeline_insert(pending_shifted: jax.Array, new_words: jax.Array,
                    msg_topic: jax.Array, delay_topic: tuple | None) -> jax.Array:
    """Insert this round's fresh receipts into the (already shifted)
    pipeline at their per-topic entry stage (stage 0 when uniform)."""
    v = pending_shifted.shape[1]
    if delay_topic is None:
        return pending_shifted.at[:, 0, :].set(
            pending_shifted[:, 0, :] | new_words
        )
    masks = pipeline_entry_masks(msg_topic, delay_topic, v)  # [V, W]
    return pending_shifted | (new_words[:, None, :] & masks[None, :, :])


def delivery_round(
    net: Net,
    msgs: MsgTable,
    dlv: Delivery,
    edge_mask: jax.Array,  # [N, K, W] u32: words edge (j,k) may carry j-ward
    tick: jax.Array,
    forward_mask: jax.Array | None = None,  # [N, W] extra gate on what gets re-forwarded
    count_events: bool = True,
    queue_cap: int = 0,    # per-edge outbound message budget per round
                           # (pubsub.go:240's 32-deep queue); 0 = lossless
    val_delay_topic: tuple | None = None,  # per-topic pipeline delays
                           # (cfg.validation_delay_topic); None = uniform
) -> tuple[Delivery, RoundInfo]:
    """Advance one propagation round: transmit every sender's `fwd` set along
    permitted edges, dedup against the seen-cache, record first receipts.

    Semantics per receiver j, edge k (sender s = nbr[j,k]):
      trans = fwd[s] & not-echo(s->j) & edge_mask & not-mine(j)
    where echo excludes the single edge a message arrived on (the "source"
    exclusion, floodsub.go:85-86) and not-mine excludes the origin.

    Messages are marked seen whether valid or not (markSeen happens inside
    validation, validation.go:285-293); only valid ones are re-forwarded
    (honest behavior — Reject stops propagation, validation.go:309-351).

    A state built with the async-validation pipeline (survey §7 hard
    part (c); validation.go's worker pool — `dlv.pending` is not None)
    marks receipts seen on arrival but holds them in the pipeline before
    their verdict; forwarding, the Deliver/Reject outcome, and `first_round`
    (the propagation-CDF timestamp, matching the reference's
    post-validation DeliverMessage timing) all happen at pipeline exit.
    """
    n, k_slots = net.nbr.shape
    m = msgs.capacity

    if dlv.fe_words.ndim == 2:
        # CSR-RESIDENT first-arrival plane (round 18): [E, W] flat
        assert net.edge_layout == "csr" and (
            dlv.fe_words.shape[0] == net.n_edges), (
            "flat fe_words needs a matching edge_layout='csr' Net "
            f"({dlv.fe_words.shape[0]} != E={net.n_edges})"
        )
    else:
        assert dlv.fe_words.shape[1] == k_slots, (
            "Delivery.fe_words edge axis does not match the topology's "
            f"max_degree ({dlv.fe_words.shape[1]} != {k_slots}) — construct "
            "the state with SimState.init(..., k=net.max_degree)"
        )
    # the pipeline's presence in the state IS the configuration — deriving
    # it here means a caller can never mismatch the two
    val_delay = 0 if dlv.pending is None else dlv.pending.shape[1]

    not_mine = ~origin_msg_words(net, msgs)  # [N, W]
    if msgs.wire_block is not None:
        # oversized messages never cross any edge (sendRPC's fragmentRPC
        # drop, gossipsub.go:1126-1140) — they still live in mcache and
        # get IHAVE-advertised, like the reference's
        not_mine = not_mine & ~bitset.pack(msgs.wire_block)[None, :]

    if net.edge_layout == "csr":
        # sparse data plane (ops/csr.py, docs/DESIGN.md §15): the whole
        # transmit composition runs over the flat [E, W] edge space —
        # the neighbor fwd view and the echo involution are E-sized
        # gathers, the edge/chaos/adversary masks pack down to the
        # present edges, and dead padded slots never move (absent
        # edges aren't in E, so the dense path's nbr_ok word mask has
        # no flat counterpart). One local unpack rebuilds the
        # [N, K, W] transmit tensor for the shared commit tail
        # (finish_delivery) and the RoundInfo consumers (scoring
        # attribution, IWANT merge, telemetry popcounts), so the
        # delivery semantics stay single-source and dense-vs-CSR
        # parity is bit-exact (tests/test_csr.py, all four engines).
        flat_resident = dlv.fe_words.ndim == 2
        fwd_e = net.peer_gather_flat(dlv.fwd)                    # [E, W]
        echo_e = net.edge_gather_flat(
            dlv.fe_words if flat_resident
            else net.pack_edges(dlv.fe_words)
        )
        mask_e = net.pack_edges(edge_mask)
        # receiver-side gate, read at each edge's owner (a local read)
        not_mine_e = net.owner_gather(not_mine)
        trans_e = fwd_e & ~echo_e & mask_e & not_mine_e
        if flat_resident:
            # fully-flat commit (round 18): the reductions back to the
            # peer axis run as ONE segmented scan over [E, W] and the
            # first-arrival plane commits flat — the dense [N, K, W]
            # transmit tensor is never materialized. This is the path
            # the power-law topo-smoke A/B wins on (dead padded slots
            # cost nothing, at rest or in flight).
            return finish_delivery_flat(
                net, msgs, dlv, trans_e, tick, forward_mask=forward_mask,
                count_events=count_events, queue_cap=queue_cap,
                val_delay_topic=val_delay_topic,
            )
        trans = net.unpack_edges(trans_e)
        return finish_delivery(
            net, msgs, dlv, trans, tick, forward_mask=forward_mask,
            count_events=count_events, queue_cap=queue_cap,
            val_delay_topic=val_delay_topic,
        )

    # what each sender is forwarding this round: [N, K, W] word gather
    fwd_gathered = net.peer_gather(dlv.fwd)

    # echo exclusion: sender s does not send m back on the edge it arrived
    # on. The packed first-arrival plane IS the sender-side echo set, so
    # this is a plain word gather: echo[j,k] = "messages s first-received
    # on its edge to j"
    echo_words = net.edge_gather(dlv.fe_words)

    ok_words = jnp.where(net.nbr_ok[..., None], jnp.uint32(0xFFFFFFFF), jnp.uint32(0))

    trans = fwd_gathered & ~echo_words & edge_mask & ok_words & not_mine[:, None, :]
    return finish_delivery(
        net, msgs, dlv, trans, tick, forward_mask=forward_mask,
        count_events=count_events, queue_cap=queue_cap,
        val_delay_topic=val_delay_topic,
    )


@stages.scope("deliver")
def finish_delivery(
    net: Net,
    msgs: MsgTable,
    dlv: Delivery,
    trans: jax.Array,  # [N, K, W] u32: the round's (pre-cap) transmit tensor
    tick: jax.Array,
    forward_mask: jax.Array | None = None,
    count_events: bool = True,
    queue_cap: int = 0,
    val_delay_topic: tuple | None = None,
) -> tuple[Delivery, RoundInfo]:
    """Cap + commit a computed transmit tensor: queue_cap backpressure,
    seen-cache dedup, first-arrival attribution, validation pipeline,
    forward-set update. Shared tail of the receiver-side `delivery_round`
    above and the phase engine's sender-side transmit form
    (gossipsub_phase.py) so the delivery semantics stay single-source."""
    m = msgs.capacity
    val_delay = 0 if dlv.pending is None else dlv.pending.shape[1]

    n_drop = jnp.int32(0)
    if queue_cap > 0:
        # outbound-queue backpressure: each directed link carries at most
        # queue_cap messages per round; the overflow is genuinely LOST —
        # the reference drops the whole RPC when the per-peer writer queue
        # is full (doDropRPC gossipsub.go:1155-1160, comm.go:139-170).
        # Lowest slots first models "queue fills, later sends dropped".
        want = trans
        trans = bitset.keep_lowest_bits(want, queue_cap, m)  # static cap
        n_drop = bitset.popcount(want & ~trans, axis=None).sum().astype(jnp.int32)

    recv_words = bitset.word_or_reduce(trans, axis=1)  # [N, W]
    new_words = recv_words & ~dlv.have

    # first-arrival edge: lowest edge slot carrying each new bit, isolated
    # in word algebra
    fa_words = bitset.first_set_per_bit(trans, axis=1) & new_words[:, None, :]
    valid_words = bitset.pack(msgs.valid)  # [W]

    if val_delay > 0:
        # fresh receipts enter at their per-topic stage (uniform: stage 0);
        # this round's validated cohort exits stage V-1
        validated = dlv.pending[:, -1]
        shifted = jnp.concatenate(
            [jnp.zeros_like(dlv.pending[:, :1]), dlv.pending[:, :-1]], axis=1
        )
        pending = pipeline_insert(shifted, new_words, msgs.topic, val_delay_topic)
    else:
        validated = new_words
        pending = dlv.pending

    validated_bits = bitset.unpack(validated, m)
    first_round = jnp.where(validated_bits, tick, dlv.first_round)

    # forwarding: validated receipts of valid messages (store-and-forward
    # happens after the verdict — Reject stops propagation)
    fwd_next = validated & valid_words[None, :]
    if forward_mask is not None:
        fwd_next = fwd_next & forward_mask

    dlv = dlv.replace(
        have=dlv.have | new_words,
        fwd=fwd_next,
        first_round=first_round,
        # overwrite (not OR) on new receipts so stale bits can't survive a
        # slot whose message is re-received after its fe column was cleared
        fe_words=(dlv.fe_words & ~new_words[:, None, :]) | fa_words,
        pending=pending,
    )

    info = _round_info(trans, validated, m, valid_words, count_events)
    info = info.replace(recv_new_words=new_words, n_drop=n_drop)
    if count_events and val_delay > 0:
        # arrival-cohort counters (duplicates/rpc) are already arrival-based
        # inside _round_info only when the cohorts coincide; recompute here
        n_new = bitset.popcount(new_words, axis=None).astype(jnp.int32).sum()
        info = info.replace(n_duplicate=info.n_rpc - n_new)
    return dlv, info


def finish_delivery_flat(
    net: Net,
    msgs: MsgTable,
    dlv: Delivery,
    trans_e: jax.Array,  # [E, W] u32: the round's flat transmit plane
    tick: jax.Array,
    forward_mask: jax.Array | None = None,
    count_events: bool = True,
    queue_cap: int = 0,
    val_delay_topic: tuple | None = None,
) -> tuple[Delivery, RoundInfo]:
    """The CSR-RESIDENT commit tail (round 18): cap + dedup +
    first-arrival attribution + pipeline + forward update, with every
    per-edge quantity staying on the flat [E, W] plane. Exact-equal to
    ``finish_delivery`` on the unpacked tensor (tests/test_csr.py):

      * the per-peer receive OR and the first-arrival isolation both
        fall out of ONE segmented prefix-OR over the row segments
        (ops/csr.segment_or_scan) — ``inc`` at each row's last edge is
        the receive set, ``x & ~exc`` keeps each bit's first carrying
        edge, and flat row-major order IS ascending dense slot order,
        so the attribution matches ``first_set_per_bit`` bit for bit;
      * the first-arrival plane commits flat — dead padded slots are
        never resident OR in flight;
      * ``RoundInfo.trans`` carries the FLAT plane (popcount-compatible
        with the dense form — absent slots transmit nothing either
        way). Engines that need the dense tensor (scoring attribution)
        run the dense-resident path instead.
    """
    from ..ops import csr

    m = msgs.capacity
    val_delay = 0 if dlv.pending is None else dlv.pending.shape[1]

    n_drop = jnp.int32(0)
    if queue_cap > 0:
        # per-directed-link budget: one flat row IS one (receiver, edge)
        # pair, so the cap applies exactly as in the dense form
        want = trans_e
        trans_e = bitset.keep_lowest_bits(want, queue_cap, m)
        n_drop = bitset.popcount(want & ~trans_e, axis=None).sum().astype(jnp.int32)

    # fused build (round 21): the capacity bound K caps every row
    # segment, so the scan runs ceil(log2 K) shifted levels instead of
    # log2(E) — the dominant delivery-chain term the cost audit's
    # fusion contract pins. Bit-exact either way.
    cap = net.max_degree if net.fused else None
    inc, exc = csr.segment_or_scan(trans_e, net.csr_seg_start, cap=cap)
    recv_words = jnp.where(
        net.csr_row_nonempty[:, None],
        inc[jnp.clip(net.csr_row_last, 0)], jnp.uint32(0),
    )  # [N, W]
    new_words = recv_words & ~dlv.have

    # first-arrival edge, isolated flat: the first edge of each row
    # carrying each new bit (exc = OR of the row's earlier edges)
    new_e = net.owner_gather(new_words)
    fa_e = trans_e & ~exc & new_e
    valid_words = bitset.pack(msgs.valid)  # [W]

    if val_delay > 0:
        validated = dlv.pending[:, -1]
        shifted = jnp.concatenate(
            [jnp.zeros_like(dlv.pending[:, :1]), dlv.pending[:, :-1]], axis=1
        )
        pending = pipeline_insert(shifted, new_words, msgs.topic, val_delay_topic)
    else:
        validated = new_words
        pending = dlv.pending

    validated_bits = bitset.unpack(validated, m)
    first_round = jnp.where(validated_bits, tick, dlv.first_round)

    fwd_next = validated & valid_words[None, :]
    if forward_mask is not None:
        fwd_next = fwd_next & forward_mask

    dlv = dlv.replace(
        have=dlv.have | new_words,
        fwd=fwd_next,
        first_round=first_round,
        # same overwrite-on-new-receipt rule as the dense commit, on the
        # flat plane (new_words read at each edge's owner row)
        fe_words=(dlv.fe_words & ~new_e) | fa_e,
        pending=pending,
    )

    info = _round_info(trans_e, validated, m, valid_words, count_events)
    info = info.replace(recv_new_words=new_words, n_drop=n_drop)
    if count_events and val_delay > 0:
        n_new = bitset.popcount(new_words, axis=None).astype(jnp.int32).sum()
        info = info.replace(n_duplicate=info.n_rpc - n_new)
    return dlv, info


def _round_info(trans, new_words, m, valid_words, count_events=True) -> RoundInfo:
    """Delivery observables from a round's transmit/new sets (shared by the
    dense and flat commits so the trace-counter semantics stay single-source).

    `count_events=False` (no EventTracer attached — tracing is opt-in in
    the reference, pubsub.go WithEventTracer) skips the aggregate popcount
    reductions; the per-message delivery state (first_round/first_edge,
    the CDF source) is exact either way."""
    if not count_events:
        z = jnp.int32(0)
        return RoundInfo(
            trans=trans,
            new_words=new_words,
            new_bits=bitset.unpack(new_words, m),
            recv_new_words=new_words,
            n_deliver=z, n_reject=z, n_duplicate=z, n_rpc=z,
        )
    n_rpc = bitset.popcount(trans, axis=None).astype(jnp.int32).sum()
    n_new = bitset.popcount(new_words, axis=None).astype(jnp.int32).sum()
    n_deliver = (
        bitset.popcount(new_words & valid_words[None, :], axis=None)
        .astype(jnp.int32).sum()
    )
    return RoundInfo(
        trans=trans,
        new_words=new_words,
        new_bits=bitset.unpack(new_words, m),
        recv_new_words=new_words,
        n_deliver=n_deliver,
        n_reject=n_new - n_deliver,
        n_duplicate=n_rpc - n_new,
        n_rpc=n_rpc,
    )


def accumulate_round_events(events: jax.Array, info: RoundInfo, n_publish) -> jax.Array:
    """Fold a round's delivery observables into the cumulative event
    counters (the EventTracer accounting that trace_test.go:26-195 checks:
    publish/deliver/duplicate/reject totals plus RPC counts)."""
    ev = events
    ev = ev.at[EV.PUBLISH_MESSAGE].add(jnp.asarray(n_publish, jnp.int32))
    ev = ev.at[EV.DELIVER_MESSAGE].add(info.n_deliver)
    ev = ev.at[EV.REJECT_MESSAGE].add(info.n_reject)
    ev = ev.at[EV.DUPLICATE_MESSAGE].add(info.n_duplicate)
    ev = ev.at[EV.SEND_RPC].add(info.n_rpc)
    ev = ev.at[EV.RECV_RPC].add(info.n_rpc)
    ev = ev.at[EV.DROP_RPC].add(info.n_drop)
    return ev
