"""Device-resident simulation state (struct-of-arrays).

The reference's `PubSub` struct owns all mutable protocol state in Go maps
(pubsub.go:42-166) mutated by a single event-loop goroutine. Here the same
state is dense arrays over all N peers at once, advanced by pure jitted
steps — the TPU-idiomatic equivalent of the single-writer actor (survey §7).

Message identity: message ids are interned to slots in a rotating global
table of capacity M (survey §7 hard-part (b)); per-peer message sets (the
seen-cache, pubsub.go:30,146; forward sets) are packed uint32 bitsets over
those slots. A slot is recycled when the cursor wraps; recycling clears the
corresponding bit column everywhere, which emulates the reference's 120s
seen-cache TTL — size M so that slot lifetime (M / publish-rate) exceeds
both propagation time and the mcache window.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from . import graph as graphlib
from .ops import bitset, csr, edges
from .perf import spans, stages
from .trace.events import zero_counters


@struct.dataclass
class Net:
    """Static network: topology + subscriptions + identity (survey L0
    collapsed into arrays; see graph.py for field semantics).

    ``edge_gather`` moves a per-edge plane across the edge involution by
    what ``build`` saw in the graph, with no switch of the caller's: rolls
    on a banded net (``band_off``), the flat [E] space on a CSR build, and
    on every other dense net a row gather through ``edge_perm``: tiered
    (``tiers``: head columns whole, of the tail columns only the present
    slots; out of the full slot table, or, where that table lies beyond
    the size the chip reads cheaply and the plan's own does not, as ONE
    gather out of a compact table that holds the head columns and the
    tail's present rows and no other) where the degree histogram makes
    that the cheaper program, the one full gather where it does not (full
    or near-regular columns, toy nets) and on a ``dynamic`` net, whose
    ``edge_perm`` is traced. Every form returns the same plane on every
    slot, absent ones included."""

    nbr: jax.Array         # [N, K] i32
    nbr_ok: jax.Array      # [N, K] bool
    rev: jax.Array         # [N, K] i32
    outbound: jax.Array    # [N, K] bool
    subscribed: jax.Array  # [N, T] bool
    my_topics: jax.Array   # [N, S] i32
    slot_of: jax.Array     # [N, T] i32
    ip_group: jax.Array    # [N] i32 (P6 colocation key)
    direct: jax.Array      # [N, K] bool — direct (explicit) peering edges
                           # (WithDirectPeers, gossipsub.go:332-345)
    edge_perm: jax.Array   # [N, K] i32 — flat (nbr*K + rev) edge involution
                           # (ops/edges.py: the fast-path cross-peer gather)
    protocol: jax.Array    # [N] i8 — negotiated protocol per peer
                           # (gossipsub_feat.go:11-36): 0 = /floodsub/1.0.0,
                           # 1 = /meshsub/1.0.0, 2 = /meshsub/1.1.0
    # banded-regular structure (ops/edges.detect_banded): static aux data;
    # when set, cross-peer gathers compile to rolls (~9x faster on TPU)
    band_off: tuple = struct.field(pytree_node=False, default=None)
    band_rev: tuple = struct.field(pytree_node=False, default=None)
    # capacity-bounded CSR edge layout (ops/csr.py, round 15): present
    # only when built with edge_layout="csr" — cross-peer movement then
    # runs over the flat [E] edge space (E = number of present edges)
    # instead of the padded [N, K] slot space. The layout selector is
    # pytree-AUX data, so engines trace exactly ONE layout with zero
    # runtime branching (same contract as band_off); "dense" builds
    # trace the pre-CSR program bit for bit.
    edge_layout: str = struct.field(pytree_node=False, default="dense")
    csr_col: jax.Array | None = None      # [E] i32 neighbor per edge
    csr_row: jax.Array | None = None      # [E] i32 owner per edge (sorted)
    csr_eperm: jax.Array | None = None    # [E] i32 flat involution
    csr_e2nk: jax.Array | None = None     # [E] i32 pack gather (n*K+k)
    csr_e_of_nk: jax.Array | None = None  # [N,K] i32 unpack map, -1 absent
    # flat segment structure (round 18): row-segment starts / per-row
    # last-edge index / nonempty rows — what the fully-flat delivery
    # commit's segmented reductions need (models/common.py; derived from
    # the FLAT ordering so they stay correct on block-padded builds)
    csr_seg_start: jax.Array | None = None     # [E] bool
    csr_row_last: jax.Array | None = None      # [N] i32 (clip-safe junk
                                               #  on empty rows)
    csr_row_nonempty: jax.Array | None = None  # [N] bool
    # block padding (edge-space sharding, round 18): present only on
    # ``edge_shards=...`` builds — inert padding edges equalize the
    # row-owner-aligned shard blocks (ops/csr.pad_csr_blocks); every
    # flat plane carries 0 there forever
    csr_e_valid: jax.Array | None = None       # [E] bool, None = no pad
    # static aux structure of the flat layout (trace-time, like band_off):
    # csr_identity — e2nk == arange(E) (full-density row-major build), so
    # pack/unpack are pure RESHAPES (GSPMD splits the sharded edge axis
    # without collectives); csr_band_* — the banded-regular roll structure
    # detected on the underlying topology, so the FLAT cross-peer gathers
    # lower to the same static rolls (= halo collective-permutes under
    # GSPMD) the dense involution compiles to
    csr_identity: bool = struct.field(pytree_node=False, default=False)
    csr_band_off: tuple = struct.field(pytree_node=False, default=None)
    csr_band_rev: tuple = struct.field(pytree_node=False, default=None)
    # fused composites (round 21, docs/DESIGN.md §21): statically select
    # the bandwidth-lean XLA forms on the shared delivery seam —
    # the capacity-bounded segmented OR in the flat commit
    # (ops/csr.segment_or_scan cap=K) and, in engines that read it, the
    # sort-form selection (ops/select fused=True). Pytree-AUX like
    # edge_layout: one build traces exactly ONE kernel set, False traces
    # the pre-fusion program bit for bit (the census gate's contract).
    fused: bool = struct.field(pytree_node=False, default=False)
    # tiered edge gather (ops/edges.plan_tiers): planned by ``build`` on a
    # dense, unbanded, static net whose high columns are nearly empty, K0
    # and the table its head reads both from the degree histogram; None is
    # K0 = K, the one full gather through ``edge_perm``
    tiers: edges.Tiers | None = None

    @stages.scope("edge_gather")
    def edge_gather(self, x: jax.Array) -> jax.Array:
        """x[N, K, ...] -> x[nbr[j,k], rev[j,k], ...] (the edge involution).
        Callers mask with nbr_ok; entries on dead/absent edges are junk
        (self-pointing — both layouts reproduce the same values, so
        dense-vs-CSR parity is bit-exact even on unmasked planes)."""
        if self.edge_layout == "csr":
            got = self.unpack_edges(
                self.edge_gather_flat(self.pack_edges(x))
            )
            if self.csr_identity:
                return got  # every slot present — no junk to fill
            # absent slots: the dense perm self-points (build_edge_perm),
            # so the junk value is the slot's own entry
            present = (self.csr_e_of_nk >= 0).reshape(
                self.csr_e_of_nk.shape + (1,) * (x.ndim - 2))
            return jnp.where(present, got, x)
        if self.band_off is not None:
            return edges.edge_permute_banded(x, self.band_off, self.band_rev)
        if self.tiers is not None:
            return edges.edge_permute_tiered(x, self.tiers)
        return edges.edge_permute(x, self.edge_perm)

    @stages.scope("edge_gather")
    def peer_gather(self, v: jax.Array) -> jax.Array:
        """v[N, ...] -> [N, K, ...] neighbor view v[nbr[j,k]]. Same masking
        contract as edge_gather (absent slots read v[0] in both layouts —
        the dense path's clip(-1, 0))."""
        if self.edge_layout == "csr":
            got = self.unpack_edges(self.peer_gather_flat(v))
            if self.csr_identity:
                return got
            present = (self.csr_e_of_nk >= 0).reshape(
                self.csr_e_of_nk.shape + (1,) * (v.ndim - 1))
            return jnp.where(present, got, v[0])
        if self.band_off is not None:
            return edges.peer_gather_banded(v, self.band_off)
        out = v[jnp.clip(self.nbr, 0)]
        edges._tally("peer", out, rows=self.nbr.size)
        return out

    # -- flat-edge-space face (edge_layout="csr" only) ---------------------

    def pack_edges(self, x: jax.Array) -> jax.Array:
        """[N, K, ...] -> [E, ...]: the present slots, row-major (a
        LOCAL relayout — adds nothing to the halo-permute budget). On a
        full-density row-major build (``csr_identity``) this is a pure
        reshape — GSPMD splits the sharded axis with no collective."""
        if self.csr_identity:
            n, k = x.shape[:2]
            return x.reshape((n * k,) + x.shape[2:])
        got = csr.pack_edges(x, self.csr_e2nk, self.max_degree)
        if self.csr_e_valid is not None:
            keep = self.csr_e_valid.reshape(
                (-1,) + (1,) * (got.ndim - 1))
            got = jnp.where(keep, got, jnp.zeros((), got.dtype))
        return got

    def unpack_edges(self, x_e: jax.Array, fill=None) -> jax.Array:
        """[E, ...] -> [N, K, ...]; absent slots take ``fill`` (zero).
        Padding edges of a block-padded build are never addressed by
        ``e_of_nk``, so they simply vanish here."""
        if self.csr_identity:
            n, k = self.csr_e_of_nk.shape
            return x_e.reshape((n, k) + x_e.shape[1:])
        return csr.unpack_edges(x_e, self.csr_e_of_nk, fill)

    def edge_gather_flat(self, x_e: jax.Array) -> jax.Array:
        """The involution on a flat edge plane: out[e] = x_e[eperm[e]]
        — E-sized cross-peer movement. On a banded-regular full-density
        build the gather lowers as the dense banded ROLLS (the same
        halo collective-permute structure under GSPMD)."""
        if self.csr_band_off is not None:
            n, k = self.csr_e_of_nk.shape
            out = edges.edge_permute_banded(
                x_e.reshape((n, k) + x_e.shape[1:]),
                self.csr_band_off, self.csr_band_rev,
            )
            return out.reshape((n * k,) + x_e.shape[1:])
        return csr.edge_permute_flat(x_e, self.csr_eperm)

    def owner_gather(self, v: jax.Array) -> jax.Array:
        """v[N, ...] read at each edge's OWNER row: out[e] = v[row[e]].
        A LOCAL read — each edge shard reads its own rows (row-owner
        partition), so this never crosses the peer axis; on identity
        builds it is a broadcast+reshape, so GSPMD sees no gather at
        all (the sharded-CSR zero-all-gather contract)."""
        if self.csr_identity:
            n, k = self.csr_e_of_nk.shape
            out = jnp.broadcast_to(v[:, None], (n, k) + v.shape[1:])
            return out.reshape((n * k,) + v.shape[1:])
        return v[self.csr_row]

    def peer_gather_flat(self, v: jax.Array) -> jax.Array:
        """Flat neighbor view: out[e] = v[col[e]] (rolls on a
        banded-regular full-density build, like the dense form)."""
        if self.csr_band_off is not None:
            n, k = self.csr_e_of_nk.shape
            out = edges.peer_gather_banded(v, self.csr_band_off)
            return out.reshape((n * k,) + v.shape[1:])
        got = csr.peer_gather_flat(v, self.csr_col)
        if self.csr_e_valid is not None:
            keep = self.csr_e_valid.reshape(
                (-1,) + (1,) * (got.ndim - 1))
            got = jnp.where(keep, got, jnp.zeros((), got.dtype))
        return got

    @classmethod
    @spans.span("setup.net_build")
    def build(
        cls,
        topo: graphlib.Topology,
        subs: graphlib.Subscriptions,
        ip_group: np.ndarray | None = None,
        direct: np.ndarray | None = None,
        protocol: np.ndarray | None = None,
        edge_layout: str = "dense",
        edge_shards: int | None = None,
        fused: bool = False,
        dynamic: bool = False,
    ) -> "Net":
        """``dynamic=True`` (round 22, docs/DESIGN.md §22) builds the
        net for the MUTABLE overlay plane: a CSR build allocates the
        full-capacity identity layout (E = N*K, absent slots inert via
        e_valid — ops/csr.build_csr_full) so rewiring only rewrites
        traced [E] planes, and banded-roll detection is skipped on both
        layouts (band structure is static; a mutating graph must never
        key the roll fast paths). Pair with ``Net.with_overlay`` and a
        ``TopoState`` plane in the sim state."""
        n = topo.n_peers
        if ip_group is None:
            ip_group = np.arange(n, dtype=np.int32)  # unique IPs
        if direct is None:
            direct = np.zeros(topo.nbr.shape, bool)
        if protocol is None:
            protocol = np.full((n,), 2, np.int8)  # all /meshsub/1.1.0
        if edge_layout not in ("dense", "csr"):
            raise ValueError(
                f"edge_layout must be 'dense' or 'csr', got {edge_layout!r}"
            )
        if edge_shards is not None and edge_layout != "csr":
            raise ValueError(
                "edge_shards is an edge-space sharding knob — it needs "
                "edge_layout='csr'"
            )
        if dynamic and fused:
            raise ValueError(
                "dynamic=True is incompatible with the fused composites "
                "(cfg.fused) — the composites assume a static edge list"
            )
        if dynamic and edge_shards is not None:
            raise ValueError(
                "dynamic=True needs the full-capacity identity layout — "
                "block padding (edge_shards) would break E == N*K"
            )
        csr_kw: dict = {}
        if edge_layout == "csr" and dynamic:
            ct, e_valid_full = csr.build_csr_full(
                topo.nbr, topo.rev, topo.nbr_ok)
            csr_kw = dict(
                csr_col=jnp.asarray(ct.col),
                csr_row=jnp.asarray(ct.row),
                csr_eperm=jnp.asarray(ct.eperm),
                csr_e2nk=jnp.asarray(ct.e2nk),
                csr_e_of_nk=jnp.asarray(ct.e_of_nk),
                csr_seg_start=jnp.asarray(ct.seg_start),
                csr_row_last=jnp.asarray(ct.row_last),
                # all-True, NOT degree > 0: an empty row may gain edges
                # mid-window and this plane is not overlay-rebound;
                # full-capacity rows always own their K-slot segment
                # (absent entries carry zeros — the padding convention)
                csr_row_nonempty=jnp.asarray(np.ones((n,), bool)),
                csr_e_valid=jnp.asarray(e_valid_full),
                csr_identity=True,
                csr_band_off=None,
                csr_band_rev=None,
            )
            band = None
        elif edge_layout == "csr":
            ct = csr.build_csr(topo.nbr, topo.rev, topo.nbr_ok)
            e_valid = None
            if edge_shards is not None and edge_shards > 1:
                ct, e_valid = csr.pad_csr_blocks(ct, int(edge_shards))
                if e_valid.all():
                    # blocks divided evenly — no padding, no mask cost
                    e_valid = None
            e = ct.n_edges
            # flat segment structure from the FLAT ordering (the
            # CsrTopology properties derive it from ct.row, so it stays
            # correct on block-padded builds: padding edges extend
            # their block's last row segment and carry zeros)
            seg_start = ct.seg_start
            row_last = ct.row_last
            row_nonempty = topo.degree > 0
            # static flat structure: identity pack/unpack (full-density
            # row-major) and the banded-roll lowering for the flat
            # gathers (both require every padded slot present)
            identity = bool((ct.e2nk == np.arange(e)).all())
            band_flat = (
                edges.detect_banded(topo.nbr, topo.rev, topo.nbr_ok)
                if identity else None
            )
            csr_kw = dict(
                csr_col=jnp.asarray(ct.col),
                csr_row=jnp.asarray(ct.row),
                csr_eperm=jnp.asarray(ct.eperm),
                csr_e2nk=jnp.asarray(ct.e2nk),
                csr_e_of_nk=jnp.asarray(ct.e_of_nk),
                csr_seg_start=jnp.asarray(seg_start),
                csr_row_last=jnp.asarray(row_last),
                csr_row_nonempty=jnp.asarray(row_nonempty),
                csr_e_valid=(
                    jnp.asarray(e_valid) if e_valid is not None else None
                ),
                csr_identity=identity,
                csr_band_off=band_flat[0] if band_flat else None,
                csr_band_rev=band_flat[1] if band_flat else None,
            )
            # the DENSE banded-roll path keys off band_off; a CSR
            # build must never fall into it (the flat
            # analogue rides csr_band_off above)
            band = None
        else:
            band = (None if dynamic
                    else edges.detect_banded(topo.nbr, topo.rev, topo.nbr_ok))
        # the host's numpy over the index planes (``plan_tiers`` ends in the
        # plan's three device puts)
        with spans.span("setup.net_build.plan"):
            edge_perm = edges.build_edge_perm(topo.nbr, topo.rev, topo.nbr_ok)
            # the tiers are static like the band: a dense net that is
            # neither banded (rolls) nor dynamic (``edge_perm`` is traced
            # there)
            tiers = (edges.plan_tiers(edge_perm, topo.nbr_ok)
                     if edge_layout == "dense" and band is None
                     and not dynamic else None)
        with spans.span("setup.net_build.planes"):
            return cls(
                edge_layout=edge_layout,
                fused=bool(fused),
                tiers=tiers,
                **csr_kw,
                band_off=band[0] if band else None,
                band_rev=band[1] if band else None,
                nbr=jnp.asarray(topo.nbr),
                nbr_ok=jnp.asarray(topo.nbr_ok),
                rev=jnp.asarray(topo.rev),
                outbound=jnp.asarray(topo.outbound),
                subscribed=jnp.asarray(subs.subscribed),
                my_topics=jnp.asarray(subs.my_topics),
                slot_of=jnp.asarray(subs.slot_of),
                ip_group=jnp.asarray(ip_group),
                direct=jnp.asarray(direct),
                edge_perm=jnp.asarray(edge_perm),
                protocol=jnp.asarray(protocol, jnp.int8),
            )

    @property
    def n_peers(self) -> int:
        return self.nbr.shape[0]

    def with_overlay(self, topo: "TopoState") -> "Net":
        """Rebind the MUTABLE overlay planes (round 22 dynamic
        topology, docs/DESIGN.md §22): nbr / nbr_ok / rev / edge_perm
        from a ``TopoState``, plus the flat col / eperm / e_valid faces
        on a CSR build. Trace-safe — every replaced plane is a traced
        array of unchanged shape, all pytree-AUX fields stay put, so a
        jitted step that rebinds per round recompiles NOTHING. Requires
        a ``Net.build(..., dynamic=True)`` net: no banded-roll
        structure on either layout, and the CSR face must be the
        full-capacity identity layout (E == N*K)."""
        if self.band_off is not None or self.csr_band_off is not None:
            raise ValueError(
                "with_overlay: banded-roll structure is static — build "
                "the net with Net.build(..., dynamic=True)"
            )
        if self.tiers is not None:
            raise ValueError(
                "with_overlay: the tiered gather's plan is static — build "
                "the net with Net.build(..., dynamic=True)"
            )
        kw = dict(nbr=topo.nbr, nbr_ok=topo.nbr_ok, rev=topo.rev,
                  edge_perm=topo.edge_perm)
        if self.edge_layout == "csr":
            e = self.n_peers * self.max_degree
            if not self.csr_identity or self.n_edges != e:
                raise ValueError(
                    "with_overlay: the CSR face must be the "
                    "full-capacity identity layout (E == N*K) — build "
                    "the net with Net.build(..., dynamic=True)"
                )
            kw.update(
                csr_col=jnp.clip(topo.nbr, 0).reshape(e),
                csr_eperm=topo.edge_perm.reshape(e),
                csr_e_valid=topo.nbr_ok.reshape(e),
            )
        return self.replace(**kw)

    @property
    def n_edges(self) -> int | None:
        """Present (directed) edge count E of a CSR build; None on a
        dense build (where the exchange is N*K-sized regardless)."""
        return None if self.csr_col is None else self.csr_col.shape[0]

    @property
    def max_degree(self) -> int:
        return self.nbr.shape[1]

    @property
    def n_topics(self) -> int:
        return self.subscribed.shape[1]

    @property
    def n_slots(self) -> int:
        return self.my_topics.shape[1]


# validation verdict codes — same numbering as ValidationResult
# (validation.go:40-52): accepted messages deliver + forward; rejected
# messages are dropped AND every sender takes the P4 invalid-message
# penalty (RejectMessage, score.go:721-786); ignored messages are dropped
# without penalizing their senders (score.go:768-774)
VERDICT_ACCEPT = 0
VERDICT_REJECT = 1
VERDICT_IGNORE = 2
# flag bit OR-able onto a verdict code: the message exceeds the wire's
# maxMessageSize (WithMaxMessageSize, pubsub.go:480-485). It is delivered
# locally, enters mcache, and is IHAVE-advertised — but every transmit
# (mesh/fanout/flood push AND IWANT responses) drops it, exactly like the
# reference's sendRPC-side fragmentRPC drop of a single message larger
# than the limit (gossipsub.go:1126-1140, fragmentRPC :1180-1187)
VERDICT_WIRE_BLOCK = 4


def decode_verdicts(pub_valid: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(accept, ignored) bool planes from a publish-verdict array.

    `pub_valid` is either bool (True = accept, False = reject — the
    original two-verdict interface) or an integer VERDICT_* code array
    (the three-verdict interface, plus the VERDICT_WIRE_BLOCK flag bit)."""
    if pub_valid.dtype == jnp.bool_:
        return pub_valid, jnp.zeros_like(pub_valid)
    base = pub_valid & ~VERDICT_WIRE_BLOCK
    return base == VERDICT_ACCEPT, base == VERDICT_IGNORE


def decode_wire_block(pub_valid: jax.Array) -> jax.Array:
    """Bool plane of the VERDICT_WIRE_BLOCK flag (False for bool verdicts)."""
    if pub_valid.dtype == jnp.bool_:
        return jnp.zeros_like(pub_valid)
    return (pub_valid & VERDICT_WIRE_BLOCK) != 0


@struct.dataclass
class MsgTable:
    """Rotating global message table (the interned message-id space).

    Seen-cache TTL ↔ slot-recycling conversion (survey §7 hard-part (e)):
    the reference's seen-cache is a 120 s first-seen TimeCache
    (pubsub.go:30 TimeCacheDuration) — a message id re-arriving within
    120 s is a duplicate; after expiry it would be treated as new. Here a
    message's "seen" lifetime is its SLOT lifetime: M slots recycled at
    publish rate p give a TTL of M/p rounds (the bench: 64/4 = 16 rounds;
    at the reference cadence of ~8 rounds/heartbeat-second that is ~2 s
    of simulated time). The conversion is conservative in the direction
    that matters: a slot outlives every in-flight copy of its message
    (propagation completes in ≤ ~8 hops = ≤ ~8 rounds < M/p), so no live
    duplicate is ever re-admitted as new — the failure mode the
    reference's 120 s figure exists to prevent. Configs that need a
    longer memory scale M (the TTL is M/p by construction), not a
    separate timer."""

    topic: jax.Array    # [M] i32, -1 = never used
    origin: jax.Array   # [M] i32
    birth: jax.Array    # [M] i32 round of publish, -1 = never used
    valid: jax.Array    # [M] bool — ValidationAccept (deliver + forward)
    ignored: jax.Array  # [M] bool — ValidationIgnore (drop, no P4 penalty;
                        # validation.go:46-52, score.go:768-774)
    cursor: jax.Array   # i32 — next slot to allocate (monotonic, mod M)
    wire_block: jax.Array | None = None  # [M] bool — oversized: never
                        # transmitted on any edge (VERDICT_WIRE_BLOCK;
                        # WithMaxMessageSize pubsub.go:480, sendRPC drop
                        # gossipsub.go:1126-1140); None = feature unused

    @classmethod
    def empty(cls, m: int, wire_block: bool = False) -> "MsgTable":
        return cls(
            topic=jnp.full((m,), -1, jnp.int32),
            origin=jnp.full((m,), -1, jnp.int32),
            birth=jnp.full((m,), -1, jnp.int32),
            valid=jnp.zeros((m,), bool),
            ignored=jnp.zeros((m,), bool),
            cursor=jnp.int32(0),
            wire_block=jnp.zeros((m,), bool) if wire_block else None,
        )

    @property
    def capacity(self) -> int:
        return self.topic.shape[0]


@struct.dataclass
class Delivery:
    """Per-peer message-delivery state.

    have        — the seen-cache (pubsub.go:30,146): marked on first receipt
                  whether or not validation later rejects (markSeen happens
                  inside validation, validation.go:285-293)
    fwd         — messages this peer will transmit next round (receipts
                  accepted for forwarding, or own publishes)
    first_round — round of first receipt, -1 never (propagation CDF +
                  delivery-window attribution)
    fe_words    — first-arrival edge, stored packed: bit m of row (n, k)
                  set iff the first copy of message m arrived at n on edge
                  k; no bit on any edge = published locally / never
                  received (the "source" exclusion, floodsub.go:85-88).
                  Packed storage keeps echo suppression and delivery
                  attribution in word algebra; the [N, M] edge-index form
                  is the derived `first_edge` property (host/trace/test
                  consumers — deriving it unpacks to [N,K,M]).
    """

    have: jax.Array         # [N, W] u32
    fwd: jax.Array          # [N, W] u32
    first_round: jax.Array  # [N, M] i32
    fe_words: jax.Array     # [N, K, W] u32 dense; [E, W] u32 on a
                            # CSR-RESIDENT build (round 18): states built
                            # against an edge_layout="csr" Net keep the
                            # per-edge plane flat — dead padded slots are
                            # not resident (the next memory tier in
                            # MEM_AUDIT.json). ndim distinguishes the two.
    # async-validation pipeline (survey §7 hard-part (c); the reference's
    # parallel validation workers, validation.go:123-135): receipts sit in
    # V shift stages between arrival and their validation verdict; absent
    # (None) when validation is inline (V=0)
    pending: jax.Array | None = None  # [N, V, W] u32

    @property
    def first_edge(self) -> jax.Array:
        """[N, M] i8: first-arrival edge slot per message, -1 when none
        (local publish or never received)."""
        if self.fe_words.ndim == 2:
            raise ValueError(
                "first_edge needs the dense [N, K, W] plane, but this "
                "state is CSR-resident (flat [E, W] fe_words) — densify "
                "first: state.densify_edge_planes(net, st)"
            )
        return bitset.first_edge_of(self.fe_words, self.first_round.shape[-1])

    @classmethod
    def empty(cls, n: int, m: int, k: int = 0, val_delay: int = 0,
              n_edges: int | None = None) -> "Delivery":
        """``n_edges`` selects the CSR-RESIDENT first-arrival plane:
        ``fe_words`` allocates flat ``[E, W]`` instead of ``[N, K, W]``
        (pass ``net.n_edges`` — None on a dense build, so
        ``n_edges=net.n_edges`` does the right thing for both
        layouts)."""
        w = bitset.n_words(m)
        fe_shape = (n, k, w) if n_edges is None else (n_edges, w)
        return cls(
            have=jnp.zeros((n, w), jnp.uint32),
            fwd=jnp.zeros((n, w), jnp.uint32),
            first_round=jnp.full((n, m), -1, jnp.int32),
            fe_words=jnp.zeros(fe_shape, jnp.uint32),
            pending=jnp.zeros((n, val_delay, w), jnp.uint32) if val_delay > 0 else None,
        )


@struct.dataclass
class ChaosState:
    """Device state of the chaos plane's Gilbert–Elliott link-fault
    generator (chaos/faults.py): the per-link two-state chain's bad
    plane. Kept symmetric over the edge involution by construction
    (transitions draw symmetric per-link uniforms from a symmetric
    init). Present only in states built for a GE generator
    (``ChaosConfig.needs_state``) — the i.i.d. generator and pure
    schedules are stateless (masks are functions of (key, tick), both
    already checkpointed)."""

    ge_bad: jax.Array  # [N, K] bool — link currently in the bad state

    @classmethod
    def empty(cls, n: int, k: int) -> "ChaosState":
        return cls(ge_bad=jnp.zeros((n, k), bool))


@struct.dataclass
class TopoState:
    """Device state of the DYNAMIC overlay plane (round 22,
    docs/DESIGN.md §22): the mutable mirror of the Net's edge-pool
    planes, carried in ``SimState`` so topology mutation is ordinary
    state evolution — scanned, donated, checkpointed (rides format v6
    with no version bump; presence changes the leaf count exactly like
    the chaos/telemetry planes).

    A step in dynamic mode rebinds its Net from this plane every round
    (``Net.with_overlay``) after applying the dispatch's host-compiled
    mutation batch (topo/dynamics.apply_mutation). ``epoch`` counts
    writes per slot — the chaos plane keys its per-link fault streams
    on slot×epoch so a REWIRED slot deterministically re-keys
    (chaos/faults.py) with checkpoint-exact resume.

    Static per-slot attributes (``Net.outbound``, ``Net.direct``) are
    NOT mirrored: a mutated slot keeps its build-time outbound/direct
    flag. That is the documented approximation of this plane — both
    only bias mesh selection (Dout / direct peering), never
    correctness."""

    nbr: jax.Array        # [N, K] i32, -1 absent
    nbr_ok: jax.Array     # [N, K] bool
    rev: jax.Array        # [N, K] i32
    edge_perm: jax.Array  # [N, K] i32 flat involution, absent self-point
    epoch: jax.Array      # [N, K] i32 — bumped on every slot write

    @classmethod
    def from_net(cls, net: "Net") -> "TopoState":
        # COPIES, not asarray views: the state tree is donated by every
        # step, and an aliased plane would delete the Net's own buffers
        # on the first dispatch (breaking every later eager read of the
        # net — checker construction, a second template_fn() call)
        return cls(
            nbr=jnp.array(net.nbr, jnp.int32, copy=True),
            nbr_ok=jnp.array(net.nbr_ok, bool, copy=True),
            rev=jnp.array(net.rev, jnp.int32, copy=True),
            edge_perm=jnp.array(net.edge_perm, jnp.int32, copy=True),
            epoch=jnp.zeros(net.nbr.shape, jnp.int32),
        )


@struct.dataclass
class SimState:
    """Carry for the jitted step loop (router-agnostic core)."""

    tick: jax.Array      # i32 current round
    key: jax.Array       # PRNG key
    msgs: MsgTable
    dlv: Delivery
    events: jax.Array    # [N_EVENTS] i64 cumulative trace counters
    # chaos plane: Gilbert–Elliott generator state (None = stateless
    # chaos or chaos off — the common case; like wire_block, presence
    # changes the pytree leaf count, so checkpoint templates must be
    # built with the same setting)
    chaos: ChaosState | None = None
    # telemetry plane (telemetry/panel.py): the per-round time-series
    # panel + flight recorder. None = telemetry off (the default) — the
    # state tree is leaf-identical to a pre-telemetry build, same
    # presence contract as the chaos/wire_block planes
    telem: object | None = None  # TelemetryState | None
    # dynamic overlay plane (round 22): the mutable topology mirror.
    # None = static topology (the default) — leaf-identical to a
    # pre-dynamics build, same presence contract as chaos/telem, and
    # rides checkpoint format v6 with no version bump
    topo: TopoState | None = None

    @classmethod
    def init(cls, n_peers: int, msg_slots: int, seed: int = 0, k: int = 0,
             val_delay: int = 0, wire_block: bool = False,
             chaos_ge: bool = False, telemetry=None,
             n_edges: int | None = None,
             topo: TopoState | None = None) -> "SimState":
        """`k` is the topology's padded max degree (net.max_degree) — it
        sizes the packed first-arrival-edge plane. k=0 is only for states
        that never enter a delivery round (e.g. checkpoint plumbing).
        `val_delay` > 0 adds the async-validation pipeline stages.
        `wire_block` enables the per-message oversized-transmit-block plane
        (WithMaxMessageSize support — off by default, zero hot-path cost).
        `chaos_ge` adds the Gilbert–Elliott link-fault chain plane
        (required iff the build's ChaosConfig.needs_state).
        `telemetry` (a telemetry.TelemetryConfig) allocates the on-device
        time-series panel — required iff the build's step records one.
        `n_edges` (round 18) selects the CSR-RESIDENT first-arrival plane
        ([E, W] instead of [N, K, W]) — pass ``net.n_edges``, which is
        None on dense builds so the same call works for both layouts.
        `topo` (round 22) installs the dynamic overlay plane — pass
        ``TopoState.from_net(net)`` for a mutable-topology build."""
        if telemetry is not None:
            from .telemetry.panel import TelemetryState

            telem = TelemetryState.empty(telemetry)
        else:
            telem = None
        return cls(
            tick=jnp.int32(0),
            key=jax.random.key(seed),
            msgs=MsgTable.empty(msg_slots, wire_block=wire_block),
            dlv=Delivery.empty(n_peers, msg_slots, k, val_delay,
                               n_edges=n_edges),
            events=zero_counters(),
            chaos=ChaosState.empty(n_peers, k) if chaos_ge else None,
            telem=telem,
            topo=topo,
        )


# ---------------------------------------------------------------------------
# CSR-resident plane conversion (round 18)
#
# States built against an edge_layout="csr" Net keep their per-edge
# planes FLAT at rest — Delivery.fe_words as [E, W], and the gossipsub
# control tier (served_lo/served_hi as [E, W], peerhave/iasked as [E]).
# The core delivery engine consumes the flat fe plane natively
# (models/common.delivery_round's flat commit); the gossipsub control
# plane is written against the dense [N, K, ...] views, so its steps
# densify at entry and re-pack at exit (wrap_csr_resident below) — the
# RESIDENT tier (scan carries, checkpoints, HBM at rest) is flat, the
# in-step temporaries are the same dense intermediates the dense build
# materializes anyway (the transmit tensor is [N, K, W] in both).
# Exactness: every dense per-edge plane is zero on absent slots by
# construction (their update masks are nbr_ok/acc_ok-gated), so
# pack -> unpack round-trips bit-exactly and dense-vs-CSR state parity
# holds under unpacking (tests/test_csr.py).


#: leaf-path suffixes of the CSR-resident tier — the ONLY sanctioned
#: layout-dependent leaves, named ONCE next to the pack/unpack code
#: that moves them. Word planes ride [E, W] flat, counters ride [E].
#: analysis.guards derives the csr schema variant from these and
#: scripts/memstat.py prices the tier off them, so adding the next
#: flat plane here updates the schema guard and the memory audit
#: together (or trips them, which is the point).
CSR_RESIDENT_WORD_PLANES = (".fe_words", ".served_lo", ".served_hi")
CSR_RESIDENT_COUNTERS = (".peerhave", ".iasked")
#: the router latency ring (routers/latency.py, docs/DESIGN.md §24c):
#: an edge word plane with an interior L axis — [E, L, W] flat,
#: [N, K, L, W] dense; priced as L word planes by memstat
CSR_RESIDENT_RING_PLANES = (".inflight",)
CSR_RESIDENT_SUFFIXES = (
    CSR_RESIDENT_WORD_PLANES + CSR_RESIDENT_COUNTERS
    + CSR_RESIDENT_RING_PLANES
)


def densify_edge_planes(net: "Net", st):
    """CSR-resident flat planes -> their transient dense forms.
    Accepts a SimState or a gossipsub-like state (anything with
    ``.core`` plus the served/peerhave planes); a state already dense
    passes through unchanged (idempotent)."""
    gossip = hasattr(st, "core")
    core = st.core if gossip else st
    core = core.replace(dlv=core.dlv.replace(
        fe_words=(net.unpack_edges(core.dlv.fe_words)
                  if core.dlv.fe_words.ndim == 2 else core.dlv.fe_words)))
    if not gossip:
        return core
    st = st.replace(core=core)
    if getattr(st, "served_lo", None) is not None and st.served_lo.ndim == 2:
        st = st.replace(
            served_lo=net.unpack_edges(st.served_lo),
            served_hi=net.unpack_edges(st.served_hi),
            peerhave=net.unpack_edges(st.peerhave),
            iasked=net.unpack_edges(st.iasked),
        )
    # the router latency ring carries its own ndim check: it exists on a
    # different static branch (cfg.router) than the served planes
    if getattr(st, "inflight", None) is not None and st.inflight.ndim == 3:
        st = st.replace(inflight=net.unpack_edges(st.inflight))
    return st


def flatten_edge_planes(net: "Net", st):
    """Dense per-edge planes -> the CSR-resident flat forms (the
    inverse of :func:`densify_edge_planes`; exact — dense absent slots
    are zero by construction). Idempotent."""
    gossip = hasattr(st, "core")
    core = st.core if gossip else st
    core = core.replace(dlv=core.dlv.replace(
        fe_words=(net.pack_edges(core.dlv.fe_words)
                  if core.dlv.fe_words.ndim == 3 else core.dlv.fe_words)))
    if not gossip:
        return core
    st = st.replace(core=core)
    if getattr(st, "served_lo", None) is not None and st.served_lo.ndim == 3:
        st = st.replace(
            served_lo=net.pack_edges(st.served_lo),
            served_hi=net.pack_edges(st.served_hi),
            peerhave=net.pack_edges(st.peerhave),
            iasked=net.pack_edges(st.iasked),
        )
    if getattr(st, "inflight", None) is not None and st.inflight.ndim == 4:
        st = st.replace(inflight=net.pack_edges(st.inflight))
    return st


def wrap_csr_resident(net: "Net", fn):
    """Wrap an engine's round/phase body for a CSR-resident state:
    densify the flat planes at entry, run the dense-written body
    unchanged, re-pack at exit. The wrapped body is what the engine
    factories jit, so the scan carry (and every checkpoint cut from it)
    stays flat while in-step temporaries are dense."""
    import functools

    @functools.wraps(fn)
    def wrapped(st, *args, **kwargs):
        out = fn(densify_edge_planes(net, st), *args, **kwargs)
        return flatten_edge_planes(net, out)

    return wrapped


# ---------------------------------------------------------------------------
# publish-slot allocation


class PhasePubPlan:
    """Phase-head batched publish allocation (round-7 tentpole).

    ``allocate_publishes`` called once per sub-round pays ~15 tiny
    kernels each time — the [M]-table scatters, the cursor scalar chain,
    the cumsum/remainder index math — and at the 12.5k shard that swarm
    of launches IS the round budget (docs/PERF.md: fixed per-fusion
    overhead dominates below ~25k). The phase engine knows its whole
    ``[r, P]`` schedule at the head, and slot assignment depends only on
    (cursor, schedule), so every per-sub-round quantity is computable
    up front as ONE set of wide ops:

      * ``sidx/is_pub [r, P]`` — slot per publish (``m`` on padding);
      * ``keep_w [r, W]`` / ``reused [r, M]`` — recycled-slot masks;
      * ``pub_words [r, N, W]`` — origin seen/fwd bits, one batched
        scatter for the whole phase;
      * message-table SNAPSHOTS ``[r+1, M]`` (last-write-wins over the
        flattened schedule): ``msgs_at(i)`` is bit-identical to the
        table ``allocate_publishes`` would have produced after the
        publishes of sub-rounds ``< i`` — the loop reads ``msgs_at(i)``
        during sub-round ``i`` and the tail commits ``msgs_at(r)``.

    The delivery-state folds (have/fwd/fe/pending keep-clears, the
    first_round stamp) still run per sub-round — they mix with evolving
    delivery state — but as wide word ops fed by the precomputed masks,
    not as fresh index math. Exactness: the snapshot recurrence IS the
    scatter recurrence (last write wins, pads dropped), pinned by
    tests/test_phase_stacked.py against the legacy path."""

    @stages.scope("pub_plan")
    def __init__(self, msgs: MsgTable, n_peers: int, tick0,
                 pub_origin: jax.Array, pub_topic: jax.Array,
                 pub_valid: jax.Array, pub_holder: jax.Array | None = None):
        r, p = pub_origin.shape
        m = msgs.capacity
        # distinct slots within one sub-round keep the batched word
        # scatter add-exact (same precondition allocate_publishes'
        # scatter form documents)
        assert m >= p, f"msg_slots {m} < publish width {p}"
        w = bitset.n_words(m)
        self.r, self.m, self.w = r, m, w
        self.msgs0 = msgs
        pub_valid = jnp.asarray(pub_valid)
        accept, ignored = decode_verdicts(pub_valid)       # [r, P]
        self.accept = accept
        rp = r * p
        flat_pub = (pub_origin >= 0).reshape(-1)           # [rP]
        self.is_pub = flat_pub.reshape(r, p)
        gpos = jnp.cumsum(flat_pub.astype(jnp.int32)) - 1
        sidx_flat = jnp.where(flat_pub, (msgs.cursor + gpos) % m, m)
        self.sidx = sidx_flat.reshape(r, p)
        counts = jnp.sum(self.is_pub.astype(jnp.int32), axis=1)  # [r]
        self.cursor_at = msgs.cursor + jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)]
        )  # [r+1]

        # last-write-wins snapshots over the flattened schedule
        eq = sidx_flat[:, None] == jnp.arange(m, dtype=jnp.int32)[None, :]
        jidx = jnp.where(eq, jnp.arange(rp, dtype=jnp.int32)[:, None], -1)
        incl = jax.lax.cummax(jnp.max(jidx.reshape(r, p, m), axis=1), axis=0)
        # lastw[i]: last flat writer of each slot among sub-rounds < i
        self._lastw = jnp.concatenate(
            [jnp.full((1, m), -1, jnp.int32), incl], axis=0
        )  # [r+1, M]
        self.reused = jnp.any(eq.reshape(r, p, m), axis=1)  # [r, M]
        self.keep_w = ~bitset.pack(self.reused)             # [r, W]

        flat_tick = tick0 + jnp.arange(rp, dtype=jnp.int32) // p
        self._topic = self._snap(msgs.topic, pub_topic.reshape(-1))
        self._origin = self._snap(msgs.origin, pub_origin.reshape(-1))
        self._birth = self._snap(msgs.birth, flat_tick)
        self._valid = self._snap(msgs.valid, accept.reshape(-1))
        self._ignored = self._snap(msgs.ignored, ignored.reshape(-1))
        self._wire_block = (
            self._snap(msgs.wire_block, decode_wire_block(pub_valid).reshape(-1))
            if msgs.wire_block is not None else None
        )
        # per-sub-round packed planes every loop iteration reads
        self.valid_words = bitset.pack(self._valid)         # [r+1, W]
        self.ignored_words = bitset.pack(self._ignored)

        # the publishes somebody holds. A churn build hands in
        # ``pub_holder`` [r, P]: ``pub_origin`` with -1 where the origin is
        # DOWN. Such a publish keeps its slot, as the ring says, and nobody
        # holds the message, the origin included (a stopped process
        # publishes nothing)
        held = (flat_pub if pub_holder is None
                else (pub_holder >= 0).reshape(-1))
        # origin publish-bit planes, ONE batched scatter for the phase
        # (distinct slots per sub-round => distinct bits, add == or)
        row_flat = jnp.where(held, pub_origin.reshape(-1), n_peers)
        self.rows = row_flat.reshape(r, p)  # [r, P], N on padding
        i_flat = jnp.arange(rp, dtype=jnp.int32) // p
        bit = jnp.uint32(1) << (sidx_flat % bitset.WORD).astype(jnp.uint32)
        self.pub_words = jnp.zeros((r, n_peers, w), jnp.uint32).at[
            i_flat, row_flat, sidx_flat // bitset.WORD
        ].add(bit, mode="drop")  # [r, N, W]

    def _snap(self, tbl0: jax.Array, vals_flat: jax.Array) -> jax.Array:
        picked = vals_flat[jnp.clip(self._lastw, 0)]        # [r+1, M]
        return jnp.where(self._lastw >= 0, picked, tbl0[None, :])

    @stages.scope("pub_plan")
    def msgs_at(self, i: int) -> MsgTable:
        """The message table as of sub-round ``i`` (after the publishes
        of sub-rounds < i); ``msgs_at(r)`` is the phase-final table."""
        return self.msgs0.replace(
            topic=self._topic[i],
            origin=self._origin[i],
            birth=self._birth[i],
            valid=self._valid[i],
            ignored=self._ignored[i],
            cursor=self.cursor_at[i],
            wire_block=(
                self._wire_block[i] if self._wire_block is not None else None
            ),
        )

    @stages.scope("pub_plan")
    def apply_to_delivery(self, dlv: "Delivery", i: int, tick_i,
                          scatter_form: bool) -> "Delivery":
        """Sub-round ``i``'s recycled-slot clears + origin seen/fwd/
        first_round stamps on the delivery state — the dlv half of
        ``allocate_publishes``, fed by the precomputed masks (wide word
        folds only; bit-identical to the per-sub-round scatter path).
        ``scatter_form`` chooses as in allocate_publishes (both forms are
        exact-equivalent)."""
        keep = self.keep_w[i]
        pw = self.pub_words[i]
        n_peers = dlv.have.shape[0]
        if scatter_form:
            # the column scatter composing clear + stamp (see
            # allocate_publishes' scatter-form measurements)
            col_vals = jnp.where(
                jnp.arange(n_peers, dtype=jnp.int32)[:, None]
                == self.rows[i][None, :],
                jnp.broadcast_to(tick_i, (n_peers, self.sidx.shape[1])), -1,
            )
            first_round = dlv.first_round.at[:, self.sidx[i]].set(
                col_vals, mode="drop"
            )
        else:
            pub_bits = bitset.unpack(pw, self.m)            # [N, M]
            reused_b = self.reused[i]
            first_round = jnp.where(
                pub_bits, jnp.broadcast_to(tick_i, pub_bits.shape),
                jnp.where(reused_b[None, :], -1, dlv.first_round),
            )
        fe_words, pending = bitset.masked_keep(
            [dlv.fe_words, dlv.pending], keep
        )
        return dlv.replace(
            have=(dlv.have & keep[None, :]) | pw,
            fwd=(dlv.fwd & keep[None, :]) | pw,
            first_round=first_round,
            fe_words=fe_words,
            pending=pending,
        )

#: peers from which the phase engine takes the scatter form of the publish
#: stamp (allocate_publishes' docstring has both forms and the readings);
#: the per-round engine keeps the plane form at every N
SCATTER_FORM_MIN_PEERS = 20_000


@stages.scope("pub_plan")
def allocate_publishes(
    msgs: MsgTable,
    dlv: Delivery,
    tick: jax.Array,
    pub_origin: jax.Array,  # [P] i32, -1 pad
    pub_topic: jax.Array,   # [P] i32
    pub_valid: jax.Array,   # [P] bool accept, or int VERDICT_* codes
    scatter_form: bool | None = None,
    stacked_clears: bool = False,
    pub_holder: jax.Array | None = None,
):
    """Intern this round's publishes into table slots (rotating cursor),
    clearing recycled slots' bit columns everywhere.

    ``pub_holder`` (churn builds: ``pub_origin`` with -1 where the origin
    is DOWN) takes such a publish off the delivery state: its slot is
    allocated as the ring says, and nobody holds the message, the origin
    included (a stopped process publishes nothing); ``pub_words`` leaves
    it out too.

    ``stacked_clears`` runs the four recycled-slot keep-ANDs (have / fwd
    / fe_words / pending) as ONE concatenated fold (bitset.masked_keep)
    instead of four kernels — the round-7 stacked-plane form, on by
    default for every router step (floodsub, randomsub, the per-round
    gossipsub step via ``cfg.wire_coalesced``); False keeps the legacy
    per-plane kernels for A/B (bit-identical either way — the parity
    suite tests/test_phase_stacked.py compares full state trees).

    Returns (msgs, dlv, slots, is_pub): `slots[P]` the assigned slot per
    publish (undefined where ~is_pub).

    Two exact-equivalent forms for the first_round/pub_words updates
    (``scatter_form``; tests/test_ops.py runs a phase build under each
    and compares every state plane):

      * scatter form: the recycled-column clear + origin stamp as ONE
        <=P-column scatter, pub_words as a P-element word scatter. The
        plane form's where(reused)/one-hot+pack reads and writes the
        whole [N, M] s32 plane (~50 MB of HBM traffic at N=100k/M=64)
        to touch at most P columns — profiled 42 us/sub-round, 7% of
        the phase round. The PHASE engine selects it at
        N >= SCATTER_FORM_MIN_PEERS:
        +6-11% on the N=100k bench (r=8: 1424 -> 1559; r=16: 1691 ->
        1882 rounds/s, round 5).
      * plane form (default): scatters carry a fixed per-op cost that
        dominates below ~20k peers (the 12.5k shard bench loses ~9%
        under scatters), and the PER-ROUND step prefers the plane form
        even at N=100k (405 vs 378 ticks/s) — its [N, M] selects fuse
        with the surrounding per-round [N, M] work that the phase
        sub-round doesn't have. Callers that profile a win opt in.
    """
    m = msgs.capacity
    pub_valid = jnp.asarray(pub_valid)
    accept, ignored = decode_verdicts(pub_valid)
    is_pub = pub_origin >= 0
    pos = jnp.cumsum(is_pub.astype(jnp.int32)) - 1
    slots = (msgs.cursor + pos) % m
    count = jnp.sum(is_pub.astype(jnp.int32))

    # scatter index M (out of bounds, mode=drop) for padding entries
    sidx = jnp.where(is_pub, slots, m)

    n_peers = dlv.have.shape[0]
    if scatter_form is None:
        scatter_form = False

    # clear recycled slots: bit columns in have/fwd/fe, rows in first_round
    reused = jnp.zeros((m,), bool).at[sidx].set(True, mode="drop")
    reused_words = bitset.pack(reused)
    keep = ~reused_words
    # the publishes somebody holds: in a churn build, not a down origin's
    held = is_pub if pub_holder is None else pub_holder >= 0
    if scatter_form:
        # ONE column scatter does both the recycled-column clear and the
        # origin stamp: column j of the update is -1 everywhere except
        # the publishing origin's row, which takes the tick (the
        # composition of the plane form's clear-then-stamp pair); the
        # holder's row is out of bounds (dropped) where nobody holds it
        row = jnp.where(held, pub_origin, n_peers)
        col_vals = jnp.where(
            jnp.arange(n_peers, dtype=jnp.int32)[:, None] == row[None, :],
            jnp.broadcast_to(tick, (n_peers, sidx.shape[0])), -1,
        )
        first_round = dlv.first_round.at[:, sidx].set(col_vals, mode="drop")
    else:
        first_round = jnp.where(reused[None, :], -1, dlv.first_round)
    if stacked_clears:
        have_c, fwd_c, fe_c, pending_c = bitset.masked_keep(
            [dlv.have, dlv.fwd, dlv.fe_words, dlv.pending], keep
        )
    else:
        have_c = dlv.have & keep[None, :]
        fwd_c = dlv.fwd & keep[None, :]
        # trailing-dim broadcast covers both the dense [N, K, W] and the
        # CSR-resident flat [E, W] first-arrival plane
        fe_c = dlv.fe_words & keep
        pending_c = (
            dlv.pending & keep[None, None, :]
            if dlv.pending is not None else None
        )
    dlv = dlv.replace(
        have=have_c,
        fwd=fwd_c,
        first_round=first_round,
        fe_words=fe_c,
        pending=pending_c,
    )

    msgs = msgs.replace(
        topic=msgs.topic.at[sidx].set(pub_topic, mode="drop"),
        origin=msgs.origin.at[sidx].set(pub_origin, mode="drop"),
        birth=msgs.birth.at[sidx].set(jnp.broadcast_to(tick, pub_topic.shape), mode="drop"),
        valid=msgs.valid.at[sidx].set(accept, mode="drop"),
        ignored=msgs.ignored.at[sidx].set(ignored, mode="drop"),
        cursor=msgs.cursor + count,
        wire_block=(
            msgs.wire_block.at[sidx].set(decode_wire_block(pub_valid), mode="drop")
            if msgs.wire_block is not None else None
        ),
    )

    # origin peers: mark seen + schedule forwarding (+ the first_round
    # stamp in the plane form; the scatter form's stamp rode the column
    # scatter above). Scatter form: distinct slots => distinct bits, so
    # the word add is exact even when two publishes of one origin share
    # a word; padding drops via the OOB row (sidx alone can be in-bounds
    # when m % 32 != 0).
    if scatter_form:
        # (a fused [N, W] P-step compare-fold for pub_words was tried
        # against this word scatter and measured WORSE — r=8 bench 1754
        # -> 1695: the fold's per-row compares ride every consumer of
        # the have/fwd ORs, while the scatter's ~35 us launch cost is
        # paid once and its output fuses cleanly)
        bit = jnp.uint32(1) << (sidx % bitset.WORD).astype(jnp.uint32)
        pub_words = jnp.zeros((n_peers, bitset.n_words(m)), jnp.uint32).at[
            row, sidx // bitset.WORD
        ].add(bit, mode="drop")
        dlv = dlv.replace(
            have=dlv.have | pub_words,
            fwd=dlv.fwd | pub_words,
            # first_edge stays -1 for local publishes
        )
    else:
        pub_bits = jnp.zeros((n_peers, m), bool).at[
            pub_origin if pub_holder is None
            else jnp.where(held, pub_origin, n_peers), sidx
        ].set(True, mode="drop")
        pub_words = bitset.pack(pub_bits)
        dlv = dlv.replace(
            have=dlv.have | pub_words,
            fwd=dlv.fwd | pub_words,
            first_round=jnp.where(
                pub_bits, jnp.broadcast_to(tick, pub_bits.shape),
                dlv.first_round,
            ),
            # first_edge stays -1 for local publishes
        )
    # keep-mask for recycled slots so routers can clear their own per-slot
    # state (mcache windows, gossip outboxes, promises)
    return msgs, dlv, slots, is_pub, keep, pub_words


def hops(msgs: MsgTable, dlv: Delivery) -> jax.Array:
    """Propagation hop count per (peer, msg): 0 at the origin, k for a peer
    first reached k hops later; -1 if never received. A message published at
    round r reaches 1-hop neighbors in round r+1."""
    h = dlv.first_round - msgs.birth[None, :]
    return jnp.where((dlv.first_round >= 0) & (msgs.birth >= 0)[None, :], h, -1)
