"""Invariant oracle plane: the verification literature's safety/liveness
properties as vectorized on-device predicates over state trees
(docs/DESIGN.md §12).

The ACL2s GossipSub verification (arXiv:2311.08859) and the FloodSub
correctness formalization (arXiv:2507.19013) state what these protocols
must *always* satisfy — no self-graft, mesh ⊆ topology ∩ subscription,
backoff respected, graylisted peers excluded, seen-cache consistency,
eventual delivery after a heal. Trace parity and CDF bands check that a
run matches the Go reference; this module checks that a run conforms to
the *protocol spec*, machine-checkably, inside runs we already execute:
each property is one masked predicate over the dense state planes
reduced with a single ``jnp.all``, evaluated every ``check_every``
dispatches by a separately jitted checker (one compile of its own, zero
host transfers in the run window — results accumulate as device bools
and are read back after the run, scan-output style).

Fault composition (the grace/due contract): faults relax exactly the
clauses the papers scope out. Mesh degree bounds suspend while a
scheduled partition (or churn storm) is active and for a declared grace
window after it changes (``due[GRACE]``); eventual delivery is an
infinite-horizon statement under fair loss, so its finite-horizon
runtime check applies only to messages whose whole propagation window
``[birth, birth + W]`` sits inside a declared QUIET interval (no
scheduled faults, no active flap generator), plus the papers'
heal-liveness clause: partition-era messages still inside the mcache
history at heal must be fully delivered by a post-heal deadline
(``due[R_*]``). The sustained-flap band keeps every safety property
live and leaves the delivery-liveness clause vacuous — by design, not
omission (GossipSub's delivery under unbounded loss is probabilistic;
the paired chaos-smoke band gates cover it statistically).

Elision contract: invariants are observers, never participants — the
checker is a separate jitted program over a *read-only* view of the
live state (no donation), the engine steps are untouched, and a run
without a hook traces the exact pre-oracle program (the chaos-off
kernel census equality `make oracle-smoke` re-asserts).

Registration is literal on purpose: analysis/simlint.py's
``invariant-registry`` rule parses the ``@invariant(...)`` calls below
and fails lint if a property omits its engine applicability or is not
referenced by a seeded-violation negative test in tests/.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

#: the engines a property may declare applicability for (the four
#: routers; "phase" is the multi-round gossipsub engine — it shares
#: GossipSubState, so every gossipsub-state property applies, checked
#: at phase boundaries)
ENGINES = ("gossipsub", "phase", "floodsub", "randomsub")

#: applicability aliases (module-level literals — the invariant-registry
#: lint rule resolves these names when checking declarations)
CORE_ENGINES = ("gossipsub", "phase", "floodsub", "randomsub")
GOSSIP_ENGINES = ("gossipsub", "phase")

#: due-vector layout (i32[7], device): the host-known schedule context a
#: check runs under. -1 sentinels disable a clause.
#:   QUIET_LO/QUIET_HI — fresh-publish eventual-delivery window: a valid
#:       message is due iff birth >= QUIET_LO and birth + W <= QUIET_HI
#:       and birth + W <= tick (its whole propagation window was quiet);
#:   R_LO/R_HI/R_DEADLINE — heal-recovery clause: messages born in
#:       [R_LO, R_HI] (the in-mcache-at-heal window) are due once
#:       tick >= R_DEADLINE;
#:   GRACE — 1 suspends the fault-scoped clauses (mesh degree bounds,
#:       heal re-formation) while faults are active / recently changed.
DUE_QUIET_LO = 0
DUE_QUIET_HI = 1
DUE_R_LO = 2
DUE_R_HI = 3
DUE_R_DEADLINE = 4
DUE_GRACE = 5
#: round-22 dynamic overlay: 1 while a topology-mutation batch landed
#: inside this check's window — the mutation-aware invariants
#: (mesh-in-topology, first-edge-wf) grace the one-check re-peering
#: transient instead of mis-flagging state keyed to pre-mutation edges
DUE_MUT_GRACE = 6
DUE_LEN = 7


def due_vector(quiet=None, recover=None, grace: bool = False,
               mut_grace: bool = False) -> np.ndarray:
    """Host-side due-vector builder. ``quiet`` is ``(lo, hi)`` — the
    quiet interval for the fresh-publish delivery clause; ``recover``
    is ``(born_lo, born_hi, deadline)`` — the heal-recovery clause;
    ``grace`` suspends the fault-scoped safety clauses; ``mut_grace``
    suspends the mutation-scoped clauses around topology-mutation
    ticks (topo/dynamics.MutationSchedule.due_fn sets it)."""
    out = np.full((DUE_LEN,), -1, np.int32)
    if quiet is not None:
        out[DUE_QUIET_LO], out[DUE_QUIET_HI] = int(quiet[0]), int(quiet[1])
    if recover is not None:
        out[DUE_R_LO] = int(recover[0])
        out[DUE_R_HI] = int(recover[1])
        out[DUE_R_DEADLINE] = int(recover[2])
    out[DUE_GRACE] = 1 if grace else 0
    out[DUE_MUT_GRACE] = 1 if mut_grace else 0
    return out


class InvariantConfigError(ValueError):
    """Raised by InvariantConfig.validate() on invalid parameters."""


@dataclasses.dataclass(frozen=True)
class InvariantConfig:
    """Static checker configuration (frozen/hashable — it closes over
    the jitted checker like the engine configs ride static args).

    ``delivery_window`` is W, the rounds a due message gets to reach
    every subscribed up peer (size it past the overlay diameter plus
    the validation-pipeline depth); ``check_every`` is the hook cadence
    in DISPATCHES (per-round engines: rounds; the phase engine: phases
    — the same cadence caveat the drain and chaos metrics document);
    ``names`` restricts the checked property subset (None = all
    applicable to the engine)."""

    delivery_window: int = 12
    check_every: int = 8
    names: tuple | None = None

    def validate(self) -> None:
        if self.delivery_window < 1:
            raise InvariantConfigError(
                f"delivery_window must be >= 1, got {self.delivery_window}")
        if self.check_every < 1:
            raise InvariantConfigError(
                f"check_every must be >= 1, got {self.check_every}")
        if self.names is not None:
            unknown = [n for n in self.names if n not in REGISTRY]
            if unknown:
                raise InvariantConfigError(
                    f"unknown invariant names: {unknown}; registered: "
                    f"{list(REGISTRY)}")


@dataclasses.dataclass(frozen=True)
class Invariant:
    """One registered property: a predicate over a check context that
    reduces to a single bool (True = the property holds)."""

    name: str
    kind: str        # "safety" | "liveness"
    engines: tuple   # subset of ENGINES
    doc: str         # one-line statement + paper citation
    fn: object = dataclasses.field(compare=False, repr=False)


#: the ordered property registry (insertion order IS the checker's
#: output order)
REGISTRY: dict[str, Invariant] = {}


def invariant(name: str, *, kind: str, engines: tuple, doc: str):
    """Register a property. ``engines`` declares applicability (the
    invariant-registry lint rule enforces a literal, known, non-empty
    declaration and a seeded-violation negative test per name)."""
    if kind not in ("safety", "liveness"):
        raise ValueError(f"{name}: kind must be safety|liveness, got {kind}")
    bad = [e for e in engines if e not in ENGINES]
    if bad or not engines:
        raise ValueError(f"{name}: engine applicability {engines!r} must be "
                         f"a non-empty subset of {ENGINES}")

    def deco(fn):
        if name in REGISTRY:
            raise ValueError(f"duplicate invariant {name!r}")
        REGISTRY[name] = Invariant(name=name, kind=kind,
                                   engines=tuple(engines), doc=doc, fn=fn)
        return fn

    return deco


def invariant_names(engine: str, names: tuple | None = None) -> tuple:
    """The ordered property names the checker evaluates for ``engine``."""
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; one of {ENGINES}")
    out = tuple(n for n, inv in REGISTRY.items()
                if engine in inv.engines and (names is None or n in names))
    return out


# ---------------------------------------------------------------------------
# check context


@dataclasses.dataclass
class Ctx:
    """Per-trace check context (plain container, not a pytree — built
    fresh inside the checker trace)."""

    engine: str
    net: object              # state.Net
    cfg: object              # GossipSubConfig | None (mesh engines)
    inv: "InvariantConfig"
    state: object            # SimState | GossipSubState
    core: object             # SimState
    gs: object               # GossipSubState | None
    tick: jax.Array          # i32 (post-step: rounds executed so far)
    due: jax.Array           # i32[DUE_LEN]
    prev_events: jax.Array   # [N_EVENTS] i32 (last check's counters)
    nbr_sub: object          # [N,S,K] bool static mesh-eligibility const
    up: jax.Array            # [N] bool effective liveness


def _mesh_eligible_const(net) -> jax.Array:
    """[N,S,K] static: neighbor k is a legal mesh member for my slot s —
    present edge, both ends mesh-capable (/meshsub/*), neighbor
    subscribed to the slot's topic, slot live. The receiver-side
    transcription of the heartbeat candidate filter's static part
    (gossipsub.go:1374-1380)."""
    from ..models.gossipsub import gather_nbr_subscribed

    mesh_capable = (net.protocol[jnp.clip(net.nbr, 0)] >= 1) & net.nbr_ok
    return (gather_nbr_subscribed(net) & mesh_capable[:, None, :]
            & (net.protocol >= 1)[:, None, None])


def _core_of(state):
    return state.core if hasattr(state, "core") else state


def _pad_word_mask(m: int) -> np.ndarray | None:
    """[W] u32 mask of padding bits (bit positions >= m) in a packed
    word plane, or None when m fills its words exactly."""
    from ..ops import bitset

    w = bitset.n_words(m)
    if m == w * bitset.WORD:
        return None
    valid = np.zeros((w * bitset.WORD,), bool)
    valid[:m] = True
    words = np.zeros((w,), np.uint32)
    for i in range(w * bitset.WORD):
        if not valid[i]:
            words[i // bitset.WORD] |= np.uint32(1) << np.uint32(
                i % bitset.WORD)
    return words


def _expected_receivers(ctx) -> jax.Array:
    """[N, M] bool: peer n is an expected receiver of live message m —
    subscribed to its topic, currently up, and not the origin (the
    origin's copy is its own; floodsub.go:85-88)."""
    msgs = ctx.core.msgs
    n = ctx.net.subscribed.shape[0]
    live = msgs.birth >= 0
    topic = jnp.clip(msgs.topic, 0)
    origin = jnp.clip(msgs.origin, 0, n - 1)
    sub = ctx.net.subscribed[:, topic]                       # [N, M]
    is_origin = jnp.arange(n, dtype=jnp.int32)[:, None] == origin[None, :]
    return sub & live[None, :] & ~is_origin & ctx.up[:, None]


# ---------------------------------------------------------------------------
# core-state properties (all four engines)


@invariant(
    "msgtable-wf", kind="safety", engines=CORE_ENGINES,
    doc="message-table slot consistency: live slots carry a legal "
        "(topic, origin, birth) triple, verdicts are exclusive, and "
        "first-receipt stamps lie in [birth, tick] (the interned "
        "message-id space FloodSub's dedup argument relies on, "
        "arXiv:2507.19013 §seen-cache)")
def _msgtable_wf(ctx) -> jax.Array:
    msgs = ctx.core.msgs
    n = ctx.net.subscribed.shape[0]
    t_dim = ctx.net.subscribed.shape[1]
    live = msgs.birth >= 0
    ok = jnp.all((msgs.topic >= 0) == live)
    ok &= jnp.all((msgs.origin >= 0) == live)
    ok &= jnp.all(jnp.where(live, msgs.topic < t_dim, True))
    ok &= jnp.all(jnp.where(live, msgs.origin < n, True))
    ok &= ~jnp.any(msgs.valid & msgs.ignored)
    fr = ctx.core.dlv.first_round
    stamped = fr >= 0
    ok &= jnp.all(jnp.where(stamped, live[None, :], True))
    ok &= jnp.all(jnp.where(stamped, fr >= msgs.birth[None, :], True))
    ok &= jnp.all(jnp.where(stamped, fr <= ctx.tick, True))
    return ok


@invariant(
    "fwd-subset-have", kind="safety", engines=CORE_ENGINES,
    doc="no forward of an unseen slot: the forward set is a subset of "
        "the seen-cache (markSeen precedes any forward, "
        "validation.go:285-293; arXiv:2507.19013 dedup soundness)")
def _fwd_subset_have(ctx) -> jax.Array:
    dlv = ctx.core.dlv
    return ~jnp.any(dlv.fwd & ~dlv.have)


@invariant(
    "first-edge-wf", kind="safety", engines=CORE_ENGINES,
    doc="first-arrival attribution well-formedness: at most one "
        "first-arrival edge per (peer, message), and every attributed "
        "message is in the seen-cache (the delivery-attribution plane "
        "P3/P7 scoring reads); mutation-aware — graced inside the "
        "DUE_MUT_GRACE window around topology-mutation ticks")
def _first_edge_wf(ctx) -> jax.Array:
    dlv = ctx.core.dlv
    fe = dlv.fe_words                    # [N, K, W] ([E, W] CSR-resident)
    if fe.ndim == 2:
        # CSR-resident flat plane (round 18): the checker never donates
        # and runs off the hot path, so the transient unpack is fine
        fe = ctx.net.unpack_edges(fe)
    k_dim = fe.shape[1]
    acc = jnp.zeros_like(dlv.have)
    multi = jnp.zeros_like(dlv.have)
    for k in range(k_dim):               # K is a small static axis
        multi = multi | (acc & fe[:, k])
        acc = acc | fe[:, k]
    ok = ~jnp.any(multi) & ~jnp.any(acc & ~dlv.have)
    return (ctx.due[DUE_MUT_GRACE] != 0) | ok


@invariant(
    "edge-involution-wf", kind="safety", engines=CORE_ENGINES,
    doc="the edge pool is structurally sound: edge_perm is a "
        "self-inverse permutation, absent slots self-point, present "
        "slots are partner-consistent (reverse present and pointing "
        "back, no self-edges, indices in range) — the involution "
        "contract every masked gather assumes, which dynamic-overlay "
        "mutation must preserve batch by batch (arXiv:1507.08417 "
        "dynamic-complex-network dissemination regime)")
def _edge_involution_wf(ctx) -> jax.Array:
    from ..ops import edges as _ops_edges

    topo = getattr(ctx.core, "topo", None)
    if topo is None:
        # frozen overlay: the planes are trace constants validated once
        # at Net.build — nothing on device can corrupt them, and
        # auditing them here would only knock-on every net-corrupting
        # seeded negative in tests/test_invariants.py
        return jnp.bool_(True)
    net = ctx.net  # already overlay-rebound for dynamic states
    ok = _ops_edges.involution_wf(net.nbr, net.rev, net.nbr_ok,
                                  net.edge_perm)
    return ok & jnp.all(topo.epoch >= 0)


@invariant(
    "word-padding-wf", kind="safety", engines=CORE_ENGINES,
    doc="packed-word bitset well-formedness: padding bits beyond the "
        "message capacity are zero in every word plane (a set padding "
        "bit silently corrupts popcounts and keep-folds)")
def _word_padding_wf(ctx) -> jax.Array:
    m = ctx.core.msgs.capacity
    pad = _pad_word_mask(m)
    if pad is None:
        return jnp.bool_(True)
    pad = jnp.asarray(pad)
    dlv = ctx.core.dlv
    planes = [dlv.have, dlv.fwd, dlv.fe_words]
    if dlv.pending is not None:
        planes.append(dlv.pending)
    if ctx.gs is not None:
        planes += [ctx.gs.mcache, ctx.gs.ihave_out, ctx.gs.iwant_out,
                   ctx.gs.served_lo, ctx.gs.served_hi]
    ok = jnp.bool_(True)
    for p in planes:
        ok &= ~jnp.any(p & pad)
    return ok


@invariant(
    "events-monotone", kind="safety", engines=CORE_ENGINES,
    doc="cumulative trace counters never decrease between checks — the "
        "runtime face of 'score/misbehaviour counters are monotone on "
        "recorded events' (arXiv:2311.08859 counter lemmas)")
def _events_monotone(ctx) -> jax.Array:
    return jnp.all(ctx.core.events >= ctx.prev_events)


@invariant(
    "eventual-delivery", kind="liveness", engines=CORE_ENGINES,
    doc="window-checked eventual delivery: a validated publish whose "
        "whole W-round propagation window was fault-quiet has reached "
        "every subscribed up peer; partition-era messages still in "
        "mcache at heal deliver by the post-heal deadline "
        "(arXiv:2507.19013 fair-loss delivery; arXiv:2311.08859 "
        "heal-liveness, scoped per docs/DESIGN.md §12)")
def _eventual_delivery(ctx) -> jax.Array:
    msgs = ctx.core.msgs
    w = jnp.int32(ctx.inv.delivery_window)
    due = ctx.due
    birth = msgs.birth
    quiet_on = due[DUE_QUIET_LO] >= 0
    quiet_due = (quiet_on
                 & (birth >= due[DUE_QUIET_LO])
                 & (birth + w <= due[DUE_QUIET_HI])
                 & (birth + w <= ctx.tick))
    rec_on = due[DUE_R_LO] >= 0
    rec_due = (rec_on
               & (birth >= due[DUE_R_LO])
               & (birth <= due[DUE_R_HI])
               & (ctx.tick >= due[DUE_R_DEADLINE]))
    due_m = (quiet_due | rec_due) & (birth >= 0) & msgs.valid
    if msgs.wire_block is not None:
        # oversized messages are never transmitted on any edge — the
        # spec scopes delivery to transmissible publishes
        due_m = due_m & ~msgs.wire_block
    delivered = ctx.core.dlv.first_round >= 0        # [N, M]
    expected = _expected_receivers(ctx)
    return ~jnp.any(expected & due_m[None, :] & ~delivered)


# ---------------------------------------------------------------------------
# gossipsub-state properties (per-round + phase engines)


@invariant(
    "no-self-mesh", kind="safety", engines=GOSSIP_ENGINES,
    doc="no self-graft: the mesh and the GRAFT outbox never target the "
        "peer itself (arXiv:2311.08859 'a node never grafts itself')")
def _no_self_mesh(ctx) -> jax.Array:
    gs = ctx.gs
    n = ctx.net.nbr.shape[0]
    self_edge = ctx.net.nbr == jnp.arange(n, dtype=ctx.net.nbr.dtype)[:, None]
    bad = (gs.mesh | gs.graft_out) & self_edge[:, None, :]
    return ~jnp.any(bad)


@invariant(
    "mesh-in-topology", kind="safety", engines=GOSSIP_ENGINES,
    doc="mesh edges exist: every mesh member rides a present topology "
        "edge whose both endpoints are up and unblacklisted (dead-peer "
        "cleanup, pubsub.go:648-689); mutation-aware — reads the "
        "overlay-rebound net and is graced inside the DUE_MUT_GRACE "
        "window around topology-mutation ticks")
def _mesh_in_topology(ctx) -> jax.Array:
    gs = ctx.gs
    up_nbr = ctx.up[jnp.clip(ctx.net.nbr, 0)]
    edge_ok = ctx.net.nbr_ok & up_nbr & ctx.up[:, None]
    ok = ~jnp.any(gs.mesh & ~edge_ok[:, None, :])
    # mutation-aware (round 22): ctx.net is overlay-rebound, so mesh
    # state keyed to a just-rewired slot is cleared in the same round
    # the edge changes — the DUE_MUT_GRACE window covers exactly the
    # checks whose window saw a mutation batch
    return (ctx.due[DUE_MUT_GRACE] != 0) | ok


@invariant(
    "mesh-subscribed", kind="safety", engines=GOSSIP_ENGINES,
    doc="mesh ⊆ topology ∩ subscription: a mesh member is mesh-capable "
        "and subscribed to the slot's topic, and the slot is live "
        "(arXiv:2311.08859 mesh-subset invariant; gossipsub.go:1374)")
def _mesh_subscribed(ctx) -> jax.Array:
    return ~jnp.any(ctx.gs.mesh & ~ctx.nbr_sub)


def _slot_live(ctx) -> jax.Array:
    """[N, S]: slots whose degree clauses apply — topic joined, peer
    mesh-capable and currently up."""
    return ((ctx.net.my_topics >= 0)
            & (ctx.net.protocol >= 1)[:, None]
            & ctx.up[:, None])


def _degree_lower_ok(ctx) -> jax.Array:
    """[N, S]: the degree LOWER clause — ``deg >= Dlo`` unless no
    eligible candidate remains. The candidate set is PRECISELY the
    heartbeat's own filter (connected ∧ subscribed ∧ ¬mesh ∧
    ¬backoff-present ∧ ¬direct ∧ score >= 0, gossipsub.go:1374-1380),
    single-sourced here so `mesh-degree-bounds` and
    `mesh-reform-after-heal` can never disagree about the same bound."""
    gs, cfg = ctx.gs, ctx.cfg
    deg = jnp.sum(gs.mesh.astype(jnp.int32), axis=-1)        # [N, S]
    cand = ctx.nbr_sub & ~gs.mesh & ~gs.backoff_present
    cand = cand & ~ctx.net.direct[:, None, :]
    up_nbr = ctx.up[jnp.clip(ctx.net.nbr, 0)]
    cand = cand & (up_nbr & ctx.up[:, None])[:, None, :]
    if cfg.score_enabled:
        cand = cand & (gs.scores >= 0.0)[:, None, :]
    n_cand = jnp.sum(cand.astype(jnp.int32), axis=-1)        # [N, S]
    return (deg >= cfg.Dlo) | (n_cand == 0)


@invariant(
    "mesh-degree-bounds", kind="safety", engines=GOSSIP_ENGINES,
    doc="heartbeat-boundary mesh degree bounds: deg <= Dhi plus the "
        "reference's own outbound-quota/opportunistic overshoot "
        "(gossipsub.go:1451-1510), and deg >= Dlo unless no eligible "
        "candidate remains; suspended inside fault grace windows "
        "(arXiv:2311.08859 degree bounds)")
def _mesh_degree_bounds(ctx) -> jax.Array:
    gs, cfg = ctx.gs, ctx.cfg
    deg = jnp.sum(gs.mesh.astype(jnp.int32), axis=-1)        # [N, S]
    overshoot = cfg.Dout + (cfg.opportunistic_graft_peers
                            if cfg.score_enabled else 0)
    upper = deg <= (cfg.Dhi + overshoot)
    ok = jnp.all(jnp.where(_slot_live(ctx),
                           upper & _degree_lower_ok(ctx), True))
    return (ctx.due[DUE_GRACE] != 0) | ok


@invariant(
    "no-graft-under-backoff", kind="safety", engines=GOSSIP_ENGINES,
    doc="backoff respected: GRAFT is never sent to a peer whose prune "
        "backoff is still present (the candidate filter tests presence, "
        "gossipsub.go:1374-1380; arXiv:2311.08859 backoff lemma)")
def _no_graft_under_backoff(ctx) -> jax.Array:
    gs = ctx.gs
    return ~jnp.any(gs.graft_out & gs.backoff_present)


@invariant(
    "graylist-not-in-mesh", kind="safety", engines=GOSSIP_ENGINES,
    doc="graylisted (negatively scored) peers are absent from the mesh "
        "under the memoized score plane the router acts on "
        "(gossipsub.go:1361-1368, :772-783; graylist_threshold <= 0 "
        "makes score >= 0 the stricter bound; arXiv:2311.08859 "
        "score-exclusion)")
def _graylist_not_in_mesh(ctx) -> jax.Array:
    if not ctx.cfg.score_enabled:
        return jnp.bool_(True)
    return ~jnp.any(ctx.gs.mesh & (ctx.gs.scores < 0.0)[:, None, :])


@invariant(
    "mcache-subset-seen", kind="safety", engines=GOSSIP_ENGINES,
    doc="mcache slot consistency: every message cached for IWANT "
        "service was seen by this peer (mcache.Put happens on "
        "validated receipt or own publish, gossipsub.go:946)")
def _mcache_subset_seen(ctx) -> jax.Array:
    from ..ops import bitset

    window = bitset.word_or_reduce(ctx.gs.mcache, axis=1)    # [N, W]
    return ~jnp.any(window & ~ctx.core.dlv.have)


@invariant(
    "score-counters-wf", kind="safety", engines=GOSSIP_ENGINES,
    doc="score counters well-formed: every delivery/penalty counter is "
        "finite and non-negative (the domain the arXiv:2311.08859 "
        "counter-monotonicity lemmas quantify over)")
def _score_counters_wf(ctx) -> jax.Array:
    if not ctx.cfg.score_enabled:
        return jnp.bool_(True)
    sc = ctx.gs.score
    ok = jnp.bool_(True)
    for plane in (sc.fmd, sc.mmd, sc.mfp, sc.imd, sc.bp):
        ok &= jnp.all(jnp.isfinite(plane) & (plane >= 0.0))
    ok &= jnp.all(sc.mesh_time >= 0)
    ok &= jnp.all(sc.graft_tick >= -1)
    ok &= jnp.all(jnp.isfinite(ctx.gs.scores))
    return ok


@invariant(
    "backoff-wf", kind="safety", engines=GOSSIP_ENGINES,
    doc="backoff bookkeeping: an unexpired backoff is always present "
        "(presence outlives expiry until the lazy clear, never the "
        "reverse; gossipsub.go:1585-1604)")
def _backoff_wf(ctx) -> jax.Array:
    gs = ctx.gs
    ok = jnp.all(gs.backoff_expire >= 0)
    active = gs.backoff_expire > ctx.tick
    return ok & ~jnp.any(active & ~gs.backoff_present)


@invariant(
    "backoff-clears", kind="liveness", engines=GOSSIP_ENGINES,
    doc="backoff eventually clears: no backoff presence survives past "
        "its expiry plus the slack and one full lazy-clear period "
        "(clearBackoff cadence, gossipsub.go:1585-1604)")
def _backoff_clears(ctx) -> jax.Array:
    gs, cfg = ctx.gs, ctx.cfg
    # the lazy clear runs inside the heartbeat and fires when
    # tick % backoff_clear_ticks == 0, so with heartbeat_every > 1 the
    # two cadences coincide once per lcm (8 and 15: every 120 rounds —
    # the reference's "every 15th heartbeat", gossipsub.go:1587); that,
    # not backoff_clear_ticks alone, is one full lazy-clear period
    period = math.lcm(cfg.backoff_clear_ticks, cfg.heartbeat_every)
    bound = (gs.backoff_expire + cfg.backoff_slack_ticks
             + period + cfg.heartbeat_every + 1)
    return ~jnp.any(gs.backoff_present & (ctx.tick > bound))


@invariant(
    "promise-wf", kind="safety", engines=GOSSIP_ENGINES,
    doc="gossip-promise well-formedness: a live IWANT promise names an "
        "in-range message slot on a present edge with a valid expiry "
        "(gossip_tracer.go:48-75)")
def _promise_wf(ctx) -> jax.Array:
    gs = ctx.gs
    m = ctx.core.msgs.capacity
    live = gs.promise_mid >= 0
    ok = jnp.all(gs.promise_mid >= -1) & jnp.all(gs.promise_mid < m)
    ok &= jnp.all(jnp.where(live, gs.promise_expire >= 0, True))
    ok &= jnp.all(jnp.where(live, ctx.net.nbr_ok, True))
    return ok


@invariant(
    "mesh-reform-after-heal", kind="liveness", engines=GOSSIP_ENGINES,
    doc="partition heal is followed by mesh re-formation: once the "
        "post-heal deadline passes, the degree lower bound holds again "
        "(the arXiv:2311.08859 heal-then-re-form liveness clause)")
def _mesh_reform_after_heal(ctx) -> jax.Array:
    active = (ctx.due[DUE_R_LO] >= 0) & (ctx.tick >= ctx.due[DUE_R_DEADLINE])
    ok = jnp.all(jnp.where(_slot_live(ctx), _degree_lower_ok(ctx), True))
    return ~active | ok


@invariant(
    "choke-wf", kind="safety", engines=GOSSIP_ENGINES,
    doc="router choke well-formedness: choked ⊆ mesh — a choked link is "
        "a DEMOTED mesh link, never a non-mesh edge (episub lazy links "
        "keep mesh membership; arXiv:2312.06800 §3, routers/choke.py "
        "guard, docs/DESIGN.md §24b); vacuously true off router builds")
def _choke_wf(ctx) -> jax.Array:
    gs = ctx.gs
    if getattr(gs, "choked", None) is None:
        return jnp.bool_(True)
    return ~jnp.any(gs.choked & ~gs.mesh)


@invariant(
    "no-choke-below-dlo", kind="safety", engines=GOSSIP_ENGINES,
    doc="choke degree floor: a topic slot holding any choked link keeps "
        "at least Dlo unchoked mesh members — lazy demotion must never "
        "starve a slot's eager delivery (the arXiv:2312.06800 safety "
        "bound the choke budget + guard enforce at every mesh mutation "
        "site, docs/DESIGN.md §24b); vacuously true off router builds")
def _no_choke_below_dlo(ctx) -> jax.Array:
    gs, cfg = ctx.gs, ctx.cfg
    if getattr(gs, "choked", None) is None:
        return jnp.bool_(True)
    unchoked = jnp.sum((gs.mesh & ~gs.choked).astype(jnp.int32), axis=-1)
    any_choked = jnp.any(gs.choked, axis=-1)
    return ~jnp.any(any_choked & (unchoked < cfg.Dlo))


# ---------------------------------------------------------------------------
# the checker


def check_state(engine: str, net, state, cfg=None,
                inv: InvariantConfig | None = None,
                *, prev_events=None, due=None,
                nbr_sub=None) -> jax.Array:
    """Evaluate every applicable property on one state tree. Returns a
    ``[P]`` bool vector ordered by :func:`invariant_names` (True = the
    property holds). Pure device ops — jit/vmap-safe; the eager form is
    the negative-test surface.

    ``prev_events`` defaults to the state's own counters (the monotone
    check degenerates to a tautology on the first observation);
    ``due`` defaults to the all-disabled vector (liveness clauses
    vacuous, no grace); ``nbr_sub`` lets a caller reuse the static
    mesh-eligibility constant across checks."""
    inv = inv or InvariantConfig()
    inv.validate()
    names = invariant_names(engine, inv.names)
    if not names:
        # fail HERE with the real reason, not as jnp.stack([]) deep in
        # the checker trace
        raise InvariantConfigError(
            f"no registered property applies to engine {engine!r} with "
            f"names={inv.names!r} — the effective property set is empty")
    core = _core_of(state)
    gs = state if hasattr(state, "core") else None
    if gs is None and engine in GOSSIP_ENGINES:
        raise ValueError(
            f"engine {engine!r} checks GossipSubState trees; got a bare "
            "SimState")
    if gs is not None and cfg is None:
        raise ValueError("gossipsub-state checks need the GossipSubConfig")
    if due is None:
        due = due_vector()
    if getattr(core, "topo", None) is not None:
        # round-22 dynamic overlay: the state CARRIES the current edge
        # pool — every topology-reading property must see it, not the
        # build-time net, and any hoisted mesh-eligibility const is
        # stale by construction (presence is structural, so this branch
        # is trace-time: static builds trace the pre-dynamics program)
        net = net.with_overlay(core.topo)
        nbr_sub = None
    if nbr_sub is None and gs is not None:
        nbr_sub = _mesh_eligible_const(net)
    n = net.nbr.shape[0]
    up = gs.up & ~gs.blacklist if gs is not None else jnp.ones((n,), bool)
    ctx = Ctx(
        engine=engine, net=net, cfg=cfg, inv=inv, state=state, core=core,
        gs=gs, tick=core.tick, due=jnp.asarray(due, jnp.int32),
        prev_events=(jnp.asarray(prev_events, core.events.dtype)
                     if prev_events is not None else core.events),
        nbr_sub=nbr_sub, up=up,
    )
    return jnp.stack([REGISTRY[n_].fn(ctx) for n_ in names])


def make_checker(engine: str, net, cfg=None,
                 inv: InvariantConfig | None = None,
                 *, batched: bool = False):
    """Build the jitted invariant checker for one engine build.

    Returns ``(jit_fn, names)`` where ``jit_fn(state, prev_events, due)
    -> [P] bool`` (``[S, P]`` with ``batched=True`` — state and
    prev_events carry the leading S axis, the due vector is shared).
    One fresh jit per build: its compile-cache size is the oracle
    plane's one-compile sentinel (the same ``_cache_size`` contract as
    the ensemble runner). The checker never donates — it reads the live
    state the run keeps using."""
    inv = inv or InvariantConfig()
    inv.validate()
    names = invariant_names(engine, inv.names)
    # the static mesh-eligibility constant is hoisted out of the traced
    # fn (one eager build, closed over — the make_*_step pattern)
    nbr_sub = _mesh_eligible_const(net) if engine in GOSSIP_ENGINES else None

    def check(state, prev_events, due):
        return check_state(engine, net, state, cfg, inv,
                           prev_events=prev_events, due=due,
                           nbr_sub=nbr_sub)

    if batched:
        fn = jax.jit(jax.vmap(check, in_axes=(0, 0, None)))
    else:
        fn = jax.jit(check)
    return fn, names


# ---------------------------------------------------------------------------
# the runner hook + report


@dataclasses.dataclass
class InvariantReport:
    """Host-side summary of a checked run (read back AFTER the run
    window — the hook's device results transfer exactly once)."""

    engine: str
    names: tuple
    ticks: tuple                 # tick per check (post-dispatch rounds)
    ok: np.ndarray               # [n_checks, S, P] bool
    check_every: int
    rounds_per_step: int

    @property
    def n_checks(self) -> int:
        return int(self.ok.shape[0])

    @property
    def n_sims(self) -> int:
        return int(self.ok.shape[1])

    @property
    def all_ok(self) -> bool:
        return bool(self.ok.all())

    @property
    def checked(self) -> int:
        """Total property evaluations (checks x sims x properties)."""
        return int(self.ok.size)

    @property
    def violated(self) -> int:
        return int((~self.ok).sum())

    @property
    def last_checked_round(self) -> int:
        return int(self.ticks[-1]) if self.ticks else -1

    def violations(self, limit: int = 32) -> list:
        """(tick, sim, property) triples of failed evaluations."""
        out = []
        bad = np.argwhere(~self.ok)
        for ci, si, pi in bad[:limit]:
            out.append((int(self.ticks[ci]), int(si), self.names[pi]))
        return out

    def per_property(self) -> dict:
        """name -> (evaluations, violations) over the whole run."""
        return {
            name: (int(self.ok[:, :, i].size), int((~self.ok[:, :, i]).sum()))
            for i, name in enumerate(self.names)
        }

    def artifact_block(self) -> dict:
        """The schema-v3 ``invariants`` artifact block (read back by
        ``BenchRecord.invariants``; legacy artifacts read
        ``perf.artifacts.INVARIANTS_OFF``)."""
        return {
            "enabled": True,
            "engine": self.engine,
            "properties": list(self.names),
            "checked": self.checked,
            "violated": self.violated,
            "n_checks": self.n_checks,
            "n_sims": self.n_sims,
            "check_every": int(self.check_every),
            "rounds_per_step": int(self.rounds_per_step),
            "last_checked_round": self.last_checked_round,
            "violations": [
                {"round": t, "sim": s, "property": p}
                for t, s, p in self.violations()
            ],
        }


class ScanInvariants:
    """The scan-folded face of the oracle plane (docs/DESIGN.md §14):
    the same property registry, due contract and report shape as
    :class:`InvariantHook`, but evaluated INSIDE the run-window program
    (driver.make_window) instead of as a separate dispatch per check —
    the checker traces into the window's scan body, due rows ride as
    stacked scan ``xs``, the previous-counters snapshot rides the scan
    carry, and the ``[n_checks, S, P]`` violation masks come back as
    scan ``ys``. A checked whole-run window is therefore still ONE XLA
    dispatch.

    Two semantic deltas vs the hook, both pinned by tests/test_window.py:

    * the first check's ``events-monotone`` compares against the
      WINDOW-ENTRY counters (the scan carry's initial value) instead of
      the hook's first-observation tautology — strictly stronger, never
      weaker (counters are born monotone);
    * no ``jnp.copy`` defensive snapshots — the carry is functional, so
      the donation hazard the hook documents cannot occur.

    ``check`` is the eager (un-jitted) predicate ``(state, prev_events,
    due_row) -> [P]`` (vmapped to ``[S, P]`` when ``batched``) that
    ``driver.make_window(check=...)`` folds in; :meth:`precompute`
    materializes the stacked due rows on device (call it BEFORE a
    ``transfer_guard`` window); :meth:`report` turns the window's
    ``ys["ok"]`` masks back into the standard :class:`InvariantReport`.
    """

    def __init__(self, engine: str, net, cfg=None,
                 inv: InvariantConfig | None = None, *,
                 batched: bool = True, due_fn=None,
                 rounds_per_step: int = 1):
        self.engine = engine
        self.inv = inv or InvariantConfig()
        self.inv.validate()
        self.names = invariant_names(engine, self.inv.names)
        self.batched = batched
        self.due_fn = due_fn
        self.rounds_per_step = max(int(rounds_per_step), 1)
        nbr_sub = (_mesh_eligible_const(net)
                   if engine in GOSSIP_ENGINES else None)
        icfg = self.inv

        def check(state, prev_events, due):
            return check_state(engine, net, state, cfg, icfg,
                               prev_events=prev_events, due=due,
                               nbr_sub=nbr_sub)

        self.check = (jax.vmap(check, in_axes=(0, 0, None)) if batched
                      else check)
        self._due = None
        self._ticks: tuple = ()

    @property
    def check_every(self) -> int:
        return self.inv.check_every

    def n_checks(self, n_steps: int) -> int:
        return int(n_steps) // self.inv.check_every

    def precompute(self, n_steps: int) -> jax.Array:
        """The stacked ``[n_checks, 6]`` due-row plane for an
        ``n_steps``-dispatch window (host → device transfers happen
        HERE, not inside the window) plus the tick labels."""
        ce = self.inv.check_every
        rows, ticks = [], []
        for i in range(int(n_steps)):
            if (i + 1) % ce:
                continue
            tick = (i + 1) * self.rounds_per_step
            rows.append(np.asarray(
                self.due_fn(tick) if self.due_fn is not None
                else due_vector(), np.int32))
            ticks.append(tick)
        self._ticks = tuple(ticks)
        self._due = jnp.asarray(
            np.stack(rows) if rows
            else np.zeros((0, DUE_LEN), np.int32))
        return self._due

    def due_rows(self, n_steps: int) -> jax.Array:
        if self._due is None or self._due.shape[0] != self.n_checks(n_steps):
            self.precompute(n_steps)
        return self._due

    def report(self, ok, ticks=None) -> InvariantReport:
        """Summarize the window's stacked ``ys["ok"]`` masks
        (``[n_checks, P]`` unbatched / ``[n_checks, S, P]`` batched)
        as the standard :class:`InvariantReport`."""
        ok = np.asarray(ok)
        if ok.ndim == 2:
            ok = ok[:, None, :]
        if ok.size and ok.shape[-1] != len(self.names):
            raise ValueError(
                f"ok mask property axis {ok.shape[-1]} != "
                f"{len(self.names)} registered for {self.engine!r}")
        return InvariantReport(
            engine=self.engine, names=self.names,
            ticks=tuple(ticks) if ticks is not None else self._ticks,
            ok=ok, check_every=self.inv.check_every,
            rounds_per_step=self.rounds_per_step,
        )


class InvariantHook:
    """The ``check_every=k`` observer ``ensemble.runner.run_rounds``
    (and the report scripts) drive: every k dispatches it evaluates the
    jitted checker on the live batched state and appends the ``[S, P]``
    bool result to a device-side list — zero host transfers inside the
    run window; :meth:`report` reads everything back afterwards.
    (:class:`ScanInvariants` is the scan-folded equivalent the window
    drivers use; this hook remains the per-dispatch face — the negative
    tests and the parity gates drive both.)

    ``due_fn(tick) -> i32[6]`` supplies the host-known schedule context
    per check (see :func:`due_vector`); it is evaluated for every
    potential check in :meth:`precompute` — call that BEFORE entering a
    ``transfer_guard`` window so the due rows are already on device.
    ``rounds_per_step`` is the engine cadence (1 for per-round engines,
    r for the phase engine), used only to label ticks."""

    def __init__(self, engine: str, net, cfg=None,
                 inv: InvariantConfig | None = None, *,
                 batched: bool = True, due_fn=None,
                 rounds_per_step: int = 1):
        self.engine = engine
        self.inv = inv or InvariantConfig()
        self.checker, self.names = make_checker(
            engine, net, cfg, self.inv, batched=batched)
        self.batched = batched
        self.due_fn = due_fn
        self.rounds_per_step = max(int(rounds_per_step), 1)
        self._due_rows: list | None = None
        self._results: list = []
        self._ticks: list = []
        self._prev_events = None
        self._cache_before = None

    # -- one-compile sentinel -------------------------------------------

    def _cache_size(self):
        try:
            return int(self.checker._cache_size())
        except Exception:  # pragma: no cover — newer-jax fallback
            return None

    @property
    def compiles(self) -> int:
        """Checker compile count since the first check (-1 unknown)."""
        after = self._cache_size()
        if self._cache_before is None or after is None:
            return -1
        return after - self._cache_before

    # -- the hook -------------------------------------------------------

    def reset(self) -> None:
        """Clear accumulated results and the monotone-counter snapshot
        (NOT the jitted checker or the precomputed due rows) — for
        reusing one hook across several independent runs (e.g. timed
        reps): a stale prev-events snapshot from a previous run's final
        counters would read a fresh run's near-zero counters as a
        bogus events-monotone violation."""
        self._results = []
        self._ticks = []
        self._prev_events = None

    def precompute(self, n_steps: int) -> None:
        """Materialize every check's due row on device up front (host →
        device transfers happen HERE, not inside the run window)."""
        if self._due_rows is not None:
            return
        rows = []
        for i in range(int(n_steps)):
            if (i + 1) % self.inv.check_every:
                rows.append(None)
                continue
            tick = (i + 1) * self.rounds_per_step
            row = (self.due_fn(tick) if self.due_fn is not None
                   else due_vector())
            rows.append(jnp.asarray(np.asarray(row, np.int32)))
        self._due_rows = rows

    def on_step(self, i: int, states) -> None:
        """Called after dispatch ``i`` with the live (batched) state."""
        if self._due_rows is None or i >= len(self._due_rows):
            # unscheduled dispatch (caller ran longer than precompute):
            # fall back to host-built rows — outside any guard window
            # this is just a tiny transfer
            tick = (i + 1) * self.rounds_per_step
            if (i + 1) % self.inv.check_every:
                return
            due = jnp.asarray(np.asarray(
                self.due_fn(tick) if self.due_fn is not None
                else due_vector(), np.int32))
        else:
            due = self._due_rows[i]
            if due is None:
                return
        core = _core_of(states)
        prev = self._prev_events
        if prev is None:
            prev = core.events       # first check: tautological monotone
        if self._cache_before is None:
            self._cache_before = self._cache_size()
        ok = self.checker(states, prev, due)
        self._results.append(ok)
        self._ticks.append((i + 1) * self.rounds_per_step)
        # COPY, never alias: the engine step donates every state buffer
        # on the next dispatch, so holding core.events itself would hand
        # the checker a deleted array one check later (the same
        # donation contract every gate's _fresh() copies around)
        self._prev_events = jnp.copy(core.events)

    # -- readback -------------------------------------------------------

    def report(self) -> InvariantReport:
        """Transfer the accumulated violation masks and summarize."""
        if self._results:
            ok = np.stack([np.asarray(r) for r in self._results])
            if ok.ndim == 2:     # unbatched checker: [n_checks, P]
                ok = ok[:, None, :]
        else:
            ok = np.zeros((0, 1, len(self.names)), bool)
        return InvariantReport(
            engine=self.engine, names=self.names,
            ticks=tuple(self._ticks), ok=ok,
            check_every=self.inv.check_every,
            rounds_per_step=self.rounds_per_step,
        )
