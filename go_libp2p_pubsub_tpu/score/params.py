"""Traced score-parameter plane — the first analysis-driven lift.

Round 16 (docs/DESIGN.md §16): the score/mesh knobs have always ridden
the jitted steps as *static* constants — `GossipSubConfig` threshold
fields closed over by the step, `TopicParamsArrays` rows baked in as
numpy constants, `PeerScoreParams` scalars read as Python floats — so
every weight change recompiled the engine, which is exactly what blocks
the ROADMAP's configs×sims parameter search (one generation = one
program sweeping many weight sets).

`analysis/lift.py` (the liftability dataflow pass) machine-classifies
every use site of those fields as SHAPE (feeds a shape, a Python
branch, an index bound, a dtype decision — must stay static) or VALUE
(pure traced arithmetic — liftable), committed as ``LIFT_AUDIT.json``.
This module ships the lift the audit justifies: every VALUE-proved
score field becomes a leaf of :class:`ScoreParams`, a flax-struct
pytree the lifted engines take as a TRACED argument — so two builds
differing only in weights/thresholds share ONE compiled program
(the recompile-free A/B sentinel, ``make analyze``'s ``lifted`` guard
row), and a vmapped plane axis sweeps whole weight populations.

What stays static, per the audit:

* ``PeerScoreParams.app_specific_weight`` — SHAPE: a non-zero weight
  gates the P5 cross-peer gather (one halo-permute set on the sharded
  mesh; score/engine.py compute_scores, the phase head's
  ``include_app``). Program structure, census-pinned — the plane
  carries it as static aux (``pytree_node=False``).
* the mesh degree knobs (D/Dlo/Dhi/Dscore/Dout/Dlazy/gossip_factor)
  rode as static until round 20: the masked-width selection contract
  (``ops/select.masked_width_topk`` — rank the full padded axis, clip
  the traced width) removed the last SHAPE site, so they now lift as
  :class:`MeshParams` and join the candidate plane
  (:class:`CandidateParams`) the tune/ search sweeps.
* the phase engine's static weight elision (p3_live/p4_live) — a
  build-time STRUCTURE decision on weight values. The lifted build
  pins the conservative all-planes-live structure instead (a traced
  weight cannot drive build-time elision), so one program is correct
  for every weight set; `LIFT_AUDIT.json` records those sites as
  guarded elisions.

Bit-exactness contract (tests/test_score_lift.py): at matched values a
lifted build's state trees equal the static build's bit for bit on all
four engines — the plane's [T] rows are built by the SAME
`TopicParamsArrays.build` arithmetic, its `gather` is the same masked
row gather, and every consuming op is unchanged (a traced f32 scalar
compares/multiplies exactly like the Python float it replaces).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct

from ..config import PeerScoreParams, PeerScoreThresholds
from .engine import TopicParamsArrays

#: the [T] per-topic rows the plane carries — one leaf per
#: TopicParamsArrays field, same dtypes (f32 except the two tick
#: fields and the scored mask), single-sourced for build() and gather()
TOPIC_ROW_FIELDS = (
    "scored", "topic_weight", "w1", "quantum_ticks", "cap1",
    "w2", "decay2", "cap2", "w3", "decay3", "cap3", "thr3",
    "window_rounds", "activation_ticks", "w3b", "decay3b", "w4", "decay4",
)

#: scalar PeerScoreParams fields the plane lifts (audit: VALUE /
#: VALUE_GUARDED — pure traced arithmetic in compute/refresh_scores)
PEER_SCALAR_FIELDS = (
    "topic_score_cap", "ip_colocation_factor_weight",
    "behaviour_penalty_weight", "behaviour_penalty_threshold",
    "behaviour_penalty_decay", "decay_to_zero",
)

#: GossipSubConfig threshold fields the plane lifts (audit: VALUE —
#: every use is a traced score compare)
THRESHOLD_FIELDS = (
    "gossip_threshold", "publish_threshold", "graylist_threshold",
    "accept_px_threshold", "opportunistic_graft_threshold",
)

#: TopicParamsArrays row -> source TopicScoreParams field (provenance;
#: `scored` derives from topic-map membership, not a field)
TOPIC_ROW_PROVENANCE = {
    "scored": None,
    "topic_weight": "topic_weight",
    "w1": "time_in_mesh_weight",
    "quantum_ticks": "time_in_mesh_quantum",
    "cap1": "time_in_mesh_cap",
    "w2": "first_message_deliveries_weight",
    "decay2": "first_message_deliveries_decay",
    "cap2": "first_message_deliveries_cap",
    "w3": "mesh_message_deliveries_weight",
    "decay3": "mesh_message_deliveries_decay",
    "cap3": "mesh_message_deliveries_cap",
    "thr3": "mesh_message_deliveries_threshold",
    "window_rounds": "mesh_message_deliveries_window",
    "activation_ticks": "mesh_message_deliveries_activation",
    "w3b": "mesh_failure_penalty_weight",
    "decay3b": "mesh_failure_penalty_decay",
    "w4": "invalid_message_deliveries_weight",
    "decay4": "invalid_message_deliveries_decay",
}

#: audit-namespace names of everything the plane carries traced — the
#: fingerprint["params"] block and scripts/lift_audit.py cross-check
#: this list against LIFT_AUDIT.json's verdicts
LIFTED_FIELD_NAMES = tuple(sorted(
    [f"GossipSubConfig.{f}" for f in THRESHOLD_FIELDS]
    + [f"PeerScoreParams.{f}" for f in PEER_SCALAR_FIELDS]
    + [f"TopicScoreParams.{TOPIC_ROW_PROVENANCE[r]}"
       for r in TOPIC_ROW_FIELDS if TOPIC_ROW_PROVENANCE[r]]
    + ["TopicParamsArrays.scored"]
))


@struct.dataclass
class ScoreParams:
    """The traced score plane: [T] per-topic rows + scalar leaves.

    Quacks as THREE things inside the lifted engines, so no adapter
    objects exist to drift: (a) the threshold source (attributes named
    exactly like GossipSubConfig's threshold fields), (b) the scalar
    params source for compute_scores/refresh_scores (attributes named
    like PeerScoreParams'), (c) via :meth:`gather`, the per-(peer,
    slot) ``tp`` dict TopicParamsArrays.gather produces. The class
    attribute ``lifted`` marks it for the one Python branch that must
    differ (compute_scores' topic-score-cap elision becomes a
    jnp.where — value-identical at matched values)."""

    # [T] per-topic rows (TopicParamsArrays dtypes)
    scored: jax.Array            # [T] bool
    topic_weight: jax.Array      # [T] f32
    w1: jax.Array
    quantum_ticks: jax.Array     # [T] f32 (>=1)
    cap1: jax.Array
    w2: jax.Array
    decay2: jax.Array
    cap2: jax.Array
    w3: jax.Array
    decay3: jax.Array
    cap3: jax.Array
    thr3: jax.Array
    window_rounds: jax.Array     # [T] i32
    activation_ticks: jax.Array  # [T] i32
    w3b: jax.Array
    decay3b: jax.Array
    w4: jax.Array
    decay4: jax.Array
    # PeerScoreParams scalars (f32 0-d)
    topic_score_cap: jax.Array
    ip_colocation_factor_weight: jax.Array
    behaviour_penalty_weight: jax.Array
    behaviour_penalty_threshold: jax.Array
    behaviour_penalty_decay: jax.Array
    decay_to_zero: jax.Array
    # v1.1 thresholds (f32 0-d; GossipSubConfig field names)
    gossip_threshold: jax.Array
    publish_threshold: jax.Array
    graylist_threshold: jax.Array
    accept_px_threshold: jax.Array
    opportunistic_graft_threshold: jax.Array
    # SHAPE fields ride as static aux: the P5 weight gates a cross-peer
    # gather (program structure — LIFT_AUDIT.json declares it SHAPE)
    app_specific_weight: float = struct.field(pytree_node=False, default=0.0)

    lifted = True  # class marker, not a field

    @classmethod
    def build(
        cls,
        score_params: PeerScoreParams,
        thresholds: PeerScoreThresholds | None = None,
        n_topics: int = 1,
        heartbeat_interval: float = 1.0,
        heartbeat_every: int = 1,
    ) -> "ScoreParams":
        """Build the plane from the SAME host structs the static path
        consumes — the [T] rows go through TopicParamsArrays.build, so
        matched-value parity is arithmetic identity, not coincidence.
        ``thresholds=None`` builds the v1.0 all-zero threshold plane
        (what GossipSubConfig.build records without thresholds)."""
        tpa = TopicParamsArrays.build(score_params, n_topics,
                                      heartbeat_interval, heartbeat_every)
        kw = {name: jnp.asarray(getattr(tpa, name))
              for name in TOPIC_ROW_FIELDS}
        for f in PEER_SCALAR_FIELDS:
            kw[f] = jnp.float32(getattr(score_params, f))
        for f in THRESHOLD_FIELDS:
            kw[f] = jnp.float32(getattr(thresholds, f)
                                if thresholds is not None else 0.0)
        return cls(app_specific_weight=float(
            score_params.app_specific_weight), **kw)

    @classmethod
    def from_config(cls, cfg, score_params: PeerScoreParams,
                    n_topics: int = 1,
                    heartbeat_interval: float = 1.0) -> "ScoreParams":
        """The matched-values constructor: thresholds read back from a
        built GossipSubConfig, so ``step(state, ..., plane)`` with this
        plane reproduces the static build bit for bit. (THRESHOLD_FIELDS
        are the GossipSubConfig field names, so the cfg duck-types as
        build()'s thresholds source.)"""
        return cls.build(score_params, cfg, n_topics, heartbeat_interval,
                         cfg.heartbeat_every)

    def gather(self, my_topics: jax.Array) -> dict:
        """The per-(peer, slot) [N, S] views — the exact
        TopicParamsArrays.gather math over traced rows; slots with no
        topic (-1) come out zeroed/unscored."""
        t = jnp.clip(my_topics, 0)
        live = my_topics >= 0

        def g(a):
            v = jnp.asarray(a)[t]
            return jnp.where(live, v, jnp.asarray(0, v.dtype))

        return {name: g(getattr(self, name)) for name in TOPIC_ROW_FIELDS}


#: GossipSubConfig mesh degree knobs the mesh plane lifts — i32 widths
#: plus the f32 gossip factor. Audit-proved VALUE (round 20: the
#: masked-width selection contract removed the one SHAPE site,
#: ops/select's conditional-expression broadcast).
MESH_INT_FIELDS = ("D", "Dlo", "Dhi", "Dscore", "Dout", "Dlazy")
MESH_FLOAT_FIELDS = ("gossip_factor",)

#: audit-namespace names the mesh plane carries traced —
#: scripts/lift_audit.py cross-checks this against LIFT_AUDIT.json
MESH_LIFTED_FIELD_NAMES = tuple(sorted(
    f"GossipSubConfig.{f}" for f in MESH_INT_FIELDS + MESH_FLOAT_FIELDS
))


@struct.dataclass
class MeshParams:
    """The traced mesh-degree plane (round 20).

    Attribute names match GossipSubConfig's, so inside the engines a
    MeshParams duck-types as the degree-knob source the same way
    ScoreParams duck-types as the threshold source (the ``msh = cfg if
    msh is None else msh`` seam). All widths reach selection kernels
    through ``ops/select.masked_width_*`` with the padded neighbor axis
    as the static ceiling, so program shape never depends on a leaf."""

    D: jax.Array        # i32 0-d
    Dlo: jax.Array
    Dhi: jax.Array
    Dscore: jax.Array
    Dout: jax.Array
    Dlazy: jax.Array
    gossip_factor: jax.Array  # f32 0-d

    lifted = True  # class marker, not a field

    @classmethod
    def from_config(cls, cfg) -> "MeshParams":
        """Matched-values constructor: a step fed this plane reproduces
        the static build bit for bit (a traced i32 width compares and
        subtracts exactly like the Python int it replaces)."""
        kw = {f: jnp.int32(getattr(cfg, f)) for f in MESH_INT_FIELDS}
        for f in MESH_FLOAT_FIELDS:
            kw[f] = jnp.float32(getattr(cfg, f))
        return cls(**kw)


@struct.dataclass
class CandidateParams:
    """One tune/ candidate: the score plane and the mesh plane, stacked
    together as a single pytree so ``ensemble.stack_planes`` sweeps both
    along the plane axis. The lifted engines detect the combined form by
    its ``mesh`` attribute (``getattr(plane, "mesh", None)``) and fall
    back to score-only semantics otherwise, so every pre-round-20 call
    site keeps working unchanged."""

    score: ScoreParams
    mesh: MeshParams

    lifted = True  # class marker, not a field

    @property
    def app_specific_weight(self) -> float:
        # static aux rides on the nested score plane; surface it so
        # ensemble.stack_planes' aux-agreement check sees it
        return self.score.app_specific_weight

    @classmethod
    def from_config(cls, cfg, score_params: PeerScoreParams,
                    n_topics: int = 1,
                    heartbeat_interval: float = 1.0) -> "CandidateParams":
        return cls(
            score=ScoreParams.from_config(cfg, score_params, n_topics,
                                          heartbeat_interval),
            mesh=MeshParams.from_config(cfg),
        )
