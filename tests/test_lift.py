"""Liftability-pass tests (analysis/lift.py, docs/DESIGN.md §16):
every classification rule must FIRE on a seeded snippet (negative),
the alias/interprocedural resolution must see through the patterns it
claims to, and the committed LIFT_AUDIT.json must reproduce
byte-identically with the shipped plane proven liftable (positive)."""

import os
import textwrap

from go_libp2p_pubsub_tpu.analysis import lift

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "go_libp2p_pubsub_tpu")


def sites_of(src, rel="models/broken.py"):
    return lift.analyze_source(textwrap.dedent(src), rel)


def kinds(sites, field):
    return sorted(s.kind for s in sites if s.field == field)


# ---------------------------------------------------------------------------
# classification rules — one seeded snippet per rule


def test_branch_site_classifies_shape():
    sites = sites_of("""
        def step(cfg, st):
            if cfg.flood_publish:
                return st
            return -st
    """)
    assert kinds(sites, "GossipSubConfig.flood_publish") == ["branch"]


def test_while_and_assert_tests_classify_branch():
    sites = sites_of("""
        def step(cfg, st):
            assert cfg.queue_cap >= 0
            while cfg.heartbeat_every:
                st = st + 1
            return st
    """)
    assert kinds(sites, "GossipSubConfig.queue_cap") == ["branch"]
    assert kinds(sites, "GossipSubConfig.heartbeat_every") == ["branch"]


def test_conditional_expression_test_classifies_branch():
    sites = sites_of("""
        def step(cfg, st):
            dt = jnp.int16 if cfg.narrow_counters else jnp.int32
            return st.astype(dt)
    """)
    assert kinds(sites, "GossipSubConfig.narrow_counters") == ["branch"]


def test_shape_arg_classifies_shape():
    sites = sites_of("""
        import jax.numpy as jnp
        def step(cfg, st):
            return jnp.zeros((cfg.fanout_slots, 4))
    """)
    assert kinds(sites, "GossipSubConfig.fanout_slots") == ["shape"]


def test_host_conversion_classifies_shape():
    sites = sites_of("""
        def step(cfg, st):
            return st * float(cfg.gossip_threshold)
    """)
    assert kinds(sites, "GossipSubConfig.gossip_threshold") == ["shape"]


def test_slice_bound_classifies_shape():
    sites = sites_of("""
        def step(cfg, st):
            return st[:, : cfg.history_gossip, :]
    """)
    assert kinds(sites, "GossipSubConfig.history_gossip") == ["shape"]


def test_traced_compare_classifies_value():
    sites = sites_of("""
        def step(cfg, st):
            return st.scores >= cfg.gossip_threshold
    """)
    assert kinds(sites, "GossipSubConfig.gossip_threshold") == ["value"]


def test_no_if_test_excuses_a_shape_site():
    sites = sites_of("""
        def step(cfg, st, use_fused):
            if use_fused:
                return st * float(cfg.gossip_threshold)
            return st
    """)
    assert kinds(sites, "GossipSubConfig.gossip_threshold") == ["shape"]


def test_tp_subscript_maps_to_topic_field():
    sites = sites_of("""
        def refresh(st, tp):
            return st.fmd * tp["decay2"]
    """)
    assert kinds(
        sites, "TopicScoreParams.first_message_deliveries_decay"
    ) == ["value"]


def test_static_argnames_kw_classifies_shape():
    sites = sites_of("""
        import jax
        def make_jitted(cfg, fn):
            return jax.jit(fn, static_argnames=cfg.edge_layout)
    """)
    assert kinds(sites, "GossipSubConfig.edge_layout") == ["shape"]


# ---------------------------------------------------------------------------
# alias + interprocedural resolution


def test_single_assign_alias_resolves():
    # w = cfg.score_weights-style single-assignment alias: the use of
    # the NAME classifies at the aliased field (the defining read is a
    # second evidence site — both value-kind here)
    sites = sites_of("""
        def step(cfg, st):
            w = cfg.graylist_threshold
            if w:
                return st
            return st.scores >= w
    """)
    got = kinds(sites, "GossipSubConfig.graylist_threshold")
    assert "branch" in got and "value" in got


def test_reassigned_alias_not_trusted():
    # a name assigned twice is no longer a sound alias — dropped
    sites = sites_of("""
        def step(cfg, st):
            thr = cfg.graylist_threshold
            thr = 0.0
            if thr:
                return st
            return -st
    """)
    assert kinds(sites, "GossipSubConfig.graylist_threshold") == ["value"]


def test_alias_of_whole_config_resolves():
    sites = sites_of("""
        def step(cfg, st):
            c = cfg
            if c.do_px:
                return st
            return -st
    """)
    assert kinds(sites, "GossipSubConfig.do_px") == ["branch"]


def test_closure_capture_resolves():
    # nested defs see the builder's cfg through lexical scoping —
    # including defs nested under an `if` (heartbeat's _oppo_grafts)
    sites = sites_of("""
        def make_step(cfg, net):
            flag = True
            if flag:
                def inner(st):
                    return st >= cfg.opportunistic_graft_threshold
            def step(st):
                return inner(st)
            return step
    """)
    assert kinds(
        sites, "GossipSubConfig.opportunistic_graft_threshold"
    ) == ["value"]


def test_consts_attribute_chain_resolves():
    sites = sites_of("""
        import numpy as np
        def make_step(cfg, net, score_params):
            consts = prepare_step_consts(cfg, net, score_params)
            w3 = np.asarray(consts.tpa.w3)
            return w3
    """)
    assert kinds(
        sites, "TopicScoreParams.mesh_message_deliveries_weight"
    ) == ["shape"]


def test_interprocedural_field_propagation():
    # a field passed positionally roots the callee's parameter: its
    # uses classify as reads of that field even though the callee knows
    # nothing of configs
    sites = sites_of("""
        def helper(wnd, msgs):
            return wnd[msgs]

        def step(cfg, st, consts):
            return helper(consts.window_rounds_t, st.topic)
    """)
    got = kinds(sites,
                "TopicScoreParams.mesh_message_deliveries_window")
    assert "value" in got


def test_method_invocation_is_not_a_read():
    sites = sites_of("""
        def build(cfg, gater_params):
            gater_params.validate()
            return cfg
    """)
    assert not any(s.field == "PeerGaterParams.validate" for s in sites)


def test_build_scope_excluded():
    sites = sites_of("""
        class FooConfig:
            def validate(self, params):
                if params.decay_to_zero <= 0:
                    raise ValueError()
    """)
    assert sites == []


# ---------------------------------------------------------------------------
# verdict aggregation


def test_verdict_shape_wins_over_value():
    sites = sites_of("""
        import jax.numpy as jnp
        def step(cfg, st):
            x = st * cfg.max_ihave_length
            return jnp.zeros((cfg.max_ihave_length,)) + x
    """)
    v = lift.field_verdicts(sites)["GossipSubConfig.max_ihave_length"]
    assert v["verdict"] == "SHAPE"


def test_verdict_shape_in_one_arm_blocks():
    sites = sites_of("""
        def step(cfg, st, use_fused):
            if use_fused:
                return st * float(cfg.gossip_threshold)
            return st.scores >= cfg.gossip_threshold
    """)
    v = lift.field_verdicts(sites)["GossipSubConfig.gossip_threshold"]
    assert v["verdict"] == "SHAPE"


def test_declared_shape_forced():
    sites = sites_of("""
        def score(params, st):
            return st * params.app_specific_weight
    """)
    v = lift.field_verdicts(sites)["PeerScoreParams.app_specific_weight"]
    assert v["verdict"] == "SHAPE"
    assert "declared_shape" in v


def test_elision_table_guards_verdict():
    # the compute_scores topic-score-cap branch is a declared
    # value-neutral elision: the branch site exists but the verdict is
    # VALUE_GUARDED, not SHAPE
    sites = sites_of("""
        import jax.numpy as jnp
        def compute_scores(st, tp, params):
            score = st * tp["topic_weight"]
            if params.topic_score_cap > 0:
                score = jnp.minimum(score, params.topic_score_cap)
            return score
    """, rel="score/engine.py")
    v = lift.field_verdicts(sites)["PeerScoreParams.topic_score_cap"]
    assert v["verdict"] == "VALUE_GUARDED"
    assert any("elision_ok" in r for r in v["sites"])


def test_check_plane_flags_unsound_lift(monkeypatch):
    sites = sites_of("""
        import jax.numpy as jnp
        def step(cfg, st):
            return jnp.zeros((int(cfg.gossip_threshold),)) + st
    """)
    verdicts = lift.field_verdicts(sites)
    fails = lift.check_plane(verdicts)
    assert any("GossipSubConfig.gossip_threshold" in f
               and "UNSOUND" in f for f in fails)


# ---------------------------------------------------------------------------
# the repo audit: the shipped lift is proven, the artifact reproduces


def test_repo_audit_proves_the_plane():
    payload = lift.audit(PKG)
    assert lift.check_plane(payload["fields"]) == []
    # the honest headline facts: thresholds VALUE, the P5 weight SHAPE,
    # the phase elision weights guarded
    f = payload["fields"]
    assert f["GossipSubConfig.gossip_threshold"]["verdict"] == "VALUE"
    assert f["PeerScoreParams.app_specific_weight"]["verdict"] == "SHAPE"
    assert f["TopicScoreParams.mesh_message_deliveries_weight"][
        "verdict"] == "VALUE_GUARDED"


def test_plane_manifest_matches_score_params():
    from go_libp2p_pubsub_tpu.score.params import LIFTED_FIELD_NAMES

    assert set(lift.SCORE_PLANE_FIELDS) == set(LIFTED_FIELD_NAMES)


def test_committed_audit_reproduces_byte_identical():
    path = lift.audit_path(ROOT)
    assert os.path.exists(path), "LIFT_AUDIT.json not committed"
    with open(path) as f:
        committed = f.read()
    assert committed == lift.dump_audit(lift.audit(PKG)), (
        "LIFT_AUDIT.json is stale — LIFT_UPDATE=1 scripts/lift_audit.py"
    )
