"""Analysis-plane tests: every simlint rule and every trace guard must
FIRE on a deliberately broken snippet/config (negative), and the repo
itself must pass clean (positive) — so `make analyze` is demonstrably a
live gate, not a rubber stamp. docs/DESIGN.md §9."""

import glob
import json
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from go_libp2p_pubsub_tpu.analysis import guards, simlint
from go_libp2p_pubsub_tpu.analysis.guards import (
    EngineHarness,
    GuardViolation,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "go_libp2p_pubsub_tpu")


def lint(src, rel="models/broken.py"):
    return simlint.lint_source(textwrap.dedent(src), rel)


def rules_of(violations):
    return {v.rule for v in violations}


# ---------------------------------------------------------------------------
# simlint rules: each fires on a seeded violation


def test_traced_branch_fires():
    vs = lint("""
        import jax.numpy as jnp
        def step(x):
            if jnp.any(x > 0):
                return x
            return -x
    """)
    assert "traced-branch" in rules_of(vs)


def test_traced_branch_ignores_host_numpy():
    # the calibrated exception: eager numpy branching (detect_banded,
    # chaos/metrics) is host-side and must NOT fire
    vs = lint("""
        import numpy as np
        def detect(nbr, ok):
            if not ok.all():
                return None
            return np.where(ok, nbr, -1)
    """, rel="ops/edges.py")
    assert vs == []


def test_host_sync_item_fires():
    vs = lint("""
        def drain(state):
            return state.events.item()
    """)
    assert "host-sync" in rules_of(vs)


def test_host_sync_nested_fn_fires_once():
    # scoped walking: a violation in a nested def is reported exactly
    # once (in its own scope), not re-reported per enclosing function
    vs = lint("""
        def make_step():
            def step(state):
                return state.events.item()
            return step
    """)
    assert len([v for v in vs if v.rule == "host-sync"]) == 1
    assert vs[0].qual == "make_step.step"


def test_host_sync_conversion_in_traced_step_fires():
    vs = lint("""
        import jax, numpy as np
        @jax.jit
        def step(state, pub):
            cap = int(state.tick)
            return np.asarray(pub)
    """)
    assert sum(v.rule == "host-sync" for v in vs) == 2


def test_traced_branch_through_alias_fires():
    # the round-16 alias-blindness fix (shared resolver: lift.py):
    # a Python if on a NAME assigned from a jnp expression was
    # previously invisible to the rule
    vs = lint("""
        import jax.numpy as jnp
        def step(x):
            w = jnp.any(x > 0)
            if w:
                return x
            return -x
    """)
    assert "traced-branch" in rules_of(vs)


def test_traced_branch_alias_of_alias_fires():
    vs = lint("""
        import jax.numpy as jnp
        def step(x):
            y = jnp.any(x > 0)
            w = y
            if w:
                return x
            return -x
    """)
    assert "traced-branch" in rules_of(vs)


def test_traced_branch_is_none_test_on_alias_ok():
    # identity tests of a traced alias are host-level — the calibrated
    # exception (window_g-style optional-plane plumbing)
    vs = lint("""
        import jax.numpy as jnp
        def step(x, w=None):
            w = jnp.sum(x) if w is None else w
            if w is None:
                return x
            return w
    """)
    assert vs == []


def test_traced_branch_shape_derived_alias_ok():
    # shape reads of a traced array are trace-time Python ints — a
    # branch on them is legal (bitset.pack's pad test), in both the
    # two-statement and the inline single-expression form
    vs = lint("""
        import jax.numpy as jnp
        def step(x):
            y = jnp.asarray(x)
            pad = y.shape[-1] % 32
            if pad:
                return y
            return -y
    """)
    assert vs == []
    vs = lint("""
        import jax.numpy as jnp
        def step(x):
            pad = jnp.asarray(x).shape[-1] % 32
            if pad:
                return x
            return -x
    """)
    assert vs == []


def test_host_sync_through_alias_chain_fires():
    # float() of an alias of a traced local — previously missed
    vs = lint("""
        import jax
        import jax.numpy as jnp
        @jax.jit
        def step(state, pub):
            y = jnp.sum(state)
            w = y
            return float(w)
    """)
    assert "host-sync" in rules_of(vs)


def test_config_hash_through_decorator_alias_fires():
    # `from dataclasses import dataclass as dc` previously made the
    # class invisible to the rule (silently skipped)
    vs = lint("""
        from dataclasses import dataclass as dc
        @dc
        class FlapConfig:
            x: int = 1
    """)
    assert "config-hash" in rules_of(vs)


def test_config_hash_struct_alias_still_exempt():
    vs = lint("""
        from flax import struct
        sd = struct.dataclass
        @sd
        class StateConfig:
            x: int = 1
    """)
    assert "config-hash" not in rules_of(vs)


def test_config_hash_struct_import_as_exempt():
    # `from flax import struct as fs` — the dotted tail must survive
    # the alias substitution so the struct exemption still fires
    vs = lint("""
        from flax import struct as fs
        @fs.dataclass
        class StateConfig:
            x: int = 1
    """)
    assert "config-hash" not in rules_of(vs)


def test_config_hash_frozen_through_partial_call_alias():
    # `dc = dataclasses.dataclass(frozen=True)` carries its frozen
    # keyword through the alias — no false 'mutable dataclass'
    vs = lint("""
        import dataclasses
        dc = dataclasses.dataclass(frozen=True)
        @dc
        class FooConfig:
            x: int = 1
    """)
    assert "config-hash" not in rules_of(vs)
    vs = lint("""
        import dataclasses
        dc = dataclasses.dataclass(frozen=False)
        @dc
        class FooConfig:
            x: int = 1
    """)
    assert "config-hash" in rules_of(vs)


def test_host_sync_static_conversion_ok():
    # float()/int() of closure statics inside a traced step are
    # trace-time constants, not per-call syncs
    vs = lint("""
        import jax, numpy as np
        cfg_threshold = 0.5
        sizes = np.cumsum([1, 2, 3])
        def make_step(cfg):
            @jax.jit
            def step(state, pub):
                thr = float(cfg_threshold)
                w = int(sizes[-1])
                return state
            return step
    """)
    assert vs == []


def test_prng_key_underived_fires():
    vs = lint("""
        import jax
        def make_step():
            def step(st, pub):
                return jax.random.uniform(st.key, (4,))
            return step
    """)
    assert "prng-key" in rules_of(vs)


def test_prng_key_reuse_fires():
    vs = lint("""
        import jax
        def pick(key, shape):
            a = jax.random.uniform(key, shape)
            b = jax.random.normal(key, shape)
            return a + b
    """)
    assert any(v.rule == "prng-key" and "second sampler" in v.msg for v in vs)


def test_prng_key_constant_in_step_fires():
    vs = lint("""
        import jax
        @jax.jit
        def step(st):
            k = jax.random.key(0)
            return st
    """)
    assert "prng-key" in rules_of(vs)


def test_prng_key_local_alias_of_state_key_fires():
    # provenance, not naming: 'key = st.key' is still raw-key reuse
    vs = lint("""
        import jax
        def make_step():
            def step(st, pub):
                key = st.key
                return jax.random.uniform(key, (4,))
            return step
    """)
    assert "prng-key" in rules_of(vs)


def test_prng_key_disciplined_ok():
    vs = lint("""
        import jax
        def heartbeat(st, tick):
            key = jax.random.fold_in(st.key, tick)
            k1, k2 = jax.random.split(key)
            noise = jax.random.uniform(k1, (4,))
            more = jax.random.uniform(k2, (4,))
            return noise + more
    """)
    assert vs == []


def test_word_dtype_fires():
    vs = lint("""
        import jax.numpy as jnp
        def bit_probe(words):
            return words & 1
    """, rel="ops/bitset.py")
    assert "word-dtype" in rules_of(vs)


def test_word_dtype_augassign_fires():
    vs = lint("""
        import jax.numpy as jnp
        def bit_probe(words):
            words &= 1
            return words
    """, rel="ops/bitset.py")
    assert "word-dtype" in rules_of(vs)


def test_word_dtype_wrapped_ok():
    vs = lint("""
        import jax.numpy as jnp
        def bit_probe(words):
            return words & jnp.uint32(1)
    """, rel="ops/bitset.py")
    assert vs == []


def test_import_exec_fires():
    vs = lint("""
        import jax.numpy as jnp
        TABLE = jnp.zeros((4,))
    """, rel="score/tables.py")
    assert "import-exec" in rules_of(vs)


def test_import_exec_lambda_factory_ok():
    vs = lint("""
        import jax.numpy as jnp
        from flax import struct
        class Info:
            n: int = struct.field(default_factory=lambda: jnp.int32(0))
    """, rel="models/info.py")
    assert vs == []


def test_config_hash_fires():
    vs = lint("""
        import dataclasses
        @dataclasses.dataclass
        class FlapConfig:
            rates: list = dataclasses.field(default_factory=list)
    """, rel="chaos/flap.py")
    got = [v for v in vs if v.rule == "config-hash"]
    assert len(got) == 2  # not frozen + unhashable field


def test_ev_drain_fires():
    vs = simlint.check_ev_drain(
        ["DELIVER_MESSAGE", "LINK_DOWN", "ORPHANED"],
        {"DELIVER_MESSAGE"},
        drain_src="TraceEvent.DELIVER_MESSAGE ... EV.LINK_DOWN counter-only",
        package_refs={"DELIVER_MESSAGE", "LINK_DOWN"},
    )
    msgs = " | ".join(v.msg for v in vs)
    assert "ORPHANED" in msgs                      # undrained + unreferenced
    assert "DELIVER_MESSAGE" not in msgs           # fully wired
    assert sum("LINK_DOWN" in v.msg for v in vs) == 0  # documented counter


def test_ev_drain_telemetry_column_counts_as_drained():
    """Round 11: a sim-only counter whose ``ev_<name>`` column appears
    in telemetry/panel.py counts as drained — the panel records its
    per-round deltas and the reconciliation gate pins them. Without
    the column (and without drain prose) the rule still fires."""
    args = dict(
        ev_names=["DELIVER_MESSAGE", "IWANT_RECOVER"],
        proto_names={"DELIVER_MESSAGE"},
        drain_src="TraceEvent.DELIVER_MESSAGE",  # no IWANT prose at all
        package_refs={"DELIVER_MESSAGE", "IWANT_RECOVER"},
    )
    vs = simlint.check_ev_drain(
        **args, telemetry_src='EV_METRICS = ("ev_iwant_recover",)')
    assert not any("IWANT_RECOVER" in v.msg for v in vs)
    vs = simlint.check_ev_drain(**args, telemetry_src="")
    assert any("IWANT_RECOVER" in v.msg for v in vs)


def test_ev_drain_adversary_counters_negatives():
    """Round 13: the adversary plane's sim-only counters (ADV_DROP /
    ADV_IHAVE_LIE / ADV_GRAFT_SPAM) must each be accumulated somewhere
    AND named by the drain (COUNTER_ONLY_EVENTS) or recorded as a
    telemetry column — seeded breakage of each half fires the rule."""
    adv = ["ADV_DROP", "ADV_IHAVE_LIE", "ADV_GRAFT_SPAM"]
    clean = simlint.check_ev_drain(
        adv, set(),
        drain_src="EV.ADV_DROP, EV.ADV_IHAVE_LIE, EV.ADV_GRAFT_SPAM "
                  "counter-only",
        package_refs=set(adv),
    )
    assert clean == []
    # never accumulated -> dead counter
    vs = simlint.check_ev_drain(
        adv, set(), drain_src="EV.ADV_DROP EV.ADV_IHAVE_LIE "
        "EV.ADV_GRAFT_SPAM", package_refs={"ADV_DROP"})
    assert any("ADV_IHAVE_LIE" in v.msg for v in vs)
    assert any("ADV_GRAFT_SPAM" in v.msg for v in vs)
    # neither drain-documented nor a telemetry column -> undrained
    vs = simlint.check_ev_drain(
        ["ADV_DROP"], set(), drain_src="", package_refs={"ADV_DROP"},
        telemetry_src="")
    assert any("ADV_DROP" in v.msg for v in vs)
    # the telemetry column alone satisfies the consumer contract
    vs = simlint.check_ev_drain(
        ["ADV_DROP"], set(), drain_src="", package_refs={"ADV_DROP"},
        telemetry_src='EV_METRICS = ("ev_adv_drop",)')
    assert not any("ADV_DROP" in v.msg for v in vs)


def test_telemetry_panel_rule_negatives():
    """The panel catalog must mirror the EV enum positionally, and a
    metric that is RECORDED but never RECONCILED is a violation (a
    timeline column the drain-vs-timeline gate never checks)."""
    ev = ["PUBLISH_MESSAGE", "DELIVER_MESSAGE"]
    ok = ["ev_publish_message", "ev_deliver_message"]
    assert simlint.check_telemetry_panel(ev, ok, ok) == []
    # missing / misordered column relabels everything after it
    vs = simlint.check_telemetry_panel(ev, ok[::-1], ok[::-1])
    assert any("enum order" in v.msg for v in vs)
    vs = simlint.check_telemetry_panel(ev, ok[:1], ok[:1])
    assert any("enum order" in v.msg for v in vs)
    # recorded but never reconciled — the negative test the issue pins
    vs = simlint.check_telemetry_panel(ev, ok, ok[:1])
    assert any("never" in v.msg or "missing from RECONCILED" in v.msg
               for v in vs)
    assert all(v.rule == "telemetry-panel" for v in vs)
    # RECONCILED naming a non-recorded column is equally broken
    vs = simlint.check_telemetry_panel(ev, ok, ok + ["ev_ghost"])
    assert any("ev_ghost" in v.msg for v in vs)


def test_telemetry_panel_rule_on_repo_source():
    """The in-tree catalog satisfies the rule, and the AST extractor
    resolves the RECONCILED = EV_METRICS alias + tuple concatenation."""
    import ast

    panel_p = os.path.join(PKG, "telemetry", "panel.py")
    with open(panel_p) as f:
        tree = ast.parse(f.read())
    ev_metrics = simlint._tuple_literal(tree, "EV_METRICS")
    reconciled = simlint._tuple_literal(tree, "RECONCILED")
    assert ev_metrics and reconciled == ev_metrics
    metrics = simlint._tuple_literal(tree, "METRICS")  # ("x",) + EV + (...)
    assert metrics is not None and metrics[0] == "delivery_ratio"
    assert simlint._rule_telemetry_panel(PKG) == []


def test_invariant_registry_rule_negatives():
    """The invariant-registry rule fires on every broken declaration
    shape: missing/unknown engines, bad kind, missing doc, and a
    property no tests/ file references (the untrippable-property
    failure mode), plus an unparseable (computed) registry."""
    known = ("gossipsub", "phase", "floodsub", "randomsub")
    good = {"name": "mesh-ok", "line": 3, "kind": "safety",
            "engines": ["gossipsub", "phase"], "doc": "mesh ⊆ topology"}
    tests_src = 'CORRUPTIONS = [("mesh-ok", corrupt_mesh)]'
    assert simlint.check_invariant_registry([good], known, tests_src) == []
    # no declared applicability
    vs = simlint.check_invariant_registry(
        [{**good, "engines": None}], known, tests_src)
    assert any("applicability" in v.msg for v in vs)
    vs = simlint.check_invariant_registry(
        [{**good, "engines": []}], known, tests_src)
    assert any("applicability" in v.msg for v in vs)
    # an engine outside the catalog
    vs = simlint.check_invariant_registry(
        [{**good, "engines": ["gossipsub", "bitcoin"]}], known, tests_src)
    assert any("applicability" in v.msg for v in vs)
    # kind must be a literal safety|liveness
    vs = simlint.check_invariant_registry(
        [{**good, "kind": "vibes"}], known, tests_src)
    assert any("safety" in v.msg for v in vs)
    # missing doc citation
    vs = simlint.check_invariant_registry(
        [{**good, "doc": None}], known, tests_src)
    assert any("doc" in v.msg for v in vs)
    # registered but untested — the rule the issue pins
    vs = simlint.check_invariant_registry([good], known, "no mention")
    assert any("seeded-violation" in v.msg for v in vs)
    # computed/empty registry is itself a violation
    vs = simlint.check_invariant_registry([], known, tests_src)
    assert any("catalog" in v.msg for v in vs)
    assert all(v.rule == "invariant-registry" for v in vs)


def test_invariant_registry_rule_on_repo_source():
    """The in-tree catalog satisfies the rule: every @invariant call
    parses to a literal declaration (alias tuples resolved), and every
    name has a seeded-violation reference in tests/."""
    import ast

    inv_p = os.path.join(PKG, "oracle", "invariants.py")
    with open(inv_p) as f:
        tree = ast.parse(f.read())
    entries = simlint.registry_entries(tree)
    assert len(entries) >= 12
    names = [e["name"] for e in entries]
    assert "mesh-degree-bounds" in names and "eventual-delivery" in names
    for e in entries:
        assert e["engines"], e
    assert simlint._rule_invariant_registry(PKG) == []


def test_narrow_dtype_rule_negatives():
    """The narrow-dtype rule (round 23): every sub-i32 ``.astype`` in
    device scope must appear, positionally, in the committed
    RANGE_AUDIT.json manifest — an unlisted narrowing cast is an
    unaudited wrap hazard, a listed-but-vanished one is a stale range
    justification."""
    src = textwrap.dedent("""
        import jax.numpy as jnp
        def pack(x):
            a = x.astype(jnp.int16)
            b = x.astype("uint8")
            return a, b.astype(np.float32)  # widening/float casts pass
    """)
    sites = simlint.narrow_astype_sites(src, "ops/broken.py")
    assert [dt for _ln, dt in sites] == ["int16", "uint8"]

    # unlisted site (seeded negative)
    vs = simlint.check_narrow_dtype({"ops/broken.py": sites}, {})
    assert vs and all(v.rule == "narrow-dtype" for v in vs)
    assert any("do not match the committed RANGE_AUDIT manifest" in v.msg
               for v in vs)
    # exact positional match passes; a reorder or a stale entry fails
    assert simlint.check_narrow_dtype(
        {"ops/broken.py": sites}, {"ops/broken.py": ("int16", "uint8")}) == []
    assert simlint.check_narrow_dtype(
        {"ops/broken.py": sites}, {"ops/broken.py": ("uint8", "int16")})
    assert simlint.check_narrow_dtype(
        {}, {"ops/gone.py": ("int8",)})


def test_narrow_dtype_rule_on_repo_source():
    """The in-tree device scope matches the committed manifest exactly
    (no sub-i32 cast today), and a missing artifact is itself a
    violation, not a silent pass."""
    assert simlint._rule_narrow_dtype(PKG) == []
    vs = simlint._rule_narrow_dtype(os.path.join(PKG, "analysis"))
    assert vs and "RANGE_AUDIT.json is missing" in vs[0].msg


def test_allowlist_filters_by_qual(tmp_path):
    vs = lint("""
        def drain(state):
            return state.events.item()
    """)
    assert vs
    allow = [("host-sync", "models/broken.py", "drain")]
    kept, allowed = simlint.filter_allowed(vs, allow)
    assert kept == [] and len(allowed) == len(vs)
    # a different qualname does not match
    kept2, _ = simlint.filter_allowed(
        vs, [("host-sync", "models/broken.py", "other")])
    assert kept2 == vs


def test_allowlist_parse_rejects_garbage(tmp_path):
    p = tmp_path / "ALLOWLIST"
    p.write_text("host-sync models/x.py::f extra-token\n")
    with pytest.raises(ValueError):
        simlint.load_allowlist(str(p))


def test_repo_lints_clean():
    """The enforced state: zero unallowed violations on the package
    (and, since round 19, the tests/ + scripts/ call-site trees under
    the donated-reuse rule — simlint.run covers both)."""
    kept, _allowed = simlint.run(PKG)
    assert kept == [], "\n".join(v.format() for v in kept)


def _environment_reads(src: str) -> list:
    """Lines of ``os.environ`` / ``os.getenv`` (however imported)."""
    import ast

    hits = []
    for node in ast.walk(ast.parse(src)):
        if (isinstance(node, ast.Attribute)
                and node.attr in ("environ", "getenv")):
            hits.append(node.lineno)
        elif (isinstance(node, ast.ImportFrom) and node.module == "os"
              and any(a.name in ("environ", "getenv") for a in node.names)):
            hits.append(node.lineno)
    return hits


@pytest.mark.parametrize("part", ["models", "ops", "score", "chaos",
                                  "routers", "state.py"])
def test_engines_read_no_environment(part):
    """What an engine traces is a function of its arguments: no process-
    wide switch under the traced code (a build that differs by the
    caller's shell is a second, untested program)."""
    assert _environment_reads("import os\nx = os.environ.get('A')") == [2]
    assert _environment_reads("from os import getenv") == [1]
    path = os.path.join(PKG, part)
    files = ([path] if path.endswith(".py") else
             glob.glob(os.path.join(path, "**", "*.py"), recursive=True))
    assert files
    found = {}
    for f in files:
        with open(f) as fh:
            lines = _environment_reads(fh.read())
        if lines:
            found[os.path.relpath(f, PKG)] = lines
    assert found == {}


def test_package_does_not_import_pallas():
    """One data plane: the engines' import chain loads no Pallas (every
    cell's set-up pays what the package imports)."""
    import subprocess
    import sys

    code = ("import sys; "
            "import go_libp2p_pubsub_tpu.models.gossipsub_phase; "
            "print(sorted(m for m in sys.modules if 'pallas' in m))")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, check=True,
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu")).stdout
    assert out.strip().splitlines()[-1] == "[]"


# ---------------------------------------------------------------------------
# donated-reuse: the call-site rule (round 19) — seeded negatives


def dlint(src, rel="tests/test_broken.py"):
    return simlint.lint_donated_reuse(textwrap.dedent(src), rel)


def test_donated_reuse_fires_on_reuse_after_step():
    vs = dlint("""
        def t(step, fresh):
            st = fresh()
            out = step(st, po)
            return st.events
    """)
    assert rules_of(vs) == {"donated-reuse"}
    assert "DONATED" in vs[0].msg


def test_donated_reuse_fires_on_window_call():
    vs = dlint("""
        def t(window, fresh, xs):
            states = fresh()
            out, ys = window(states, xs)
            return states
    """)
    assert rules_of(vs) == {"donated-reuse"}


def test_donated_reuse_fires_on_module_level_engine_step():
    vs = dlint("""
        def t(net, st):
            out = floodsub_step(net, st, po, pt, pv)
            return st.events
    """)
    assert rules_of(vs) == {"donated-reuse"}


def test_donated_reuse_fires_on_loop_backedge():
    # the canonical loop form of the footgun: donation inside a loop,
    # state never rebound — iteration 2 reads the donated buffers
    vs = dlint("""
        def t(step, fresh):
            st = fresh()
            for i in range(4):
                out = step(st, po)
            return out
    """)
    assert rules_of(vs) == {"donated-reuse"}


def test_donated_reuse_fresh_build_inside_loop_ok():
    vs = dlint("""
        def t(step, fresh):
            for i in range(4):
                st = fresh()
                out = step(st, po)
            return out
    """)
    assert vs == []


def test_donated_reuse_multiline_call_ok():
    # a donating call wrapped across lines must not read its own
    # argument as after-donation reuse
    vs = dlint("""
        def t(step, fresh):
            st = fresh()
            out = step(
                st, po)
            return out
    """)
    assert vs == []


def test_donated_reuse_rebind_idiom_ok():
    vs = dlint("""
        def t(step, fresh):
            st = fresh()
            for i in range(4):
                st = step(st, po)
            return st.events
    """)
    assert vs == []


def test_donated_reuse_fresh_rebind_after_donation_ok():
    vs = dlint("""
        def t(step, fresh):
            st = fresh()
            out = step(st, po)
            st = fresh()
            return st.events
    """)
    assert vs == []


def test_donated_reuse_make_and_observer_calls_exempt():
    # make_* builds a step (never donates); hook.on_step observes the
    # LIVE state (never donates) — both must stay clean
    vs = dlint("""
        def t(cfg, net, fresh, hook):
            st = fresh()
            step = make_gossipsub_step(cfg, net)
            st = step(st, po)
            hook.on_step(0, st)
            return st.events
    """)
    assert vs == []


def test_donated_reuse_callsite_trees_clean():
    """tests/ and scripts/ follow the donation discipline — the rule
    holds repo-wide with the ALLOWLIST still empty."""
    kept = simlint.lint_callsites(ROOT)
    assert kept == [], "\n".join(v.format() for v in kept)


# ---------------------------------------------------------------------------
# trace guards: each fires on a deliberately broken harness


def _harness(fn, state, args_of=None, **jit_kw):
    return EngineHarness(
        name="broken",
        jit_fn=jax.jit(fn, **jit_kw),
        state=state,
        make_args=args_of or (lambda i: (jnp.ones((4,), jnp.int32),)),
        static_kwargs={},
    )


def test_guard_strict_dtype_fires():
    # int32 state mixed with a uint32 operand: standard mode silently
    # promotes, strict mode is the gate
    h = _harness(
        lambda s, a: {"x": s["x"] + a.astype(jnp.uint32)},
        {"x": jnp.zeros((4,), jnp.int32)},
    )
    with pytest.raises(GuardViolation) as ei:
        guards.strict_trace(h)
    assert ei.value.guard == "strict-dtype"


def test_guard_schema_weak_type_fires():
    # a pure python-scalar constant in the carry is a weak-typed leaf:
    # next call re-traces it as an input with a DIFFERENT aval -> the
    # recompile-per-round bug the schema guard exists to catch
    h = _harness(lambda s, a: {"x": s["x"], "t": jnp.asarray(0.0)},
                 {"x": jnp.zeros((4,), jnp.float32)})
    out = jax.eval_shape(lambda s: h.jit_fn(s, jnp.ones((4,), jnp.int32)),
                         h.state)
    assert any(r["weak_type"] for r in guards.schema_of(out))
    with pytest.raises(GuardViolation) as ei:
        guards.check_schema(h, out, None)
    assert ei.value.guard == "schema"


def test_guard_schema_drift_fires():
    h = _harness(lambda s, a: s, {"x": jnp.zeros((4,), jnp.int32)})
    out = jax.eval_shape(lambda s: s, h.state)
    rows = guards.schema_of(out)
    doctored = json.loads(json.dumps(rows))
    doctored[0]["dtype"] = "int64"
    baseline = {"engines": {"broken": {"leaves": doctored}}}
    with pytest.raises(GuardViolation) as ei:
        guards.check_schema(h, out, baseline)
    assert ei.value.guard == "schema"
    assert guards.diff_schema("broken", rows, doctored)


def test_guard_schema_missing_engine_fires():
    h = _harness(lambda s, a: s, {"x": jnp.zeros((4,), jnp.int32)})
    out = jax.eval_shape(lambda s: s, h.state)
    with pytest.raises(GuardViolation):
        guards.check_schema(h, out, {"engines": {}})


def test_guard_donation_fires_and_passes():
    state = {"x": jnp.zeros((8,), jnp.float32)}
    undonated = _harness(lambda s, a: {"x": s["x"] + 1.0}, state)
    with pytest.raises(GuardViolation) as ei:
        guards.check_donation(undonated)
    assert ei.value.guard == "donation"
    donated = _harness(lambda s, a: {"x": s["x"] + 1.0}, state,
                       donate_argnums=0)
    guards.check_donation(donated)


def test_guard_recompile_sentinel_fires():
    # growing arg shapes cache-bust: one compile per round
    h = _harness(
        lambda s, a: s,
        {"x": jnp.zeros((4,), jnp.int32)},
        args_of=lambda i: (jnp.ones((4 + i,), jnp.int32),),
    )
    with pytest.raises(GuardViolation) as ei:
        guards.run_rounds_guarded(h, rounds=3)
    assert ei.value.guard == "recompile"


def test_guard_transfer_fires():
    # a numpy array sneaking into the round loop = an implicit
    # host->device transfer per call; the guard turns it into an error
    h = _harness(
        lambda s, a: {"x": s["x"] + a},
        {"x": jnp.zeros((4,), jnp.int32)},
        args_of=lambda i: (np.ones((4,), np.int32),),
    )
    with pytest.raises(GuardViolation) as ei:
        guards.run_rounds_guarded(h, rounds=2)
    assert ei.value.guard == "transfer"


# ---------------------------------------------------------------------------
# positive: one real engine end-to-end + the committed baseline


def test_floodsub_guards_end_to_end():
    h = guards.build_engine("floodsub")
    out = guards.strict_trace(h)
    rows = guards.check_schema(h, out, None)
    guards.check_donation(h)
    guards.run_rounds_guarded(h)
    # the committed STATE_SCHEMA.json matches what this container traces
    baseline = guards.load_baseline(ROOT)
    assert baseline is not None, "STATE_SCHEMA.json not committed"
    want = baseline["engines"]["floodsub"]["leaves"]
    assert guards.diff_schema("floodsub", rows, want) == []


def test_schema_engines_complete():
    baseline = guards.load_baseline(ROOT)
    assert baseline is not None
    assert set(baseline["engines"]) == set(guards.ENGINES)


# ---------------------------------------------------------------------------
# the declarative row registry (round 16): every derived harness is one
# registry line; the new lifted-score and phase+csr rows are present
# and their builders/runners resolve


def test_guard_registry_rows():
    names = [r.name for r in guards.DERIVED_ROWS]
    assert names == ["ensemble", "telemetry", "csr", "phase_csr", "lifted",
                     "csr_fused", "lifted_fused", "dynamic",
                     "idontwant", "choke"]
    for row in guards.DERIVED_ROWS:
        assert callable(getattr(guards, row.runner)), row.runner
        assert row.base in guards.ENGINES, row
    assert guards.ALL_ROWS == tuple(guards.ENGINES) + tuple(names)


def test_lifted_plane_pair_distinct():
    import numpy as np

    pa, pb = guards.lifted_plane_pair()
    # the A/B sentinel is vacuous unless the two planes differ on every
    # surface the lift exists to sweep
    for leaf in ("w2", "behaviour_penalty_weight", "gossip_threshold",
                 "publish_threshold", "topic_score_cap"):
        assert not np.array_equal(np.asarray(getattr(pa, leaf)),
                                  np.asarray(getattr(pb, leaf))), leaf


def test_lifted_schema_must_equal_base():
    # seeded negative: a state tree that differs from the base rows
    # trips the equal-base schema check with the lifted message
    h = _harness(lambda s, a: {"x": s["x"], "extra": jnp.zeros((2,))},
                 {"x": jnp.zeros((4,), jnp.int32)})
    out = jax.eval_shape(lambda s: h.jit_fn(s, jnp.ones((4,), jnp.int32)),
                         h.state)
    base_rows = [{"path": "['x']", "dtype": "int32", "shape": [4],
                  "weak_type": False}]
    with pytest.raises(GuardViolation) as ei:
        guards.check_schema_equal(h, out, base_rows, "gossipsub",
                                  "the lifted score plane leaked into "
                                  "the state tree")
    assert ei.value.guard == "schema"
    assert "leaked" in str(ei.value)
