"""lift — the liftability dataflow pass (docs/DESIGN.md §16).

Answers, as a machine-checked artifact instead of code-review folklore,
the question the ROADMAP's parameter-search item turns on: *which
config knobs can become traced parameter planes, and which must stay
jit statics?* An interprocedural AST dataflow pass over the device
scope (``models/``, ``ops/``, ``score/``, ``chaos/``, ``state.py``)
tracks every read of a ``*Config`` / score-parameter field — through
single-assignment local aliases, closure captures, and cross-function
call edges — and classifies each use site:

  SHAPE   the read feeds program STRUCTURE: an array shape or index
          bound, a Python ``if``/``while``/``assert``/ternary test, a
          host conversion (``float``/``int``/``bool``/``np.*`` — a
          value baked at trace time), a dtype decision, or a
          ``static_argnames`` tuple. Such a field must remain a jit
          static: tracing it would either fail or silently bake one
          branch.
  VALUE   pure traced arithmetic — compares, multiplies, ``jnp.where``
          selects, traced-index gathers. Liftable: replacing the baked
          constant with a traced scalar/row yields the same ops on the
          same dtypes, bit-exact at matched values.

Per-field verdicts aggregate the sites: any un-excused SHAPE site ⇒
``SHAPE``; SHAPE sites all covered by the declared :data:`ELISION_OK`
table (build-time elision decisions that are *value-neutral* and that
the lifted engines resolve conservatively — see each entry's note) ⇒
``VALUE_GUARDED``; otherwise ``VALUE``. The committed
``LIFT_AUDIT.json`` (``make lift-audit``; byte-identical reproduction
gated like MEM_AUDIT.json, ``LIFT_UPDATE=1`` rewrites) carries every
verdict with its evidence sites, and ``scripts/lift_audit.py`` asserts
the shipped :class:`score.params.ScoreParams` plane lifts exactly the
fields the audit proves liftable.

The alias resolver here (:func:`single_assign_exprs`) is shared with
simlint, which previously missed traced expressions read through a
local alias (``w = jnp.any(x); if w:``) — the round-16 simlint fix.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os

#: package-relative prefixes the pass scans (the device scope — the
#: code that traces into jitted steps or builds their constants)
DEVICE_SCOPE = ("models/", "ops/", "score/", "chaos/", "state.py")

#: files never scanned (generated code)
_SKIP_DIRS = ("pb", "__pycache__")

#: parameter-name conventions that root the dataflow (the repo's
#: calling convention is uniform — handlers take ``cfg``, score math
#: takes ``params``/``score_params``, the gathered per-topic dict is
#: ``tp``); annotations override where present. A ``FIELD:`` value
#: roots the parameter at a single field (its uses ARE reads of that
#: field).
PARAM_ROOTS = {
    "cfg": "GossipSubConfig",
    "config": "GossipSubConfig",
    "params": "PeerScoreParams",
    "score_params": "PeerScoreParams",
    "thresholds": "PeerScoreThresholds",
    "gater_params": "PeerGaterParams",
    "tp": "TP",
    "tpa": "TPA",
    "consts": "CONSTS",
    # the threshold-source convention (round 16): handlers read
    # thresholds through ``thr`` — cfg on the static path, the traced
    # ScoreParams plane on the lifted one; either way the read is a
    # GossipSubConfig-namespace threshold use
    "thr": "GossipSubConfig",
    # the mesh-degree-source convention (round 20): handlers read the
    # degree knobs through ``msh`` — cfg on the static path, the traced
    # MeshParams plane on the candidate-lifted one; either way the read
    # is a GossipSubConfig-namespace degree use
    "msh": "GossipSubConfig",
    "window_rounds_t":
        "FIELD:TopicScoreParams.mesh_message_deliveries_window",
}

#: constructor calls whose RESULT is a tracked aggregate — a local
#: assigned from one roots like the aggregate itself (the phase/step
#: builders' ``consts = prepare_step_consts(...)``)
_CTOR_ROOTS = {"prepare_step_consts": "CONSTS"}

#: annotation -> root kind (beats the name convention)
ANNOT_ROOTS = {
    "GossipSubConfig": "GossipSubConfig",
    "PeerScoreParams": "PeerScoreParams",
    "PeerScoreThresholds": "PeerScoreThresholds",
    "PeerGaterParams": "PeerGaterParams",
    "TopicParamsArrays": "TPA",
    "StepConsts": "CONSTS",
}

#: attribute map of the StepConsts aggregate (models/gossipsub.py)
CONSTS_ATTRS = {
    "score_params": "PeerScoreParams",
    "tp": "TP",
    "tpa": "TPA",
    "window_rounds_t": "FIELD:TopicScoreParams.mesh_message_deliveries_window",
}

#: gathered-tp dict key / TopicParamsArrays row -> audit field name
#: (provenance through score.engine.TopicParamsArrays.build; `scored`
#: derives from topic-map membership, not a TopicScoreParams field)
TP_KEY_FIELD = {
    "scored": "TopicParamsArrays.scored",
    "topic_weight": "TopicScoreParams.topic_weight",
    "w1": "TopicScoreParams.time_in_mesh_weight",
    "quantum_ticks": "TopicScoreParams.time_in_mesh_quantum",
    "cap1": "TopicScoreParams.time_in_mesh_cap",
    "w2": "TopicScoreParams.first_message_deliveries_weight",
    "decay2": "TopicScoreParams.first_message_deliveries_decay",
    "cap2": "TopicScoreParams.first_message_deliveries_cap",
    "w3": "TopicScoreParams.mesh_message_deliveries_weight",
    "decay3": "TopicScoreParams.mesh_message_deliveries_decay",
    "cap3": "TopicScoreParams.mesh_message_deliveries_cap",
    "thr3": "TopicScoreParams.mesh_message_deliveries_threshold",
    "window_rounds": "TopicScoreParams.mesh_message_deliveries_window",
    "activation_ticks": "TopicScoreParams.mesh_message_deliveries_activation",
    "w3b": "TopicScoreParams.mesh_failure_penalty_weight",
    "decay3b": "TopicScoreParams.mesh_failure_penalty_decay",
    "w4": "TopicScoreParams.invalid_message_deliveries_weight",
    "decay4": "TopicScoreParams.invalid_message_deliveries_decay",
}

#: calls whose argument values are baked at trace time (all-args shape
#: sinks unless a position tuple narrows it)
_SHAPE_SINKS: dict = {
    "float": None, "int": None, "bool": None, "range": None, "len": None,
    "np.full": (0,), "np.zeros": None, "np.ones": None, "np.arange": None,
    "np.cumsum": None, "np.asarray": None, "np.array": None,
    "np.any": None, "np.all": None, "np.flatnonzero": None,
    "jnp.zeros": (0,), "jnp.ones": (0,), "jnp.empty": (0,),
    "jnp.full": (0,), "jnp.arange": (0, 1, 2),
}
#: method-call sinks (attribute tail): every arg is a shape/layout
_SHAPE_METHOD_SINKS = frozenset({"reshape", "broadcast_to", "transpose"})

#: functions whose bodies never trace (pure host/build helpers) —
#: methods of the config/param structs themselves plus the explicit
#: build-time validators; their reads are construction, not use
_BUILD_CLASSES = ("Config", "Params", "Thresholds", "TopicParamsArrays")
_BUILD_FUNCS = frozenset({"validate", "validation_timed_out", "build",
                          "init", "empty", "from_config"})

#: fields lifted into the traced ScoreParams plane (round 16). The
#: audit must prove each VALUE or VALUE_GUARDED — scripts/lift_audit.py
#: and tests/test_lift.py cross-check this tuple against
#: score.params.LIFTED_FIELD_NAMES so the pass and the plane cannot
#: drift.
SCORE_PLANE_FIELDS = (
    "GossipSubConfig.accept_px_threshold",
    "GossipSubConfig.gossip_threshold",
    "GossipSubConfig.graylist_threshold",
    "GossipSubConfig.opportunistic_graft_threshold",
    "GossipSubConfig.publish_threshold",
    "PeerScoreParams.behaviour_penalty_decay",
    "PeerScoreParams.behaviour_penalty_threshold",
    "PeerScoreParams.behaviour_penalty_weight",
    "PeerScoreParams.decay_to_zero",
    "PeerScoreParams.ip_colocation_factor_weight",
    "PeerScoreParams.topic_score_cap",
    "TopicParamsArrays.scored",
    "TopicScoreParams.first_message_deliveries_cap",
    "TopicScoreParams.first_message_deliveries_decay",
    "TopicScoreParams.first_message_deliveries_weight",
    "TopicScoreParams.invalid_message_deliveries_decay",
    "TopicScoreParams.invalid_message_deliveries_weight",
    "TopicScoreParams.mesh_failure_penalty_decay",
    "TopicScoreParams.mesh_failure_penalty_weight",
    "TopicScoreParams.mesh_message_deliveries_activation",
    "TopicScoreParams.mesh_message_deliveries_cap",
    "TopicScoreParams.mesh_message_deliveries_decay",
    "TopicScoreParams.mesh_message_deliveries_threshold",
    "TopicScoreParams.mesh_message_deliveries_weight",
    "TopicScoreParams.mesh_message_deliveries_window",
    "TopicScoreParams.time_in_mesh_cap",
    "TopicScoreParams.time_in_mesh_quantum",
    "TopicScoreParams.time_in_mesh_weight",
    "TopicScoreParams.topic_weight",
)

#: fields lifted into the traced MeshParams plane (round 20): the mesh
#: degree knobs, liftable once every selection width rides the
#: masked-width kernels (ops/select.masked_width_* — rank the full
#: padded axis, clip the traced width). Cross-checked against
#: score.params.MESH_LIFTED_FIELD_NAMES by scripts/lift_audit.py.
MESH_PLANE_FIELDS = (
    "GossipSubConfig.D",
    "GossipSubConfig.Dhi",
    "GossipSubConfig.Dlazy",
    "GossipSubConfig.Dlo",
    "GossipSubConfig.Dout",
    "GossipSubConfig.Dscore",
    "GossipSubConfig.gossip_factor",
)

#: fields DECLARED shape regardless of site classification, with the
#: structural reason — the audit's guard against lifting something
#: whose staticness is a program-structure contract rather than a
#: syntactic property
DECLARED_SHAPE = {
    "PeerScoreParams.app_specific_weight": (
        "a non-zero P5 weight gates the app-score cross-peer gather "
        "(one halo-permute set on the sharded mesh; compute_scores and "
        "the phase head's include_app) — program structure, census-"
        "pinned, so the weight stays a build-time static"
    ),
}

#: (file, outermost qualname, field) triples whose SHAPE/branch sites
#: are *value-neutral build-time elisions* the lifted engines resolve
#: conservatively — each entry names its mitigation; a field whose
#: only SHAPE sites are covered here verdicts VALUE_GUARDED
ELISION_OK = {
    ("score/engine.py", "compute_scores",
     "PeerScoreParams.topic_score_cap"):
        "static cap>0 elision; the lifted path applies "
        "jnp.where(cap > 0, min(score, cap), score) — value-identical "
        "at matched values (score/engine.py)",
    ("models/gossipsub_phase.py", "make_gossipsub_phase_step",
     "TopicScoreParams.mesh_message_deliveries_weight"):
        "p3_live static weight elision; lifted builds pin "
        "p3_live=True (all attribution planes live)",
    ("models/gossipsub_phase.py", "make_gossipsub_phase_step",
     "TopicScoreParams.mesh_failure_penalty_weight"):
        "p3_live static weight elision; lifted builds pin p3_live=True",
    ("models/gossipsub_phase.py", "make_gossipsub_phase_step",
     "TopicScoreParams.mesh_message_deliveries_threshold"):
        "p3_live static weight elision; lifted builds pin p3_live=True",
    ("models/gossipsub_phase.py", "make_gossipsub_phase_step",
     "TopicScoreParams.invalid_message_deliveries_weight"):
        "p4_live static weight elision; lifted builds pin p4_live=True",
}


@dataclasses.dataclass(frozen=True)
class Site:
    """One classified use site of a tracked field."""

    field: str
    rel: str
    line: int
    qual: str
    kind: str      # "value" | "shape" | "branch"
    context: str   # why / what construct

    def as_row(self) -> dict:
        return {"file": self.rel, "line": self.line, "qual": self.qual,
                "kind": self.kind, "context": self.context}


# ---------------------------------------------------------------------------
# alias resolution (shared with simlint)


def single_assign_exprs(fn: ast.AST) -> dict:
    """``{name: value_expr}`` for every local assigned EXACTLY once in
    ``fn``'s own scope via a plain ``name = expr`` statement (no tuple
    targets, no augmented assigns; names also bound by for/with/comp
    targets or re-assigned anywhere are dropped). This is the
    single-assignment alias map both this pass and simlint resolve
    reads through — the round-16 alias-blindness fix."""
    counts: dict = {}
    exprs: dict = {}
    poisoned: set = set()

    def bump(name, expr=None):
        counts[name] = counts.get(name, 0) + 1
        if expr is not None:
            exprs[name] = expr

    for node in _walk_shallow(fn):
        if isinstance(node, ast.Assign):
            if (len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                bump(node.targets[0].id, node.value)
            else:
                for tgt in node.targets:
                    for t in ast.walk(tgt):
                        if isinstance(t, ast.Name):
                            bump(t.id)
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            t = node.target
            if isinstance(t, ast.Name):
                bump(t.id)
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            for t in ast.walk(node.target):
                if isinstance(t, ast.Name):
                    poisoned.add(t.id)
        elif isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                if item.optional_vars is not None:
                    for t in ast.walk(item.optional_vars):
                        if isinstance(t, ast.Name):
                            poisoned.add(t.id)
        elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                               ast.GeneratorExp)):
            for gen in node.generators:
                for t in ast.walk(gen.target):
                    if isinstance(t, ast.Name):
                        poisoned.add(t.id)
        elif isinstance(node, ast.NamedExpr):
            if isinstance(node.target, ast.Name):
                bump(node.target.id)
    return {n: e for n, e in exprs.items()
            if counts.get(n) == 1 and n not in poisoned}


def name_copy_closure(aliases: dict, seed: set) -> set:
    """Transitive closure of ``seed`` through BARE-NAME single
    assignments (``v = w``) in an alias map from
    :func:`single_assign_exprs`. Deliberately Name-copy-only: derived
    expressions (``n = x.shape[-1]``, ``flag = x is None``) change
    what the value IS, so each consumer decides its own seeds — this
    is the one propagation rule every alias-aware simlint rule
    shares."""
    out = set(seed)
    for _ in range(len(aliases)):
        grew = False
        for n, e in aliases.items():
            if n not in out and isinstance(e, ast.Name) and e.id in out:
                out.add(n)
                grew = True
        if not grew:
            break
    return out


def _walk_shallow(fn: ast.AST):
    """ast.walk that does not descend into nested function bodies."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
            stack.extend(ast.iter_child_nodes(node))


# ---------------------------------------------------------------------------
# reference resolution


def _annot_root(annot) -> str | None:
    if annot is None:
        return None
    try:
        src = ast.unparse(annot)
    except Exception:  # pragma: no cover
        return None
    for name, kind in ANNOT_ROOTS.items():
        if name in src:
            return kind
    return None


def _param_env(fn: ast.FunctionDef) -> dict:
    env = {}
    for a in list(fn.args.args) + list(fn.args.kwonlyargs):
        kind = _annot_root(a.annotation)
        if kind is None:
            kind = PARAM_ROOTS.get(a.arg)
        if kind is not None:
            env[a.arg] = kind
    return env


class _Resolver:
    """Resolves an expression to a tracked root kind ('GossipSubConfig',
    'TP', ...) or a field ref ('FIELD:<name>') against a lexical env
    chain plus the function's single-assignment alias map."""

    def __init__(self, env: dict, aliases: dict):
        self.env = env          # name -> kind or "FIELD:..."
        self.aliases = aliases  # name -> value expr

    def resolve(self, node, depth: int = 0):
        if depth > 8 or node is None:
            return None
        if isinstance(node, ast.Name):
            got = self.env.get(node.id)
            if got is not None:
                return got
            alias = self.aliases.get(node.id)
            if alias is not None and alias is not node:
                return self.resolve(alias, depth + 1)
            return None
        if isinstance(node, ast.Attribute):
            base = self.resolve(node.value, depth + 1)
            if base is None or base.startswith("FIELD:"):
                return None
            if base == "CONSTS":
                return CONSTS_ATTRS.get(node.attr)
            if base == "TPA":
                f = TP_KEY_FIELD.get(node.attr)
                return f"FIELD:{f}" if f else None
            if base in ("GossipSubConfig", "PeerScoreParams",
                        "PeerScoreThresholds", "PeerGaterParams",
                        "TopicScoreParams"):
                return f"FIELD:{base}.{node.attr}"
            return None
        if isinstance(node, ast.Subscript):
            base = self.resolve(node.value, depth + 1)
            if base == "TP":
                sl = node.slice
                if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
                    f = TP_KEY_FIELD.get(sl.value)
                    return f"FIELD:{f}" if f else None
            return None
        if isinstance(node, ast.Call):
            fname = node.func.id if isinstance(node.func, ast.Name) else None
            return _CTOR_ROOTS.get(fname)
        return None


# ---------------------------------------------------------------------------
# site classification


def _call_root(node) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover
        return ""


def _classify(node, parents: dict, rel: str) -> tuple:
    """(kind, context) for a tracked read at ``node`` by walking the
    ancestor chain up to its enclosing statement."""
    anc = parents.get(id(node))
    chain = []
    while anc is not None:
        chain.append(anc)
        anc = parents.get(id(anc))
    prev = node
    for a in chain:
        # Python-branch tests: structure decisions
        if isinstance(a, (ast.If, ast.While)) and prev is a.test:
            return "branch", f"python {type(a).__name__.lower()} test"
        if isinstance(a, ast.Assert) and prev is a.test:
            return "branch", "assert test"
        if isinstance(a, ast.IfExp) and prev is a.test:
            return "branch", "conditional-expression test"
        # slice bounds: index/extent decisions
        if isinstance(a, ast.Slice) and prev in (a.lower, a.upper, a.step):
            return "shape", "slice bound"
        # shape/host-conversion call sinks
        if isinstance(a, ast.Call) and prev in a.args:
            root = _call_root(a.func)
            pos = a.args.index(prev)
            sink = _SHAPE_SINKS.get(root)
            if root in _SHAPE_SINKS and (sink is None or pos in sink):
                return "shape", f"{root}(...) arg {pos} is a trace-time constant"
            if (isinstance(a.func, ast.Attribute)
                    and a.func.attr in _SHAPE_METHOD_SINKS):
                return "shape", f".{a.func.attr}(...) layout argument"
        if isinstance(a, ast.keyword) and a.arg in (
                "shape", "dtype", "static_argnames", "length", "axis"):
            return "shape", f"{a.arg}= trace-time keyword"
        if isinstance(a, ast.stmt):
            break
        prev = a
    return "value", "traced arithmetic/compare"


# ---------------------------------------------------------------------------
# per-module analysis


def _direct_defs(node):
    """FunctionDefs belonging to ``node``'s own scope — at any
    statement depth (a def nested under an ``if`` still binds in the
    enclosing scope: heartbeat's ``_oppo_grafts``), but never inside
    another def's body."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        child = stack.pop()
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield child
            continue
        if isinstance(child, ast.ClassDef):
            continue
        stack.extend(ast.iter_child_nodes(child))


def _iter_functions(tree: ast.Module):
    """(qual, fn, class_chain) for every def, outermost first."""
    out = []

    def visit(prefix, node, classes):
        for child in _direct_defs(node):
            qual = f"{prefix}.{child.name}" if prefix else child.name
            out.append((qual, child, classes))
            visit(qual, child, classes)
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                cq = f"{prefix}.{child.name}" if prefix else child.name
                visit(cq, child, classes + (child.name,))

    visit("", tree, ())
    return out


def _is_build_scope(qual: str, classes: tuple, fn_name: str) -> bool:
    if fn_name in _BUILD_FUNCS:
        return True
    return any(c.endswith(_BUILD_CLASSES) for c in classes)


def _parent_map(fn: ast.AST) -> dict:
    parents: dict = {}
    stack = [fn]
    while stack:
        node = stack.pop()
        for child in ast.iter_child_nodes(node):
            # do not cross into nested defs: each is analyzed in its
            # own scope with the lexical env chained in
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            parents[id(child)] = node
            stack.append(child)
    return parents


def analyze_source(src: str, rel: str,
                   inherited: dict | None = None) -> list:
    """All classified sites of one module. ``inherited`` maps
    ``funcname -> {param: kind}`` roots propagated from call sites in
    other modules (the interprocedural pass feeds it)."""
    tree = ast.parse(src)
    inherited = inherited or {}
    sites: list[Site] = []
    # lexical env chain: qual -> env of that function
    envs: dict = {}
    fns = list(_iter_functions(tree))
    by_qual = {q: f for q, f, _ in fns}
    for qual, fn, classes in fns:
        env = {}
        parts = qual.split(".")
        for i in range(len(parts) - 1):
            outer = by_qual.get(".".join(parts[: i + 1]))
            if outer is not None:
                env.update(envs.get(".".join(parts[: i + 1]), {}))
        env.update(_param_env(fn))
        env.update(inherited.get(fn.name, {}))
        envs[qual] = env
        if _is_build_scope(qual, classes, fn.name):
            continue
        aliases = single_assign_exprs(fn)
        res = _Resolver(env, aliases)
        # field-level names: parameters rooted at one field (inherited
        # interprocedural roots, FIELD: conventions) plus local
        # single-assignment aliases of a field read — their USES
        # classify at the alias's declared field
        field_names = {n: k[6:] for n, k in env.items()
                       if isinstance(k, str) and k.startswith("FIELD:")}
        for name, expr in aliases.items():
            got = res.resolve(expr)
            if got and got.startswith("FIELD:"):
                field_names[name] = got[6:]
        parents = _parent_map(fn)
        for node in _walk_shallow(fn):
            field = None
            if isinstance(node, (ast.Attribute, ast.Subscript)):
                got = res.resolve(node)
                if got and got.startswith("FIELD:"):
                    par = parents.get(id(node))
                    # skip if this node is part of a larger tracked
                    # chain (cfg.chaos.loss -> classify outermost only)
                    if isinstance(par, ast.Attribute):
                        outer = res.resolve(par)
                        if outer and outer.startswith("FIELD:"):
                            continue
                    # a method INVOCATION (cfg.validate()) is not a
                    # field read
                    if isinstance(par, ast.Call) and par.func is node:
                        continue
                    field = got[6:]
            elif isinstance(node, ast.Name) and node.id in field_names:
                # a use of the alias name, not its defining assignment
                par = parents.get(id(node))
                if isinstance(par, ast.Assign) and node in par.targets:
                    continue
                field = field_names[node.id]
            if field is None:
                continue
            kind, ctx = _classify(node, parents, rel)
            sites.append(Site(field, rel, node.lineno, qual, kind, ctx))
    return sites


# ---------------------------------------------------------------------------
# interprocedural root propagation


def _call_edges(tree: ast.Module, envs_of, known_fns: set) -> list:
    """(callee_name, param_name, kind) edges: a tracked root passed as
    an argument to a known module-level function binds that root to
    the callee's parameter."""
    edges = []
    fns = list(_iter_functions(tree))
    by_qual = {q: f for q, f, _ in fns}
    for qual, fn, classes in fns:
        env = {}
        parts = qual.split(".")
        for i in range(len(parts)):
            outer = by_qual.get(".".join(parts[: i + 1]))
            if outer is not None:
                env.update(_param_env(outer))
        aliases = single_assign_exprs(fn)
        res = _Resolver(env, aliases)
        for node in _walk_shallow(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = node.func.id if isinstance(node.func, ast.Name) else None
            if callee not in known_fns:
                continue
            callee_fn = envs_of.get(callee)
            if callee_fn is None:
                continue
            pos_params = [a.arg for a in callee_fn.args.args]
            for i, arg in enumerate(node.args):
                got = res.resolve(arg)
                if got is not None and i < len(pos_params):
                    edges.append((callee, pos_params[i], got))
            for kw in node.keywords:
                got = res.resolve(kw.value)
                if got is not None and kw.arg:
                    edges.append((callee, kw.arg, got))
    return edges


def analyze_package(pkg_root: str) -> list:
    """Every classified site across the device scope, with one round
    of interprocedural root propagation (call-site argument roots bound
    to callee parameters — names the naming convention alone would
    miss, e.g. a threshold field passed positionally)."""
    sources = dict(_iter_scope_sources(pkg_root))
    trees = {rel: ast.parse(src) for rel, src in sources.items()}
    # module-level function defs by bare name (collisions keep first —
    # the repo's handler names are unique)
    fn_defs: dict = {}
    for rel, tree in trees.items():
        for node in tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fn_defs.setdefault(node.name, node)
    # call-site roots bound to callee parameters: both whole-aggregate
    # kinds ("GossipSubConfig", "TP", ...) and single-field "FIELD:..."
    # entries land in the callee's env, where the resolver understands
    # either form (a FIELD: param's uses ARE reads of that field)
    inherited: dict = {}
    for rel, tree in trees.items():
        for callee, param, kind in _call_edges(tree, fn_defs,
                                               set(fn_defs)):
            inherited.setdefault(callee, {})[param] = kind
    sites: list[Site] = []
    for rel, src in sources.items():
        sites.extend(analyze_source(src, rel, inherited))
    return sorted(sites, key=lambda s: (s.field, s.rel, s.line, s.qual))


# ---------------------------------------------------------------------------
# verdicts + the committed audit artifact


AUDIT_NAME = "LIFT_AUDIT.json"


def field_verdicts(sites: list) -> dict:
    """Aggregate classified sites into per-field verdicts.

    ``SHAPE``: at least one un-excused shape/branch site (or the field
    is in :data:`DECLARED_SHAPE`). ``VALUE_GUARDED``: every
    shape/branch site is covered by the :data:`ELISION_OK` table (a
    value-neutral build-time elision the lifted engines resolve
    conservatively). ``VALUE``: traced arithmetic only."""
    by_field: dict = {}
    for s in sites:
        by_field.setdefault(s.field, []).append(s)
    out = {}
    for field, fsites in sorted(by_field.items()):
        rows = []
        hard = []
        guarded = []
        for s in fsites:
            row = s.as_row()
            if s.kind in ("shape", "branch"):
                key = (s.rel, s.qual.split(".")[0], field)
                note = ELISION_OK.get(key)
                if note is not None:
                    row["elision_ok"] = note
                    guarded.append(s)
                else:
                    hard.append(s)
            rows.append(row)
        if field in DECLARED_SHAPE:
            verdict = "SHAPE"
        elif hard:
            verdict = "SHAPE"
        elif guarded:
            verdict = "VALUE_GUARDED"
        else:
            verdict = "VALUE"
        entry = {"verdict": verdict, "sites": rows,
                 "lifted": (field in SCORE_PLANE_FIELDS
                            or field in MESH_PLANE_FIELDS)}
        if field in DECLARED_SHAPE:
            entry["declared_shape"] = DECLARED_SHAPE[field]
        out[field] = entry
    return out


def check_plane(verdicts: dict) -> list:
    """The machine check that the shipped lift is justified: every
    plane field must be read somewhere AND prove VALUE/VALUE_GUARDED;
    every DECLARED_SHAPE field must be outside the plane. Returns
    failure strings (empty = the lift is proven)."""
    failures = []
    for field in SCORE_PLANE_FIELDS + MESH_PLANE_FIELDS:
        v = verdicts.get(field)
        if v is None:
            failures.append(
                f"plane field {field} has no classified use site — the "
                "pass lost track of it (roots/aliases drifted?)")
        elif v["verdict"] not in ("VALUE", "VALUE_GUARDED"):
            bad = [r for r in v["sites"]
                   if r["kind"] in ("shape", "branch")
                   and "elision_ok" not in r]
            failures.append(
                f"plane field {field} verdicts {v['verdict']} — lifting "
                f"it is UNSOUND; offending sites: "
                + "; ".join(f"{r['file']}:{r['line']} ({r['context']})"
                            for r in bad[:3]))
    for field in DECLARED_SHAPE:
        if field in SCORE_PLANE_FIELDS + MESH_PLANE_FIELDS:
            failures.append(
                f"{field} is declared SHAPE but listed in the lifted "
                "plane — contradiction")
    return failures


def audit(pkg_root: str | None = None) -> dict:
    """The full audit payload: every tracked field's verdict + evidence
    sites, the lifted-plane manifest, and summary counts. Deterministic
    for a given source tree — the committed artifact must reproduce
    byte-identical (the MEM_AUDIT pattern)."""
    if pkg_root is None:
        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sites = analyze_package(pkg_root)
    verdicts = field_verdicts(sites)
    counts = {"VALUE": 0, "VALUE_GUARDED": 0, "SHAPE": 0}
    for v in verdicts.values():
        counts[v["verdict"]] += 1
    return {
        "schema": 1,
        "note": (
            "liftability dataflow audit (analysis/lift.py, make "
            "lift-audit): per-field SHAPE/VALUE verdicts with evidence "
            "sites; LIFT_UPDATE=1 rewrites"
        ),
        "scope": list(DEVICE_SCOPE),
        "summary": {"fields": len(verdicts), "sites": len(sites),
                    **counts},
        "lifted_plane": sorted(SCORE_PLANE_FIELDS),
        "mesh_plane": sorted(MESH_PLANE_FIELDS),
        "fields": verdicts,
    }


def dump_audit(payload: dict) -> str:
    return json.dumps(payload, indent=1, sort_keys=True) + "\n"


def audit_path(repo_root: str | None = None) -> str:
    root = repo_root or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, AUDIT_NAME)


def _iter_scope_sources(pkg_root: str):
    for dirpath, dirs, files in os.walk(pkg_root):
        dirs[:] = [d for d in dirs if d not in _SKIP_DIRS]
        for f in sorted(files):
            if not f.endswith(".py"):
                continue
            p = os.path.join(dirpath, f)
            rel = os.path.relpath(p, pkg_root).replace(os.sep, "/")
            if not rel.startswith(DEVICE_SCOPE):
                continue
            with open(p) as fh:
                yield rel, fh.read()
