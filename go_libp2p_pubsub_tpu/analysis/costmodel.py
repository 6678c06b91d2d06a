"""costmodel — static device-cost auditor (docs/DESIGN.md §19).

The fifth static pass, and the first one that prices the machine. The
other four prove STRUCTURE — simlint (source), guards (trace), lift
(dataflow), hloaudit (lowered text) — but every cost claim in the repo
(CSR's power-law win, the telemetry/oracle overhead ceilings, the v5e-8
projection) rests on wall-clock timings from a noisy CPU container.
This pass walks the CLOSED JAXPR of every engine×layout build and
statically computes per-round

    flops          per-primitive arithmetic-op accounting (dot_general
                   2·out·K, reductions charge their input, elementwise
                   their output, shape/layout ops nothing)
    hbm_bytes      sum of operand+result bytes per primitive — the
                   UNFUSED-traffic upper bound (XLA fuses aggressively,
                   so true traffic is lower; the derived arithmetic
                   intensity is therefore a LOWER bound and the
                   roofline term built from it is conservative)
    halo_bytes     the AUDITED cross-peer movement: the ops/edges tally
                   seams armed during the trace (exactly the accounting
                   `make topo-smoke` measures — the seams the sharded
                   lowering turns into collective permutes)
    rng_bits       bits drawn from the PRNG (random_bits et al.;
                   impl-independent at jaxpr level — the impl rides the
                   key dtype, not the primitive)
    gather_bytes / scatter_bytes
                   bytes moved by real gather/scatter ops (the slow
                   path the banded-roll layout exists to avoid)
    collective_bytes
                   payload of explicit collectives (ppermute /
                   all_gather / all_to_all) — zero in single-device
                   traces; the rule exists so sharded jaxprs price
                   their wire bytes through the same table

with a two-point N-slope fit (the memstat pattern: every per-round
metric is affine in N at fixed K/M/r, so two trace points determine
``cost(N) = const + slope·N`` exactly) committed to ``COST_AUDIT.json``
under the byte-identical-reproduction gate (``COST_UPDATE=1``
rewrites).

Hard contracts (each tripped by a doctored-jaxpr negative test in
tests/test_costmodel.py):

  halo-density   on a power-law topology the csr/dense halo_bytes
                 ratio EQUALS the graph density E/(N·K) — the whole
                 sparse-plane argument, now a static theorem instead of
                 a measured ratio;
  halo-measured  the model's halo_bytes equals the measured
                 ``ops/edges.tally_halo_bytes`` sum for the same build
                 (routed through ``edges.tally_step`` — the guarded
                 path that raises :class:`ops.edges.TallyCacheHit`
                 instead of silently reading zero off a cached jaxpr);
  floodsub-rng   floodsub draws ZERO rng bits (the reference defines
                 it with no randomness);
  telemetry-flops  the telemetry-on minus telemetry-off flop delta
                 stays under a static share ceiling of the off build;
  oracle-flops   the invariant checker's flops stay under a bounded
                 share of the step's flops (the "observers are cheap"
                 claim, priced statically).

Entry: ``scripts/cost_audit.py`` / ``make cost-audit`` (wired into
``make analyze``, ``make static`` and ``make quick``). The audit's
arithmetic intensity feeds ``perf/projection.py``'s v5e-8 roofline term
(disarmed by default — committed round-5 projections reproduce
byte-identically).
"""

from __future__ import annotations

import dataclasses
import json
import math
import os

#: the two trace points of the slope fit (any pair works — per-round
#: costs are affine in N at fixed K/M/r; these keep tracing fast)
N_LO, N_HI = 256, 512
#: audit array-sizing (the bench geometry: ring d=8 -> K=16, M=64)
AUDIT_M = 64
AUDIT_DEGREE_D = 2 * 8  # K of the ring builds
#: phase-engine sub-rounds / window dispatches of the audited builds
PHASE_R = 4
WINDOW_D = 4
PUB_WIDTH = 4

#: the power-law cell of the halo-density contract (a scaled-down
#: topo-smoke graph: same generator, same accounting seams)
POWERLAW_N = 512
POWERLAW_EXPONENT = 2.2
POWERLAW_D_MIN = 2
POWERLAW_MAX_DEGREE = 16
POWERLAW_SEED = 0

#: static contract ceilings — committed constants, not measurements:
#: the telemetry recorder may cost at most this share of the base
#: build's flops (measured ~1.4% at the audit shape; runtime gate is
#: telemetry-smoke's 15%), and the invariant checker at most this share
#: of one step's flops (measured ~10%; runtime gate is oracle-smoke's
#: 10% wall-clock — flops overstate the checker, whose planes fuse)
TELEMETRY_FLOP_SHARE_CEILING = 0.05
ORACLE_FLOP_SHARE_CEILING = 0.25

#: fusion contract (round 21): the fused csr build must price at most
#: this fraction of the unfused build's hbm bytes/round — at_hi AND
#: slope (the acceptance floor is a 20% cut; measured ~0.6). The phase
#: row's delivery is dense-commit (its csr traffic rides edge_gather),
#: so only the shared heartbeat fuses there: FALLING, no fixed cut.
FUSED_HBM_RATIO_CEILING = 0.8
PHASE_FUSED_HBM_RATIO_CEILING = 1.0

#: hbm-ceilings contract (round 21): every build row commits
#: ceiling = measured hbm_bytes/round at_hi × (1 + margin) into
#: COST_AUDIT.json; a later audit whose fresh at_hi exceeds the
#: COMMITTED ceiling trips the gate — a named cost regression, not
#: just a byte-identity diff
HBM_CEILING_MARGIN = 0.05

#: tolerance of the halo-density equality (the ratio is exact shape
#: arithmetic; the epsilon only absorbs float division)
HALO_DENSITY_TOL = 1e-9

AUDIT_NAME = "COST_AUDIT.json"

METRICS = ("flops", "hbm_bytes", "halo_bytes", "rng_bits",
           "gather_bytes", "scatter_bytes", "collective_bytes")

#: every engine×layout build the audit prices (the guards/hloaudit
#: registry plus the scanned window). Round 21: the csr/phase_csr rows
#: price the FUSED builds (sort-composite selection + capacity-bounded
#: segmented scan — the shipping configuration); the *_unfused rows
#: keep the legacy pairwise/log2(E) pricing live so the fusion
#: contract has a same-trace denominator.
AUDIT_BUILDS = ("gossipsub", "gossipsub_phase", "floodsub", "randomsub",
                "csr", "phase_csr", "csr_unfused", "phase_csr_unfused",
                "lifted", "window")


class CostContractViolation(Exception):
    """One failed cost contract; .build and .contract say which."""

    def __init__(self, build: str, contract: str, msg: str):
        super().__init__(f"[{build}] {contract}: {msg}")
        self.build = build
        self.contract = contract


# ---------------------------------------------------------------------------
# the jaxpr interpreter (pure accounting — unit-testable on tiny fns)


def _zero() -> dict:
    return {m: 0 for m in METRICS}


def _add(acc: dict, other: dict, scale: int = 1) -> None:
    for m in METRICS:
        acc[m] += other[m] * scale


def _aval_bytes(aval) -> int:
    """Byte size of one aval; PRNG keys normalize to 8 bytes/element
    (the memstat/STATE_SCHEMA normalization) so the audit is
    independent of the ambient jax_default_prng_impl."""
    dt = str(aval.dtype)
    if dt.startswith("key<"):
        return int(aval.size) * 8
    return int(aval.size) * aval.dtype.itemsize


def _var_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "size"):
        return 0
    return _aval_bytes(aval)


#: primitives that only relayout/alias data — zero flops (their bytes
#: still count toward the unfused-traffic bound)
_SHAPE_OPS = frozenset({
    "broadcast_in_dim", "reshape", "transpose", "slice", "squeeze",
    "concatenate", "pad", "iota", "convert_element_type",
    "bitcast_convert_type", "copy", "rev", "expand_dims",
    "dynamic_slice", "dynamic_update_slice", "stop_gradient",
    "random_seed", "random_wrap", "random_unwrap", "random_split",
    "random_fold_in", "device_put",
})

#: reductions charge their INPUT size (one op per reduced element)
_REDUCE_OPS = frozenset({
    "reduce", "reduce_sum", "reduce_max", "reduce_min", "reduce_and",
    "reduce_or", "reduce_xor", "reduce_prod", "reduce_window",
    "argmax", "argmin", "reduce_precision",
})

_CUM_OPS = frozenset({"cumsum", "cummax", "cummin", "cumprod",
                      "cumlogsumexp"})

_SCATTER_OPS = frozenset({"scatter", "scatter-add", "scatter-mul",
                          "scatter-min", "scatter-max"})

_RNG_OPS = frozenset({"random_bits", "rng_bit_generator", "threefry2x32",
                      "random_gamma"})

#: explicit collectives: payload = operand bytes (the halo permutes the
#: sharded lowering emits price through here on an sharded trace)
_COLLECTIVE_OPS = frozenset({"ppermute", "all_gather", "all_to_all",
                             "psum", "pmax", "pmin"})


def _dot_flops(eqn) -> int:
    (lhs_c, _rhs_c), _batch = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval
    k = 1
    for d in lhs_c:
        k *= int(lhs.shape[d])
    out = eqn.outvars[0].aval
    return 2 * int(out.size) * k


def _leaf_cost(eqn) -> dict:
    """Accounting for one primitive equation (no sub-jaxprs)."""
    out = _zero()
    name = eqn.primitive.name
    in_bytes = sum(_var_bytes(v) for v in eqn.invars)
    out_bytes = sum(_var_bytes(v) for v in eqn.outvars)
    out["hbm_bytes"] = in_bytes + out_bytes
    first_out = eqn.outvars[0].aval if eqn.outvars else None
    out_size = int(getattr(first_out, "size", 0) or 0)

    if name in _SHAPE_OPS:
        return out
    if name == "dot_general":
        out["flops"] = _dot_flops(eqn)
        return out
    if name in _REDUCE_OPS:
        out["flops"] = sum(
            int(v.aval.size) for v in eqn.invars
            if hasattr(getattr(v, "aval", None), "size"))
        return out
    if name in _CUM_OPS:
        out["flops"] = out_size
        return out
    if name == "sort":
        n = max(int(eqn.invars[0].aval.shape[
            eqn.params.get("dimension", -1)]), 2)
        out["flops"] = sum(int(v.aval.size) for v in eqn.invars
                           if hasattr(getattr(v, "aval", None), "size")
                           ) * max(int(math.ceil(math.log2(n))), 1)
        return out
    if name == "gather":
        out["gather_bytes"] = out_bytes
        return out
    if name in _SCATTER_OPS:
        upd = eqn.invars[2].aval if len(eqn.invars) > 2 else None
        out["scatter_bytes"] = _aval_bytes(upd) if upd is not None else 0
        out["flops"] = int(getattr(upd, "size", 0) or 0)
        return out
    if name in _RNG_OPS:
        bits = 0
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            dt = str(getattr(aval, "dtype", ""))
            if dt.startswith("key<"):
                continue
            if hasattr(aval, "size"):
                bits += int(aval.size) * aval.dtype.itemsize * 8
        out["rng_bits"] = bits
        return out
    if name in _COLLECTIVE_OPS:
        out["collective_bytes"] = in_bytes
        return out
    # default: elementwise — one op per output element
    out["flops"] = out_size
    return out


def _closed_jaxprs(v) -> list:
    """ClosedJaxpr values inside one eqn param (scalars pass through)."""
    from jax.extend.core import ClosedJaxpr

    if isinstance(v, ClosedJaxpr):
        return [v]
    if isinstance(v, (list, tuple)):
        out = []
        for item in v:
            out.extend(_closed_jaxprs(item))
        return out
    return []


def cost_jaxpr(jaxpr) -> dict:
    """Walk one ``jax.extend.core.Jaxpr`` and return the metric totals.
    Control flow: ``scan`` multiplies its body by the static trip
    count, ``while`` charges cond+body ONCE (trip count is dynamic —
    the engines carry no unbounded whiles; the window's loop is a
    scan), ``cond`` charges its most-expensive branch (by flops)."""
    total = _zero()
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pjit":
            _add(total, cost_closed(eqn.params["jaxpr"]))
            continue
        if name == "scan":
            body = cost_closed(eqn.params["jaxpr"])
            _add(total, body, scale=int(eqn.params["length"]))
            continue
        if name == "while":
            _add(total, cost_closed(eqn.params["cond_jaxpr"]))
            _add(total, cost_closed(eqn.params["body_jaxpr"]))
            continue
        if name == "cond":
            branches = [cost_closed(b) for b in eqn.params["branches"]]
            _add(total, max(branches, key=lambda c: c["flops"]))
            continue
        if name in _REDUCE_OPS:
            # `reduce`'s monoid jaxpr is per-pair — the input-size
            # charge already prices it; don't double count
            _add(total, _leaf_cost(eqn))
            continue
        subs = []
        for v in eqn.params.values():
            subs.extend(_closed_jaxprs(v))
        if subs:
            # custom_jvp/vjp/remat-style calls: the sub-jaxpr IS the
            # computation
            for sub in subs[:1]:
                _add(total, cost_closed(sub))
            continue
        _add(total, _leaf_cost(eqn))
    return total


def cost_closed(closed) -> dict:
    return cost_jaxpr(closed.jaxpr)


def cost_of(fn, state, *, with_halo: bool = True) -> dict:
    """Cost one traced call ``fn(state)`` (bind everything else in a
    closure): the jaxpr walk for the primitive metrics plus — when
    ``with_halo`` — the ops/edges byte tally armed DURING this same
    trace, so ``halo_bytes`` is the audited seam accounting, not a
    primitive heuristic. ``fn`` must be an UNJITTED body (the
    :func:`ops.edges.tally_step` cache caveat)."""
    import jax

    from ..ops import edges

    entries: list = []
    if with_halo:
        with edges.tally_halo_bytes(entries):
            jpr = jax.make_jaxpr(fn)(state)
        if not entries:
            # the same footgun tally_step guards: a jit hidden inside
            # the costed callable can satisfy the trace from a cached
            # jaxpr without re-running the seams — committing a zero
            # halo fit would bless the broken number forever
            raise edges.TallyCacheHit(
                "cost_of recorded ZERO halo seams — a cached inner "
                "jaxpr skipped the ops/edges seams (pass the raw "
                "body), or the build moved nothing cross-peer; use "
                "with_halo=False for seam-free programs")
    else:
        jpr = jax.make_jaxpr(fn)(state)
    cost = cost_closed(jpr)
    missing = [k for k, b in entries if b is None]
    if missing:
        raise CostContractViolation(
            "trace", "halo-measured",
            f"halo seams without byte accounting: {missing} — a gather "
            "seam predates the round-18 moved-tensor tally")
    cost["halo_bytes"] = sum(b for _, b in entries)
    return cost


# ---------------------------------------------------------------------------
# build harnesses (raw bodies at a parametric N — the guards registry
# shapes, re-derived so the slope fit can move N)


def _pub_args(shape, n: int):
    import jax.numpy as jnp
    import numpy as np

    po = np.full(shape, -1, np.int32)
    po.reshape(-1)[0] = 0
    pt = np.zeros(shape, np.int32)
    pv = np.ones(shape, bool)
    del n
    return jnp.asarray(po), jnp.asarray(pt), jnp.asarray(pv)


def _ring_net(n: int, edge_layout: str = "dense"):
    from .. import graph
    from ..state import Net

    return Net.build(graph.ring_lattice(n, d=8),
                     graph.subscribe_all(n, 1), edge_layout=edge_layout)


@dataclasses.dataclass
class BuildCell:
    """One costable build: an unjitted ``call(state)`` closure, its
    initial state, and how many delivery rounds one call advances
    (``halo_rounds`` differs only for the window, whose scan body — and
    therefore the one armed tally — is traced once for D dispatches)."""

    name: str
    call: object
    state: object
    rounds_per_call: int
    halo_rounds_per_call: int


def build_cell(name: str, n: int) -> BuildCell:
    from ..perf.sweep import build_bench

    if name in ("gossipsub", "csr", "csr_unfused", "lifted"):
        layout = "csr" if name.startswith("csr") else None
        st, step, _, _ = build_bench(
            n, AUDIT_M, heartbeat_every=1, rounds_per_phase=1,
            edge_layout=layout, lift_scores=(name == "lifted"),
            fused=(name == "csr"))
        raw = getattr(step, "__wrapped__", step)
        args = _pub_args((PUB_WIDTH,), n)
        if name == "lifted":
            from .guards import lifted_plane_pair

            plane, _ = lifted_plane_pair()
            return BuildCell(name, lambda s: raw(s, *args, plane), st, 1, 1)
        return BuildCell(name, lambda s: raw(s, *args), st, 1, 1)
    if name in ("gossipsub_phase", "phase_csr", "phase_csr_unfused"):
        st, step, _, _ = build_bench(
            n, AUDIT_M, heartbeat_every=PHASE_R, rounds_per_phase=PHASE_R,
            edge_layout=("csr" if name.startswith("phase_csr") else None),
            fused=(name == "phase_csr"))
        raw = getattr(step, "__wrapped__", step)
        args = _pub_args((PHASE_R, PUB_WIDTH), n)
        return BuildCell(
            name, lambda s: raw(s, *args, do_heartbeat=True), st,
            PHASE_R, PHASE_R)
    if name == "floodsub":
        from ..models.floodsub import floodsub_step
        from ..state import SimState

        net = _ring_net(n)
        raw = floodsub_step.__wrapped__
        st = SimState.init(n, AUDIT_M, k=net.max_degree)
        args = _pub_args((PUB_WIDTH,), n)
        return BuildCell(name, lambda s: raw(net, s, *args), st, 1, 1)
    if name == "randomsub":
        from ..models.randomsub import make_randomsub_step
        from ..state import SimState

        net = _ring_net(n)
        step = make_randomsub_step(net)
        raw = getattr(step, "__wrapped__", step)
        st = SimState.init(n, AUDIT_M, k=net.max_degree)
        args = _pub_args((PUB_WIDTH,), n)
        return BuildCell(name, lambda s: raw(s, *args), st, 1, 1)
    if name == "window":
        import jax.numpy as jnp
        import numpy as np

        from ..driver import make_window
        from ..models.floodsub import floodsub_step
        from ..state import SimState

        net = _ring_net(n)

        def stepped(st, po, pt, pv):
            # the RAW body, so the window's scan trace re-runs the
            # tally seams (a jitted inner call could hit a cached
            # jaxpr and tally nothing)
            return floodsub_step.__wrapped__(net, st, po, pt, pv)

        win = make_window(stepped)
        raw = getattr(win, "__wrapped__", win)
        st = SimState.init(n, AUDIT_M, k=net.max_degree)
        po = np.full((WINDOW_D, PUB_WIDTH), -1, np.int32)
        po[:, 0] = 0
        xs = (jnp.asarray(po),
              jnp.zeros((WINDOW_D, PUB_WIDTH), jnp.int32),
              jnp.ones((WINDOW_D, PUB_WIDTH), bool))
        # the scan body (and its armed tally) traces ONCE for the
        # whole window: jaxpr metrics amortize over D dispatches, the
        # tally is already per-dispatch
        return BuildCell(name, lambda s: raw(s, xs), st, WINDOW_D, 1)
    raise ValueError(f"unknown build {name!r}; expected one of "
                     f"{AUDIT_BUILDS}")


def per_round_cost(cell: BuildCell) -> dict:
    """Per-ROUND metrics of one build cell (phase/window calls amortize
    their cadence)."""
    cost = cost_of(cell.call, cell.state)
    out = {}
    for m in METRICS:
        div = (cell.halo_rounds_per_call if m == "halo_bytes"
               else cell.rounds_per_call)
        out[m] = cost[m] / div if div != 1 else cost[m]
    return out


# ---------------------------------------------------------------------------
# contracts (pure functions over costed numbers — the negative tests
# feed them doctored-jaxpr costs)


def check_floodsub_rng(build: str, cost: dict) -> None:
    """floodsub must draw ZERO rng bits — the reference defines it
    with no randomness (the same contract hloaudit pins on the lowered
    text; this one holds at jaxpr level, PRNG-impl-independent)."""
    if cost["rng_bits"] != 0:
        raise CostContractViolation(
            build, "floodsub-rng",
            f"{cost['rng_bits']} rng bits in a program the reference "
            "defines with no randomness — a sampler leaked in")


def check_halo_density(dense_halo: float, csr_halo: float,
                       density: float, *,
                       tol: float = HALO_DENSITY_TOL) -> float:
    """csr/dense halo-bytes ratio must EQUAL the topology density
    E/(N·K) — flat [E] planes cross the seams where dense moves the
    full [N,K] capacity; any deviation means a seam moves bytes that
    do not scale with the edge count."""
    if dense_halo <= 0:
        raise CostContractViolation(
            "powerlaw_dense", "halo-density",
            "dense build moved zero halo bytes — the tally seams are "
            "not firing")
    ratio = csr_halo / dense_halo
    if abs(ratio - density) > tol:
        raise CostContractViolation(
            "powerlaw_csr", "halo-density",
            f"csr/dense halo-bytes ratio {ratio:.9f} != topology "
            f"density {density:.9f} — the sparse layout's wire bytes "
            "stopped tracking the edge count")
    return ratio


def check_halo_measured(build: str, model_halo: float,
                        measured_halo: float) -> None:
    """The cost model's halo_bytes must equal the MEASURED
    ``tally_halo_bytes`` sum for the same build (the topo-smoke
    accounting, routed through ``edges.tally_step`` — the guarded
    path)."""
    if model_halo != measured_halo:
        raise CostContractViolation(
            build, "halo-measured",
            f"model halo_bytes {model_halo} != measured tally "
            f"{measured_halo} — the cost trace and the audited seams "
            "disagree (cached jaxpr, or a seam outside the trace)")


def check_telemetry_flops(off_flops: float, on_flops: float, *,
                          ceiling: float = TELEMETRY_FLOP_SHARE_CEILING
                          ) -> float:
    """The telemetry recorder's flop delta must stay under the static
    share ceiling of the base build."""
    if off_flops <= 0:
        raise CostContractViolation(
            "telemetry", "telemetry-flops",
            "telemetry-off build costs zero flops — broken cell")
    share = (on_flops - off_flops) / off_flops
    if share > ceiling:
        raise CostContractViolation(
            "telemetry", "telemetry-flops",
            f"telemetry-on flop delta is {share:.4f} of the off build "
            f"(> static ceiling {ceiling}) — the recorder stopped "
            "being a cheap observer")
    return share


def check_oracle_flops(step_flops: float, checker_flops: float, *,
                       ceiling: float = ORACLE_FLOP_SHARE_CEILING
                       ) -> float:
    """The folded invariant checker's flops must stay under a bounded
    share of one step's flops — observers never dominate the work."""
    if step_flops <= 0:
        raise CostContractViolation(
            "oracle", "oracle-flops",
            "step build costs zero flops — broken cell")
    share = checker_flops / step_flops
    if share > ceiling:
        raise CostContractViolation(
            "oracle", "oracle-flops",
            f"invariant checker costs {share:.4f} of a step's flops "
            f"(> static ceiling {ceiling}) — the oracle plane stopped "
            "being a cheap observer")
    return share


def check_fused_hbm(build: str, fused: dict, unfused: dict, *,
                    ceiling: float = FUSED_HBM_RATIO_CEILING) -> dict:
    """The fused build's hbm_bytes/round must price at most ``ceiling``
    × the unfused build's — on the at_hi point AND the N-slope (both
    fit rows are ``per_round['hbm_bytes']``). The fused path exists to
    move fewer bytes; a composite that stops cutting traffic is a
    regression even while staying bit-exact."""
    out = {}
    for field in ("at_hi", "slope"):
        f, u = fused["hbm_bytes"][field], unfused["hbm_bytes"][field]
        if u <= 0:
            raise CostContractViolation(
                build, "fused-hbm",
                f"unfused hbm_bytes {field} is {u} — broken cell")
        ratio = f / u
        if ratio > ceiling or ratio >= 1.0:
            raise CostContractViolation(
                build, "fused-hbm",
                f"fused/unfused hbm_bytes {field} ratio {ratio:.4f} "
                f"(ceiling {ceiling}) — the fused build stopped "
                "cutting traffic")
        out[field] = ratio
    return out


def hbm_ceilings(builds: dict, *,
                 margin: float = HBM_CEILING_MARGIN) -> dict:
    """Per-build hbm_bytes/round ceilings from this audit's measured
    at_hi points — the numbers COMMITTED into COST_AUDIT.json that
    ``check_hbm_ceilings`` gates later runs against."""
    return {name: row["per_round"]["hbm_bytes"]["at_hi"] * (1 + margin)
            for name, row in builds.items()}


def check_hbm_ceilings(committed: dict, builds: dict) -> None:
    """Every fresh build row's hbm_bytes/round at_hi must stay under
    the COMMITTED ceiling — the cost-regression gate of ``make
    cost-audit`` (byte-identity says "something moved"; this says
    "the byte budget REGRESSED, in this build, past the margin")."""
    for name, row in builds.items():
        if name not in committed:
            continue  # a new build has no committed budget yet
        fresh = row["per_round"]["hbm_bytes"]["at_hi"]
        if fresh > committed[name]:
            raise CostContractViolation(
                name, "hbm-ceiling",
                f"hbm_bytes/round at N_HI is {fresh:.6g}, over the "
                f"committed ceiling {committed[name]:.6g} — the device "
                "program grew its byte budget (review, then "
                "COST_UPDATE=1 to re-commit)")


# ---------------------------------------------------------------------------
# contract cells (extra builds the headline registry doesn't carry)


def _powerlaw_pair():
    """(dense_cost, csr_cost, density, measured) of the scaled-down
    topo-smoke cell: floodsub on one power-law edge list, both
    layouts. ``measured`` maps layout -> the tally_halo_bytes sum via
    the guarded ``edges.tally_step`` path."""
    from .. import graph, topo
    from ..models.floodsub import floodsub_step
    from ..ops import edges
    from ..state import SimState

    el = topo.powerlaw(POWERLAW_N, exponent=POWERLAW_EXPONENT,
                       d_min=POWERLAW_D_MIN,
                       max_degree=POWERLAW_MAX_DEGREE, seed=POWERLAW_SEED)
    subs = graph.subscribe_all(POWERLAW_N, 1)
    _t, net_d, net_c = topo.build_nets(el, subs,
                                       max_degree=POWERLAW_MAX_DEGREE)
    density = net_c.n_edges / float(POWERLAW_N * net_d.max_degree)
    args = _pub_args((PUB_WIDTH,), POWERLAW_N)
    raw = floodsub_step.__wrapped__
    out = {}
    measured = {}
    for layout, net in (("dense", net_d), ("csr", net_c)):
        st = SimState.init(POWERLAW_N, AUDIT_M, k=net.max_degree,
                           n_edges=net.n_edges)
        out[layout] = cost_of(lambda s: raw(net, s, *args), st)
        # the measured cross-check goes through the GUARDED tally path
        # (tally_step raises TallyCacheHit instead of reading zero)
        tally = edges.tally_step(
            floodsub_step,
            SimState.init(POWERLAW_N, AUDIT_M, k=net.max_degree,
                          n_edges=net.n_edges),
            args, {}, net=net, count_bytes=True)
        measured[layout] = sum(b for _, b in tally if b is not None)
    return out["dense"], out["csr"], density, measured


def _telemetry_pair():
    """(off_flops, on_flops) of the bench gossipsub step with the
    per-round telemetry recorder off/on at the audit shape."""
    from ..perf.sweep import build_bench
    from ..telemetry import TelemetryConfig

    flops = []
    for tcfg in (None, TelemetryConfig(rows=8, tracked=(0, 7))):
        st, step, _, _ = build_bench(
            N_LO, AUDIT_M, heartbeat_every=1, rounds_per_phase=1,
            telemetry=tcfg, count_events=True)
        raw = getattr(step, "__wrapped__", step)
        args = _pub_args((PUB_WIDTH,), N_LO)
        flops.append(cost_of(lambda s: raw(s, *args),
                             st, with_halo=False)["flops"])
    return flops[0], flops[1]


def _oracle_pair():
    """(step_flops, checker_flops) of the guard-shape gossipsub build
    and its full invariant checker."""
    import dataclasses as _dc

    import jax.numpy as jnp

    from ..config import GossipSubParams, PeerScoreThresholds
    from ..models.gossipsub import (
        GossipSubConfig,
        GossipSubState,
        make_gossipsub_step,
    )
    from ..oracle import invariants
    from ..perf.sweep import bench_score_params

    net = _ring_net(N_LO)
    _tp, sp = bench_score_params("default", 1)
    cfg = GossipSubConfig.build(
        _dc.replace(GossipSubParams(), flood_publish=False),
        PeerScoreThresholds(), score_enabled=True)
    cfg = _dc.replace(cfg, count_events=False, fanout_slots=0)
    st = GossipSubState.init(net, AUDIT_M, cfg, score_params=sp)
    step = make_gossipsub_step(cfg, net, score_params=sp)
    raw = getattr(step, "__wrapped__", step)
    args = _pub_args((PUB_WIDTH,), N_LO)
    step_flops = cost_of(lambda s: raw(s, *args), st,
                         with_halo=False)["flops"]

    checker, _names = invariants.make_checker("gossipsub", net, cfg)
    craw = getattr(checker, "__wrapped__", checker)
    prev = jnp.zeros_like(getattr(st, "core", st).events)
    due = jnp.asarray(invariants.due_vector(), jnp.int32)
    checker_flops = cost_of(lambda s: craw(s, prev, due), st,
                            with_halo=False)["flops"]
    return step_flops, checker_flops


# ---------------------------------------------------------------------------
# the audit artifact


def _fit_rows(lo: dict, hi: dict) -> dict:
    rows = {}
    for m in METRICS:
        a, b = lo[m], hi[m]
        slope = (b - a) / float(N_HI - N_LO)
        const = a - slope * N_LO
        rows[m] = {"at_lo": a, "at_hi": b,
                   "slope": slope, "const": const}
    return rows


def eval_fit(rows: dict, metric: str, n: int) -> float:
    """``const + slope·N`` of one committed fit row — the projection's
    read path (perf.projection roofline term)."""
    r = rows[metric]
    return float(r["const"]) + float(r["slope"]) * float(n)


def build_audit() -> dict:
    """The full audit: per-build slope fits + the contract block.
    Deterministic trace arithmetic — committed COST_AUDIT.json must
    reproduce byte-identical (the MEM_AUDIT pattern)."""
    builds = {}
    for name in AUDIT_BUILDS:
        lo = per_round_cost(build_cell(name, N_LO))
        hi = per_round_cost(build_cell(name, N_HI))
        rows = _fit_rows(lo, hi)
        builds[name] = {
            "per_round": rows,
            "arithmetic_intensity_at_hi": (
                hi["flops"] / hi["hbm_bytes"] if hi["hbm_bytes"] else 0.0),
        }

    contracts: dict = {}

    check_floodsub_rng(
        "floodsub", {m: builds["floodsub"]["per_round"][m]["at_hi"]
                     for m in METRICS})
    contracts["floodsub_rng"] = {
        "rng_bits": builds["floodsub"]["per_round"]["rng_bits"]["at_hi"],
        "pass": True,
    }

    dense, csr, density, measured = _powerlaw_pair()
    for layout, cost in (("dense", dense), ("csr", csr)):
        check_halo_measured(f"powerlaw_{layout}", cost["halo_bytes"],
                            measured[layout])
    ratio = check_halo_density(dense["halo_bytes"], csr["halo_bytes"],
                               density)
    contracts["halo_density"] = {
        "n_peers": POWERLAW_N,
        "density": density,
        "dense_halo_bytes": dense["halo_bytes"],
        "csr_halo_bytes": csr["halo_bytes"],
        "ratio": ratio,
        "measured_tally_bytes": measured,
        "pass": True,
    }

    off_flops, on_flops = _telemetry_pair()
    tshare = check_telemetry_flops(off_flops, on_flops)
    contracts["telemetry_flops"] = {
        "off_flops": off_flops, "on_flops": on_flops,
        "share": tshare, "ceiling": TELEMETRY_FLOP_SHARE_CEILING,
        "pass": True,
    }

    step_flops, checker_flops = _oracle_pair()
    oshare = check_oracle_flops(step_flops, checker_flops)
    contracts["oracle_flops"] = {
        "step_flops": step_flops, "checker_flops": checker_flops,
        "share": oshare, "ceiling": ORACLE_FLOP_SHARE_CEILING,
        "pass": True,
    }

    fusion = {}
    for fused_name, ceil in (("csr", FUSED_HBM_RATIO_CEILING),
                             ("phase_csr", PHASE_FUSED_HBM_RATIO_CEILING)):
        f_rows = builds[fused_name]["per_round"]
        u_rows = builds[f"{fused_name}_unfused"]["per_round"]
        ratios = check_fused_hbm(fused_name, f_rows, u_rows, ceiling=ceil)
        fusion[fused_name] = {
            "fused_hbm_at_hi": f_rows["hbm_bytes"]["at_hi"],
            "unfused_hbm_at_hi": u_rows["hbm_bytes"]["at_hi"],
            "ratio_at_hi": ratios["at_hi"],
            "ratio_slope": ratios["slope"],
            "ceiling": ceil,
        }
    contracts["fusion"] = {**fusion, "pass": True}

    contracts["hbm_ceilings"] = {
        "margin": HBM_CEILING_MARGIN,
        "ceilings": hbm_ceilings(builds),
        "pass": True,
    }

    return {
        "schema": 1,
        "note": ("static device-cost audit (analysis/costmodel.py; "
                 "COST_UPDATE=1 rewrites). Per-round metric fits are "
                 "const + slope*N from two trace points; hbm_bytes is "
                 "the unfused-traffic upper bound, halo_bytes the "
                 "audited ops/edges seam accounting."),
        "shape": {"n_lo": N_LO, "n_hi": N_HI, "msg_slots": AUDIT_M,
                  "k": AUDIT_DEGREE_D, "rounds_per_phase": PHASE_R,
                  "window_dispatches": WINDOW_D,
                  "pub_width": PUB_WIDTH},
        "builds": builds,
        "contracts": contracts,
    }


# ---------------------------------------------------------------------------
# byte-identity gate helpers (shared with the MEM/LIFT audit gates —
# the round-19 satellite: a failed reproduction must NAME the diverging
# key, not just say "mismatch")


def baseline_divergences(committed, fresh, prefix: str = "",
                         limit: int = 8) -> list:
    """JSON-path strings of every point where two parsed artifacts
    diverge (first ``limit``): ``builds.floodsub.per_round.flops.slope:
    <committed> != <fresh>``. Shared by the cost/mem/lift
    byte-identity gates so a stale artifact names its drift."""
    out: list = []
    _diverge(committed, fresh, prefix, out, limit)
    return out


def _diverge(a, b, path, out, limit) -> None:
    if len(out) >= limit:
        return
    if isinstance(a, dict) and isinstance(b, dict):
        for k in sorted(set(a) | set(b), key=str):
            p = f"{path}.{k}" if path else str(k)
            if k not in a:
                out.append(f"{p}: missing from committed artifact")
            elif k not in b:
                out.append(f"{p}: missing from this run")
            else:
                _diverge(a[k], b[k], p, out, limit)
            if len(out) >= limit:
                return
        return
    if isinstance(a, list) and isinstance(b, list):
        if len(a) != len(b):
            out.append(f"{path}: length {len(a)} != {len(b)}")
            return
        for i, (x, y) in enumerate(zip(a, b)):
            _diverge(x, y, f"{path}[{i}]", out, limit)
            if len(out) >= limit:
                return
        return
    if a != b:
        out.append(f"{path}: {a!r} != {b!r}")


def audit_path(repo_root: str | None = None) -> str:
    root = repo_root or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, AUDIT_NAME)


def dump_audit(payload: dict) -> str:
    return json.dumps(payload, indent=1, sort_keys=True) + "\n"
