"""ranges — static range/overflow auditor (docs/DESIGN.md §23).

The sixth static pass, and the first one that proves VALUES. simlint
reads source, guards watch traces, lift checks dataflow, hloaudit greps
lowered text, costmodel prices bytes — none of them can say "this int16
add cannot wrap" or "this gather index stays inside its operand". Those
claims exist in the repo as prose: PR 11's ``narrow_counters`` int16
packing is justified by a range argument in a comment, the flat-[E] CSR
index arithmetic is assumed to fit i32 at the MEM_AUDIT 10M-peer
headroom scale, and the i32 EV counters of an always-on ``serve/`` cell
have no stated overflow horizon. This pass turns each of them into a
committed, regression-gated verdict.

It is an abstract interpreter over the same CLOSED JAXPRS the cost
auditor walks (the costmodel build cells, plus the guards registry's
dynamic overlay build, plus a ``narrow_counters=True`` cell and an
event-counting cell):

  interval domain    every variable carries elementwise ``[lo, hi]``
                     float64 bounds in the aval's shape. Trace-time
                     constants (Net tables, publish batches, score
                     planes — closure consts) seed EXACT from their
                     concrete values, so topology-derived index chains
                     get real bounds, not dtype tops.
  known bits         packed-word bitwise ops keep finite bounds through
                     the uint32 planes: ``and`` meets, ``or``/``xor``
                     round up to the next all-ones mask,
                     ``population_count`` is bounded by the lane width,
                     shifts are monotone on the non-negative cone.
  fact seeding       state leaves default to dtype-top; a declared
                     FACTS table (each entry carries its invariant
                     justification — the PR-7/PR-12 oracle checks most
                     of them at runtime) narrows the few leaves whose
                     bounds are protocol invariants rather than dtype
                     facts (heartbeat-cleared IHAVE counters, the
                     mod-M cursor, publish origins in [-1, N-1]).
  control flow       scan runs its body to a widening fixpoint (grown
                     carries widen to dtype-top, then one sound rerun);
                     while widens carries immediately (no unbounded
                     whiles in the engines); cond unions its branches;
                     pjit/custom_* recurse.

Hard contracts (each tripped by a doctored-jaxpr negative test in
tests/test_ranges.py that names the exact eqn/leaf):

  narrow-nonwrap   every eqn producing a sub-i32 integer dtype must be
                   proven non-wrapping — the PR-11 prose proof for the
                   int16 ``peerhave``/``iasked`` counters, machine
                   checked; ``GossipSubConfig.build``'s 2^15 refusals
                   are now derived from ``np.iinfo(np.int16)``.
  index-bounds     every gather/scatter index interval must be proven
                   inside its operand, or the site must be NAMED in the
                   sanctioned-drop catalog (mode fill_or_drop/clip plus
                   a declared reason: the dense junk-convention
                   self-pointing reads, ``apply_mutation``'s drop
                   scatters). An unproven ``promise_in_bounds`` site is
                   always a violation.
  index-width      the flat ``[E]``/``[E,W]``/``e2nk`` index formulas,
                   re-evaluated SYMBOLICALLY (exact ints, no tracing)
                   at the MEM_AUDIT headroom points 100k/1M/10M under
                   the audit geometry AND a growth-envelope geometry —
                   every site gets an explicit PROVEN_I32 / NEEDS_I64
                   verdict (no silent pass); an audit-geometry
                   NEEDS_I64 fails the gate until acknowledged, and the
                   verdicts feed MEM_AUDIT's ``index_width`` column.
  overflow-horizon the per-EV-counter per-round deltas (events seeded
                   [0, 0], the output's hi IS the round bound) give
                   each i32 counter an overflow horizon in rounds —
                   surfaced as a serve/ supervisor startup note — and
                   each f32 telemetry column a 2^24 exact-count
                   horizon; any horizon under the floor fails.
  narrow-manifest  the source-level ``.astype(<sub-i32>)`` sites in the
                   device scope must equal the declared manifest
                   (positionally, per file) — the cross-check simlint's
                   ``narrow-dtype`` rule replays against the committed
                   artifact on every lint.

Entry: ``scripts/range_audit.py`` / ``make range-audit`` (wired into
``make analyze``, ``make static`` and ``make quick``); committed
``RANGE_AUDIT.json`` under the byte-identity gate, ``RANGE_UPDATE=1``
rewrites. Pure tracing + numpy interval arithmetic — no compile, no
execution, PRNG-impl-independent.
"""

from __future__ import annotations

import dataclasses
import os

from .costmodel import (  # noqa: F401  (re-exported audit plumbing)
    AUDIT_M,
    N_LO,
    PHASE_R,
    PUB_WIDTH,
    WINDOW_D,
    audit_path as _cost_audit_path,
    baseline_divergences,
    dump_audit,
)

#: single trace point — range verdicts are not slope fits; one N is
#: enough (bounds that hold at the audit shape are what the contracts
#: pin; the index-width leg re-evaluates the SCALING claims exactly)
RANGE_N = N_LO

AUDIT_NAME = "RANGE_AUDIT.json"

#: every build the range interpreter walks: the costmodel registry rows
#: (one N point each) plus the dynamic-overlay build, the
#: narrow_counters int16 cell and the event-counting cell
RANGE_BUILDS = ("gossipsub", "gossipsub_phase", "floodsub", "randomsub",
                "csr", "phase_csr", "lifted", "window", "dynamic",
                "narrow", "events")

#: contract floor: every i32 EV counter must survive at least this many
#: rounds at the audit shape before wrapping (a standing serve/ cell
#: heartbeats every few hundred rounds; a counter that wraps inside
#: ~2k rounds would corrupt drain accounting within one session)
HORIZON_FLOOR_ROUNDS = 1000

#: f32 telemetry columns count exactly until 2^24 (float32 integer
#: exactness bound) — the horizon divisor of the telemetry leg
F32_EXACT_LIMIT = 2 ** 24

#: index-width scale targets — the MEM_AUDIT headroom points
SCALE_TARGETS = (100_000, 1_000_000, 10_000_000)

#: index-width geometries: ``audit`` is the bench/MEM_AUDIT geometry
#: (ring d=8 -> K=16, M=64) — the one MEM_AUDIT's projections assume;
#: ``envelope`` is the documented growth margin (K=64 high-degree
#: overlays, M=1024 deep message windows) — the qualifier row: indices
#: that refute HERE bound how far the i32 plane stretches
SCALE_GEOMETRIES = {
    "audit": {"k": 16, "m": 64},
    "envelope": {"k": 64, "m": 1024},
}

#: audit-geometry sites allowed to read NEEDS_I64 (none today; adding
#: one here must come with the MEM_AUDIT qualifier — see check_index_width)
I64_ACKNOWLEDGED: tuple = ()


def _w_of(m: int) -> int:
    return (m + 31) // 32


#: the flat-index site table (contract index-width): max index value as
#: an EXACT python-int formula over (n, k, m, w, e) with e = n*k (the
#: density-1 capacity bound — real E is smaller, so the bound is
#: conservative). Mirrors ops/csr.py / the dense planes.
INDEX_SITES = (
    ("e2nk", "flat dense-slot address n*K + k "
     "(ops/csr.py CsrTopology.e2nk, pack_edges/unpack_edges)",
     lambda n, k, m, w, e: n * k - 1),
    ("row_ptr", "CSR row pointer: row_ptr[N] == E (ops/csr.py build_csr)",
     lambda n, k, m, w, e: e),
    ("eperm", "flat edge-involution target (ops/csr.py edge_permute_flat)",
     lambda n, k, m, w, e: e - 1),
    ("col", "flat neighbor peer id (ops/csr.py peer_gather_flat)",
     lambda n, k, m, w, e: n - 1),
    ("flat_ew", "[E, W] packed word-plane linearization e*W + w",
     lambda n, k, m, w, e: e * w - 1),
    ("dense_nkw", "[N, K, W] dense wire-plane linearization",
     lambda n, k, m, w, e: n * k * w - 1),
    ("first_round_nm", "[N, M] first-arrival plane linearization n*M + m",
     lambda n, k, m, w, e: n * m - 1),
)

#: sanctioned drop/clip catalog (contract index-bounds): builds whose
#: gather/scatter indices the interpreter cannot prove in-bounds may
#: pass ONLY when the site's mode drops/clips out-of-range lanes AND
#: the (build, primitive) pair is named here with its reason. Silent
#: passes are what this table exists to forbid.
_DENSE_JUNK = (
    "dense junk-convention reads: absent [N, K] slots self-point "
    "(ops/edges.build_edge_perm) and state-derived slot/peer indices "
    "(first_edge, mesh candidates, mcache slots) are dtype-seeded, so "
    "the interval spans the sentinel -1 / the full axis; every consumer "
    "masks on validity and the lowering's fill/clip mode drops the "
    "out-of-range lanes")
_CSR_JUNK = (
    "flat-[E] plane reads through clip-guarded indices "
    "(ops/csr.py unpack_edges/segment_or_words jnp.clip on e_of_nk/"
    "row_last; -1 marks absent) plus state-derived message-slot "
    "gathers — masked by e_valid/row_nonempty downstream")
_SCATTER_DROP = (
    "scatter updates addressed by state-derived slots (message cache "
    "ring, IWANT bookkeeping, per-peer planes) — the engine masks "
    "invalid rows and the scatter mode drops out-of-range lanes "
    "instead of trapping")
_MUTATION_DROP = (
    "apply_mutation's drop scatters (topo/dynamics.py): write batches "
    "padded with -1 rows are DROPPED by mode=drop scatter semantics — "
    "the documented no-op convention of the mutation word stream")

SANCTIONED_DROPS = {
    "gossipsub": {"gather": _DENSE_JUNK, "scatter": _SCATTER_DROP,
                  "scatter-add": _SCATTER_DROP},
    "gossipsub_phase": {"gather": _DENSE_JUNK, "scatter": _SCATTER_DROP,
                        "scatter-add": _SCATTER_DROP},
    "floodsub": {"gather": _DENSE_JUNK, "scatter": _SCATTER_DROP,
                 "scatter-add": _SCATTER_DROP},
    "randomsub": {"gather": _DENSE_JUNK, "scatter": _SCATTER_DROP,
                  "scatter-add": _SCATTER_DROP},
    "csr": {"gather": _CSR_JUNK, "scatter": _SCATTER_DROP,
            "scatter-add": _SCATTER_DROP},
    "phase_csr": {"gather": _CSR_JUNK, "scatter": _SCATTER_DROP,
                  "scatter-add": _SCATTER_DROP},
    "lifted": {"gather": _DENSE_JUNK, "scatter": _SCATTER_DROP,
               "scatter-add": _SCATTER_DROP},
    "window": {"gather": _DENSE_JUNK, "scatter": _SCATTER_DROP,
               "scatter-add": _SCATTER_DROP},
    "dynamic": {"gather": _DENSE_JUNK,
                "scatter": _MUTATION_DROP + "; plus " + _SCATTER_DROP,
                "scatter-add": _SCATTER_DROP},
    "narrow": {"gather": _DENSE_JUNK, "scatter": _SCATTER_DROP,
               "scatter-add": _SCATTER_DROP},
    "events": {"gather": _DENSE_JUNK, "scatter": _SCATTER_DROP,
               "scatter-add": _SCATTER_DROP},
}

#: source-level sub-i32 ``.astype`` manifest (contract narrow-manifest;
#: the simlint ``narrow-dtype`` rule replays this cross-check against
#: the committed artifact): per device-scope file, the ORDERED narrow
#: target dtypes of its ``.astype`` callsites, each justified here.
NARROW_ASTYPE_MANIFEST: dict = {}


class RangeContractViolation(Exception):
    """One failed range contract; .build and .contract say which."""

    def __init__(self, build: str, contract: str, msg: str):
        super().__init__(f"[{build}] {contract}: {msg}")
        self.build = build
        self.contract = contract


# ---------------------------------------------------------------------------
# the interval domain (pure numpy — unit-testable on tiny jaxprs)

_INF = float("inf")


def _dtype_top(dtype):
    """Scalar (lo, hi) covering every value of one dtype."""
    import numpy as np

    dt = np.dtype(dtype) if not str(dtype).startswith("key<") else None
    if dt is None:
        return (-_INF, _INF)
    if dt.kind == "b":
        return (0.0, 1.0)
    if dt.kind in "iu":
        info = np.iinfo(dt)
        return (float(info.min), float(info.max))
    return (-_INF, _INF)


def _full(shape, lo, hi):
    import numpy as np

    return (np.broadcast_to(np.float64(lo), shape),
            np.broadcast_to(np.float64(hi), shape))


def _top(aval):
    lo, hi = _dtype_top(aval.dtype)
    return _full(aval.shape, lo, hi)


def _collapse(iv):
    """Global scalar (lo, hi) of one interval pair."""
    lo, hi = iv
    return (float(lo.min()) if lo.size else 0.0,
            float(hi.max()) if hi.size else 0.0)


def _const_ival(c, aval):
    """Exact interval of one trace constant (key dtypes -> top)."""
    import numpy as np

    if str(aval.dtype).startswith("key<"):
        return _top(aval)
    a = np.asarray(c, np.float64)
    return (a, a.copy())


def _nan_guard(lo, hi):
    """0*inf etc. produce NaN — widen those lanes instead of poisoning."""
    import numpy as np

    return (np.where(np.isnan(lo), -_INF, lo),
            np.where(np.isnan(hi), _INF, hi))


def _union(a, b):
    import numpy as np

    return (np.minimum(a[0], b[0]), np.maximum(a[1], b[1]))


def _next_mask(x):
    """Elementwise smallest all-ones mask >= x (known-bits or/xor bound)."""
    import numpy as np

    x = np.maximum(x, 0.0)
    with np.errstate(divide="ignore"):
        bits = np.ceil(np.log2(x + 1.0))
    return np.exp2(np.minimum(bits, 64.0)) - 1.0


#: arithmetic primitives where an integer result can leave its dtype —
#: the narrow-nonwrap recording set (selection/shape ops are
#: value-closed and cannot wrap)
_WRAP_PRIMS = frozenset({
    "add", "sub", "mul", "neg", "dot_general", "reduce_sum", "cumsum",
    "shift_left", "integer_pow", "pow", "scatter-add",
    "convert_element_type", "div", "rem",
})


@dataclasses.dataclass
class NarrowSite:
    path: str
    primitive: str
    dtype: str
    lo: float
    hi: float
    fits: bool


@dataclasses.dataclass
class IndexSite:
    path: str
    primitive: str
    mode: str
    index_lo: float
    index_hi: float
    bound: float
    proven: bool


class Recorder:
    """Per-build site records (None disables recording — the scan
    widening pre-pass walks without double-counting)."""

    def __init__(self):
        self.narrow: list[NarrowSite] = []
        self.index: list[IndexSite] = []

    def narrow_site(self, path, prim, dtype, lo, hi, fits):
        self.narrow.append(NarrowSite(path, prim, str(dtype),
                                      float(lo), float(hi), bool(fits)))

    def index_site(self, path, prim, mode, ilo, ihi, bound, proven):
        self.index.append(IndexSite(path, prim, str(mode), float(ilo),
                                    float(ihi), float(bound), bool(proven)))


def _int_out(eqn, iv, rec, path):
    """Dtype-fit pass over one eqn's first output: record sub-i32
    integer sites (contract narrow-nonwrap), widen wrapped results to
    dtype-top (unsigned wrap is legal; signed i32/i64 overflow widens
    silently — no engine does round-level i32 arithmetic near 2^31
    except the counters the horizon leg bounds)."""
    import numpy as np

    aval = eqn.outvars[0].aval
    dt = np.dtype(aval.dtype) if not str(aval.dtype).startswith("key<") \
        else None
    if dt is None or dt.kind not in "iu":
        return _nan_guard(*iv)
    lo, hi = _nan_guard(*iv)
    glo, ghi = float(lo.min()), float(hi.max())
    dlo, dhi = _dtype_top(dt)
    fits = glo >= dlo and ghi <= dhi
    name = eqn.primitive.name
    if dt.itemsize < 4 and rec is not None and name in _WRAP_PRIMS:
        rec.narrow_site(path, name, dt, glo, ghi, fits)
    if not fits:
        return _full(aval.shape, dlo, dhi)
    return (lo, hi)


def _mode_name(mode) -> str:
    s = str(mode)
    return s.rsplit(".", 1)[-1].lower() if s else "none"


def _gather_bounds(eqn):
    """Per-mapped-dim max legal start index of one gather eqn."""
    dn = eqn.params["dimension_numbers"]
    slice_sizes = eqn.params["slice_sizes"]
    opshape = eqn.invars[0].aval.shape
    return [opshape[d] - slice_sizes[d] for d in dn.start_index_map]


def _transfer_gather(eqn, ivals, rec, path):
    import numpy as np

    op, idx = ivals[0], ivals[1]
    bounds = _gather_bounds(eqn)
    mode = _mode_name(eqn.params.get("mode"))
    ilo, ihi = _collapse(idx)
    proven = bool(bounds) and ilo >= 0 and ihi <= min(bounds)
    if not proven and bounds and len(bounds) > 1:
        # per-column check: the index vector's last axis maps columns to
        # operand dims in start_index_map order
        lo_a, hi_a = idx
        if lo_a.ndim >= 1 and lo_a.shape[-1] == len(bounds):
            proven = all(
                float(lo_a[..., i].min()) >= 0
                and float(hi_a[..., i].max()) <= b
                for i, b in enumerate(bounds))
    if rec is not None:
        rec.index_site(path, "gather", mode, ilo, ihi,
                       float(min(bounds)) if bounds else 0.0, proven)
    aval = eqn.outvars[0].aval
    if proven:
        glo, ghi = _collapse(op)
        return _full(aval.shape, glo, ghi)
    return _top(aval)


def _transfer_scatter(eqn, ivals, rec, path):
    import numpy as np

    name = eqn.primitive.name
    op, idx = ivals[0], ivals[1]
    upd = ivals[2] if len(ivals) > 2 else None
    dn = eqn.params["dimension_numbers"]
    opshape = eqn.invars[0].aval.shape
    dims = getattr(dn, "scatter_dims_to_operand_dims", ())
    bounds = [opshape[d] - 1 for d in dims]
    mode = _mode_name(eqn.params.get("mode"))
    ilo, ihi = _collapse(idx)
    proven = bool(bounds) and ilo >= 0 and ihi <= min(bounds)
    if rec is not None:
        rec.index_site(path, name, mode, ilo, ihi,
                       float(min(bounds)) if bounds else 0.0, proven)
    aval = eqn.outvars[0].aval
    # exact path: 1-D operand, single statically-pinned index, scalar
    # update — the ``counters.at[EV.X].add(n)`` shape. Updating only
    # the addressed slot is what gives the overflow-horizon leg
    # per-EV resolution instead of one uniform bound.
    if (proven and upd is not None and len(op[0].shape) == 1
            and idx[0].size == 1 and ilo == ihi
            and eqn.invars[2].aval.size == 1):
        j = int(ilo)
        lo, hi = op[0].copy(), op[1].copy()
        ulo, uhi = _collapse(upd)
        if name == "scatter-add":
            lo[j], hi[j] = lo[j] + ulo, hi[j] + uhi
        elif name == "scatter":
            lo[j], hi[j] = ulo, uhi
        else:
            lo[j], hi[j] = min(lo[j], ulo), max(hi[j], uhi)
        return (lo, hi)
    olo, ohi = _collapse(op)
    if upd is None:
        return _full(aval.shape, olo, ohi)
    ulo, uhi = _collapse(upd)
    if name == "scatter-add":
        n_upd = int(eqn.invars[2].aval.size) or 1
        return _full(aval.shape, olo + min(0.0, ulo * n_upd),
                     ohi + max(0.0, uhi * n_upd))
    if name == "scatter-mul":
        return _top(aval)
    # replace/min/max: value-closed over operand ∪ updates
    return _full(aval.shape, min(olo, ulo), max(ohi, uhi))


def _reduce_axes(eqn):
    ax = eqn.params.get("axes", ())
    return tuple(int(a) for a in ax)


def _monotone(fn, iv):
    import numpy as np

    with np.errstate(all="ignore"):
        a, b = fn(iv[0]), fn(iv[1])
    return _nan_guard(np.minimum(a, b), np.maximum(a, b))


def _mul_iv(a, b):
    import numpy as np

    with np.errstate(all="ignore"):
        cands = [a[0] * b[0], a[0] * b[1], a[1] * b[0], a[1] * b[1]]
    lo = np.minimum(np.minimum(cands[0], cands[1]),
                    np.minimum(cands[2], cands[3]))
    hi = np.maximum(np.maximum(cands[0], cands[1]),
                    np.maximum(cands[2], cands[3]))
    return _nan_guard(lo, hi)


def _div_iv(a, b):
    import numpy as np

    blo, bhi = b
    if float(blo.min()) <= 0.0 <= float(bhi.max()):
        return None  # divisor may straddle zero — caller widens
    with np.errstate(all="ignore"):
        cands = [a[0] / b[0], a[0] / b[1], a[1] / b[0], a[1] / b[1]]
    lo = np.minimum(np.minimum(cands[0], cands[1]),
                    np.minimum(cands[2], cands[3]))
    hi = np.maximum(np.maximum(cands[0], cands[1]),
                    np.maximum(cands[2], cands[3]))
    return _nan_guard(lo, hi)


def _bitwise(eqn, name, a, b):
    """Known-bits transfer for and/or/xor on the non-negative cone."""
    import numpy as np

    aval = eqn.outvars[0].aval
    if str(aval.dtype) == "bool":
        if name == "and":
            return (a[0] * b[0], a[1] * b[1])
        return (np.maximum(a[0], b[0]) if name == "or"
                else np.zeros_like(a[0]),
                np.minimum(a[1] + b[1], 1.0))
    if float(a[0].min()) < 0 or float(b[0].min()) < 0:
        return _top(aval)
    zero = np.zeros_like(a[0])
    if name == "and":
        return (zero, np.minimum(a[1], b[1]))
    return (zero, _next_mask(np.maximum(a[1], b[1])))


def _transfer(eqn, ivals, rec, path):
    """One primitive equation -> output intervals (list, one per
    outvar). Unknown primitives fall back to dtype-top — sound."""
    import numpy as np

    name = eqn.primitive.name
    aval = eqn.outvars[0].aval if eqn.outvars else None
    p = eqn.params

    if name in ("copy", "stop_gradient", "device_put", "reduce_precision"):
        return [ivals[0]]
    if name == "convert_element_type":
        return [_int_out(eqn, ivals[0], rec, path)]
    if name == "broadcast_in_dim":
        shape = tuple(p["shape"])
        bd = tuple(p["broadcast_dimensions"])
        exp = [1] * len(shape)
        for i, d in enumerate(bd):
            exp[d] = ivals[0][0].shape[i]
        lo = np.broadcast_to(ivals[0][0].reshape(exp), shape)
        hi = np.broadcast_to(ivals[0][1].reshape(exp), shape)
        return [(lo, hi)]
    if name == "reshape":
        dims = p.get("dimensions")
        lo, hi = ivals[0]
        if dims is not None:
            lo, hi = np.transpose(lo, dims), np.transpose(hi, dims)
        ns = tuple(p["new_sizes"])
        return [(lo.reshape(ns), hi.reshape(ns))]
    if name == "transpose":
        perm = tuple(p["permutation"])
        return [(np.transpose(ivals[0][0], perm),
                 np.transpose(ivals[0][1], perm))]
    if name == "squeeze":
        ax = tuple(int(d) for d in p["dimensions"])
        return [(np.squeeze(ivals[0][0], axis=ax),
                 np.squeeze(ivals[0][1], axis=ax))]
    if name == "expand_dims":
        ax = tuple(int(d) for d in p["dimensions"])
        lo, hi = ivals[0]
        for d in sorted(ax):
            lo, hi = np.expand_dims(lo, d), np.expand_dims(hi, d)
        return [(lo, hi)]
    if name == "rev":
        ax = tuple(int(d) for d in p["dimensions"])
        return [(np.flip(ivals[0][0], ax), np.flip(ivals[0][1], ax))]
    if name == "slice":
        starts = p["start_indices"]
        limits = p["limit_indices"]
        strides = p["strides"] or (1,) * len(starts)
        sl = tuple(slice(int(a), int(b), int(s))
                   for a, b, s in zip(starts, limits, strides))
        return [(np.ascontiguousarray(ivals[0][0][sl]),
                 np.ascontiguousarray(ivals[0][1][sl]))]
    if name == "concatenate":
        d = int(p["dimension"])
        return [(np.concatenate([iv[0] for iv in ivals], axis=d),
                 np.concatenate([iv[1] for iv in ivals], axis=d))]
    if name == "pad":
        glo, ghi = _collapse(_union(
            _collapse_pair(ivals[0]), _collapse_pair(ivals[1])))
        return [_full(aval.shape, glo, ghi)]
    if name == "iota":
        d = int(p["dimension"])
        shape = tuple(p["shape"])
        ar = np.arange(shape[d], dtype=np.float64).reshape(
            [shape[d] if i == d else 1 for i in range(len(shape))])
        return [(np.broadcast_to(ar, shape),
                 np.broadcast_to(ar, shape))]
    if name == "dynamic_slice":
        glo, ghi = _collapse(ivals[0])
        return [_full(aval.shape, glo, ghi)]
    if name == "dynamic_update_slice":
        ulo, uhi = _collapse(ivals[1])
        return [(np.minimum(ivals[0][0], ulo),
                 np.maximum(ivals[0][1], uhi))]
    if name == "select_n":
        # elementwise feasibility: a case whose index the predicate
        # interval excludes does not widen the union — this is what
        # keeps the jnp.mod lowering (rem + lt(x,0) + select fix-up)
        # from leaking the infeasible negative branch
        plo, phi = ivals[0]
        lo = np.full(plo.shape, _INF)
        hi = np.full(plo.shape, -_INF)
        for i, iv in enumerate(ivals[1:]):
            feas = (plo <= i) & (phi >= i)
            lo = np.where(feas, np.minimum(lo, iv[0]), lo)
            hi = np.where(feas, np.maximum(hi, iv[1]), hi)
        return [(lo, hi)]
    if name == "clamp":
        mn, x, mx = ivals
        lo = np.minimum(np.maximum(x[0], mn[0]), mx[0])
        hi = np.minimum(np.maximum(x[1], mn[1]), mx[1])
        return [(lo, hi)]
    if name == "gather":
        return [_transfer_gather(eqn, ivals, rec, path)]
    if name.startswith("scatter"):
        return [_int_out(eqn, _transfer_scatter(eqn, ivals, rec, path),
                         rec, path)]
    if name in ("add", "sub"):
        a, b = ivals
        iv = ((a[0] + b[0], a[1] + b[1]) if name == "add"
              else (a[0] - b[1], a[1] - b[0]))
        return [_int_out(eqn, iv, rec, path)]
    if name == "mul":
        return [_int_out(eqn, _mul_iv(*ivals), rec, path)]
    if name == "div":
        out = _div_iv(*ivals)
        if out is None:
            return [_top(aval)]
        if np.dtype(aval.dtype).kind in "iu":
            out = (np.trunc(out[0]), np.trunc(out[1]))
        return [_int_out(eqn, out, rec, path)]
    if name == "rem":
        dmax = np.maximum(np.abs(ivals[1][0]), np.abs(ivals[1][1]))
        glo, ghi = _collapse((dmax, dmax))
        nonneg = float(ivals[0][0].min()) >= 0
        return [_full(aval.shape, 0.0 if nonneg else -(ghi - 1),
                      max(ghi - 1, 0.0))]
    if name == "neg":
        return [_int_out(eqn, (-ivals[0][1], -ivals[0][0]), rec, path)]
    if name == "abs":
        lo, hi = ivals[0]
        alo = np.where(lo > 0, lo, np.where(hi < 0, -hi, 0.0))
        ahi = np.maximum(np.abs(lo), np.abs(hi))
        return [(alo, ahi)]
    if name == "sign":
        return [(np.sign(ivals[0][0]), np.sign(ivals[0][1]))]
    if name in ("max", "min"):
        f = np.maximum if name == "max" else np.minimum
        return [(f(ivals[0][0], ivals[1][0]), f(ivals[0][1], ivals[1][1]))]
    if name in ("eq", "ne", "lt", "le", "gt", "ge"):
        # elementwise decidable comparisons fold to 0/1 — predicate
        # precision is what makes the select_n feasibility filter work
        a, b = ivals
        one = lambda x: x.astype(np.float64)  # noqa: E731
        if name == "lt":
            return [(one(a[1] < b[0]), one(a[0] < b[1]))]
        if name == "le":
            return [(one(a[1] <= b[0]), one(a[0] <= b[1]))]
        if name == "gt":
            return [(one(a[0] > b[1]), one(a[1] > b[0]))]
        if name == "ge":
            return [(one(a[0] >= b[1]), one(a[1] >= b[0]))]
        overlap = (a[0] <= b[1]) & (b[0] <= a[1])
        pinned = (a[0] == a[1]) & (b[0] == b[1]) & (a[0] == b[0])
        if name == "eq":
            return [(one(pinned), one(overlap))]
        return [(one(~overlap), one(~pinned))]
    if name == "is_finite":
        return [_full(aval.shape, 0.0, 1.0)]
    if name in ("and", "or", "xor"):
        return [_bitwise(eqn, name, ivals[0], ivals[1])]
    if name == "not":
        if str(aval.dtype) == "bool":
            return [(1.0 - ivals[0][1], 1.0 - ivals[0][0])]
        return [(-ivals[0][1] - 1.0, -ivals[0][0] - 1.0)]
    if name == "population_count":
        bits = np.dtype(aval.dtype).itemsize * 8
        return [_full(aval.shape, 0.0, float(bits))]
    if name in ("clz", "count_leading_zeros"):
        bits = np.dtype(aval.dtype).itemsize * 8
        return [_full(aval.shape, 0.0, float(bits))]
    if name == "shift_left":
        a, b = ivals
        if float(a[0].min()) < 0 or float(b[0].min()) < 0:
            return [_top(aval)]
        with np.errstate(over="ignore"):
            iv = (a[0] * np.exp2(b[0]), a[1] * np.exp2(b[1]))
        return [_int_out(eqn, iv, rec, path)]
    if name in ("shift_right_logical", "shift_right_arithmetic"):
        a, b = ivals
        with np.errstate(over="ignore"):
            if float(a[0].min()) < 0:
                if name == "shift_right_arithmetic":
                    return [(np.floor(a[0] / np.exp2(b[0])),
                             np.floor(a[1] / np.exp2(b[0])))]
                return [_top(aval)]
            return [(np.floor(a[0] / np.exp2(b[1])),
                     np.floor(a[1] / np.exp2(b[0])))]
    if name in ("reduce_sum",):
        ax = _reduce_axes(eqn)
        iv = (ivals[0][0].sum(axis=ax), ivals[0][1].sum(axis=ax))
        return [_int_out(eqn, iv, rec, path)]
    if name in ("reduce_max", "reduce_min"):
        f = np.max if name == "reduce_max" else np.min
        ax = _reduce_axes(eqn)
        return [(f(ivals[0][0], axis=ax), f(ivals[0][1], axis=ax))]
    if name in ("reduce_or", "reduce_and"):
        ax = _reduce_axes(eqn)
        if str(aval.dtype) == "bool":
            f = np.max if name == "reduce_or" else np.min
            return [(f(ivals[0][0], axis=ax), f(ivals[0][1], axis=ax))]
        if name == "reduce_and" and float(ivals[0][0].min()) >= 0:
            return [(np.zeros(aval.shape),
                     np.min(ivals[0][1], axis=ax))]
        if float(ivals[0][0].min()) >= 0:
            return [(np.zeros(aval.shape),
                     _next_mask(np.max(ivals[0][1], axis=ax)))]
        return [_top(aval)]
    if name in ("argmax", "argmin"):
        ax = _reduce_axes(eqn)
        opshape = eqn.invars[0].aval.shape
        top = max((opshape[a] for a in ax), default=1) - 1
        return [_full(aval.shape, 0.0, float(top))]
    if name in ("cumsum",):
        ax = int(p["axis"])
        lo, hi = ivals[0]
        if p.get("reverse"):
            lo, hi = np.flip(lo, ax), np.flip(hi, ax)
        lo, hi = np.cumsum(lo, axis=ax), np.cumsum(hi, axis=ax)
        if p.get("reverse"):
            lo, hi = np.flip(lo, ax), np.flip(hi, ax)
        return [_int_out(eqn, (lo, hi), rec, path)]
    if name in ("cummax", "cummin"):
        f = np.maximum.accumulate if name == "cummax" \
            else np.minimum.accumulate
        ax = int(p["axis"])
        return [(f(ivals[0][0], axis=ax), f(ivals[0][1], axis=ax))]
    if name == "sort":
        d = int(p.get("dimension", -1))
        outs = []
        for iv in ivals:
            lo = np.broadcast_to(iv[0].min(axis=d, keepdims=True),
                                 iv[0].shape)
            hi = np.broadcast_to(iv[1].max(axis=d, keepdims=True),
                                 iv[1].shape)
            outs.append((lo, hi))
        return outs
    if name == "dot_general":
        (lhs_c, _rhs_c), _batch = p["dimension_numbers"]
        kdim = 1
        for d in lhs_c:
            kdim *= int(eqn.invars[0].aval.shape[d])
        a, b = _collapse(ivals[0]), _collapse(ivals[1])
        cands = [a[0] * b[0], a[0] * b[1], a[1] * b[0], a[1] * b[1]]
        return [_int_out(
            eqn, _full(aval.shape, kdim * min(cands), kdim * max(cands)),
            rec, path)]
    if name == "integer_pow":
        y = int(p["y"])
        lo, hi = ivals[0]
        with np.errstate(all="ignore"):
            c1, c2 = lo ** y, hi ** y
        olo, ohi = np.minimum(c1, c2), np.maximum(c1, c2)
        if y % 2 == 0:
            olo = np.where((lo < 0) & (hi > 0), 0.0, olo)
        return [_int_out(eqn, _nan_guard(olo, ohi), rec, path)]
    if name in ("exp", "log", "tanh", "logistic", "sqrt", "rsqrt",
                "floor", "ceil", "round", "sin", "cos", "log1p",
                "expm1", "erf", "cbrt"):
        fmap = {"exp": np.exp, "log": np.log, "tanh": np.tanh,
                "logistic": lambda x: 1.0 / (1.0 + np.exp(-x)),
                "sqrt": np.sqrt,
                "rsqrt": lambda x: 1.0 / np.sqrt(x),
                "floor": np.floor, "ceil": np.ceil, "round": np.round,
                "log1p": np.log1p, "expm1": np.expm1, "cbrt": np.cbrt,
                "sin": None, "cos": None, "erf": None}
        f = fmap[name]
        if f is None:
            return [_full(aval.shape, -1.0, 1.0)]
        return [_monotone(f, ivals[0])]
    if name in ("random_bits", "rng_bit_generator", "threefry2x32"):
        return [_top(v.aval) for v in eqn.outvars]
    if name == "split":
        sizes = p["sizes"]
        ax = int(p["axis"])
        los = np.split(ivals[0][0], np.cumsum(sizes)[:-1], axis=ax)
        his = np.split(ivals[0][1], np.cumsum(sizes)[:-1], axis=ax)
        return [(np.ascontiguousarray(a), np.ascontiguousarray(b))
                for a, b in zip(los, his)]
    # unknown primitive: sound fallback
    return [_top(v.aval) for v in eqn.outvars]


def _collapse_pair(iv):
    lo, hi = _collapse(iv)
    import numpy as np

    return (np.float64(lo), np.float64(hi))


# ---------------------------------------------------------------------------
# the jaxpr walker (costmodel.cost_jaxpr's control-flow shape, carrying
# intervals instead of byte tallies)


def _read(env, atom):
    from jax.extend.core import Literal

    if isinstance(atom, Literal):
        return _const_ival(atom.val, atom.aval)
    iv = env.get(atom)
    if iv is None:
        return _top(atom.aval)
    return iv


def _shape_fix(iv, aval):
    """Broadcast a seeded interval to the aval's shape."""
    import numpy as np

    lo = np.broadcast_to(np.asarray(iv[0], np.float64), aval.shape)
    hi = np.broadcast_to(np.asarray(iv[1], np.float64), aval.shape)
    return (lo, hi)


def interp_jaxpr(jaxpr, consts, in_ivals, rec, path=""):
    """Walk one ``jax.extend.core.Jaxpr`` propagating intervals; returns the
    output intervals. ``rec=None`` walks silently (scan pre-pass)."""
    env = {}
    for v, c in zip(jaxpr.constvars, consts):
        env[v] = _const_ival(c, v.aval)
    for v, iv in zip(jaxpr.invars, in_ivals):
        env[v] = _shape_fix(iv, v.aval)

    for i, eqn in enumerate(jaxpr.eqns):
        epath = f"{path}eqns[{i}]"
        name = eqn.primitive.name
        ivals = [_read(env, a) for a in eqn.invars]
        if name == "pjit":
            outs = interp_closed(eqn.params["jaxpr"], ivals, rec,
                                 path=f"{epath}/")
        elif name == "scan":
            outs = _interp_scan(eqn, ivals, rec, epath)
        elif name == "while":
            outs = _interp_while(eqn, ivals, rec, epath)
        elif name == "cond":
            outs = _interp_cond(eqn, ivals, rec, epath)
        else:
            subs = []
            for val in eqn.params.values():
                subs.extend(_closed_jaxprs(val))
            if subs and name not in ("reduce_or", "reduce_and",
                                     "reduce_sum", "reduce_max",
                                     "reduce_min", "reduce"):
                sub = subs[0]
                if len(sub.jaxpr.outvars) == len(eqn.outvars):
                    outs = interp_closed(sub, ivals, rec,
                                         path=f"{epath}/")
                else:
                    outs = [_top(v.aval) for v in eqn.outvars]
            else:
                outs = _transfer(eqn, ivals, rec, epath)
        for v, iv in zip(eqn.outvars, outs):
            env[v] = _shape_fix(iv, v.aval)
    return [_read(env, v) for v in jaxpr.outvars]


def _closed_jaxprs(v):
    from .costmodel import _closed_jaxprs as cj

    return cj(v)


def interp_closed(closed, in_ivals, rec, path=""):
    return interp_jaxpr(closed.jaxpr, closed.consts, in_ivals, rec,
                        path=path)


def _widen_carry(init, out, aval):
    """Scan widening: a carry whose bounds grew widens to dtype-top."""
    import numpy as np

    grew = (float(out[0].min()) < float(init[0].min())
            or float(out[1].max()) > float(init[1].max()))
    return _top(aval) if grew else init


def _interp_scan(eqn, ivals, rec, path):
    import numpy as np

    p = eqn.params
    nc, ncar = int(p["num_consts"]), int(p["num_carry"])
    length = int(p["length"])
    body = p["jaxpr"]
    consts, carry, xs = ivals[:nc], ivals[nc:nc + ncar], ivals[nc + ncar:]
    # per-iteration slice bound of each xs: elementwise union over the
    # leading (iteration) axis
    x_elts = [(x[0].min(axis=0), x[1].max(axis=0)) for x in xs]

    def run(car, r):
        return interp_closed(body, consts + car + x_elts, r,
                             path=f"{path}/scan/")

    pre = run(carry, None)
    carry_avals = [v.aval for v in body.jaxpr.invars[nc:nc + ncar]]
    widened = [_widen_carry(c, o, a)
               for c, o, a in zip(carry, pre[:ncar], carry_avals)]
    outs = run(widened, rec)
    car_out = [_union(_shape_fix(c, a), _shape_fix(o, a))
               for c, o, a in zip(carry, outs[:ncar], carry_avals)]
    ys = []
    for iv, v in zip(outs[ncar:], eqn.outvars[ncar:]):
        lo = np.broadcast_to(iv[0][None], (length,) + iv[0].shape)
        hi = np.broadcast_to(iv[1][None], (length,) + iv[1].shape)
        ys.append((lo.reshape(v.aval.shape), hi.reshape(v.aval.shape)))
    return car_out + ys


def _interp_while(eqn, ivals, rec, path):
    p = eqn.params
    cn, bn = int(p["cond_nconsts"]), int(p["body_nconsts"])
    cond_consts = ivals[:cn]
    body_consts = ivals[cn:cn + bn]
    carry = ivals[cn + bn:]
    carry_avals = [v.aval for v in
                   p["body_jaxpr"].jaxpr.invars[bn:]]
    top_carry = [_top(a) for a in carry_avals]
    interp_closed(p["cond_jaxpr"], cond_consts + top_carry, rec,
                  path=f"{path}/while_cond/")
    interp_closed(p["body_jaxpr"], body_consts + top_carry, rec,
                  path=f"{path}/while_body/")
    return top_carry


def _interp_cond(eqn, ivals, rec, path):
    branches = eqn.params["branches"]
    ops = ivals[1:]
    outs = None
    for b, br in enumerate(branches):
        got = interp_closed(br, ops, rec, path=f"{path}/branches[{b}]/")
        got = [_shape_fix(iv, v.aval)
               for iv, v in zip(got, eqn.outvars)]
        outs = got if outs is None else [
            _union(a, g) for a, g in zip(outs, got)]
    return outs


# ---------------------------------------------------------------------------
# fact seeding (the declared-invariant table; docs/DESIGN.md §23)


@dataclasses.dataclass(frozen=True)
class RangeFact:
    """One declared state-leaf bound: matched by path substring, bounds
    resolved against the build's static shape context."""

    match: str
    lo: object        # int | callable(ctx) -> int
    hi: object
    why: str


FACTS = (
    RangeFact(
        ".peerhave", 0, lambda c: c["heartbeat_every"],
        "IHAVE batch counter: +<=1 per round (handle_ihave counts one "
        "advertising batch per edge per round), cleared every "
        "heartbeat_every rounds (clearIHaveCounters; gossipsub.go "
        "heartbeat parity) — so it never exceeds heartbeat_every "
        "between clears"),
    RangeFact(
        ".iasked", 0, lambda c: c["heartbeat_every"] * c["m"],
        "IWANT-asked counter: grows by at most popcount(ihave) <= M "
        "ids per round on the uncapped branch (the build() guard "
        "M*(heartbeat_every+1) <= max_ihave_length selects it at the "
        "audit shape), cleared with peerhave every heartbeat"),
    RangeFact(
        ".msgs.cursor", 0, lambda c: c["m"] - 1,
        "message-ring cursor: allocator writes cursor' = (cursor + "
        "batch) mod M (state.allocate_publishes)"),
    RangeFact(
        ".tick", 0, lambda c: 2 ** 31 - 1 - 64,
        "round counter: i32 with the overflow-horizon leg's declared "
        "headroom — the supervisor note states the 2^31-1-round "
        "horizon; seeded below it so tick+r proves in-range"),
    RangeFact(
        ".events", 0, 0,
        "cumulative EV counters seeded to ZERO on purpose: the "
        "output's hi is then the exact per-round delta bound, which "
        "is the overflow-horizon divisor (contract overflow-horizon)"),
    RangeFact(
        ".msgs.origin", -1, lambda c: c["n"] - 1,
        "message origin ids: -1 empty sentinel or a peer index "
        "(allocate_publishes writes pub_origin, masked >= 0)"),
    RangeFact(
        ".msgs.topic", -1, lambda c: max(c["t"] - 1, 0),
        "message topics: -1 empty sentinel or a subscribed topic index"),
    RangeFact(
        ".msgs.birth", -1, lambda c: 2 ** 31 - 1 - 64,
        "birth round stamps: -1 or a past tick (bounded by the tick "
        "fact's headroom)"),
    RangeFact(
        ".dlv.first_round", -1, lambda c: 2 ** 31 - 1 - 64,
        "first-arrival round stamps: -1 or a past tick"),
    RangeFact(
        ".dlv.first_edge", -1, lambda c: c["k"] - 1,
        "first-arrival edge slots: -1 or a slot index in [0, K)"),
    RangeFact(
        ".topo.nbr", 0, lambda c: c["n"] - 1,
        "dynamic overlay neighbor ids: the junk convention self-points "
        "absent slots (edges.build_edge_perm; state.DynTopo), so every "
        "entry is a valid peer index — mutation writes preserve it "
        "(apply_mutation's batches carry peer ids or the self id)"),
    RangeFact(
        ".topo.rev", 0, lambda c: c["k"] - 1,
        "dynamic overlay reciprocal slots: rev[j, s] is the slot of "
        "edge (j, s) in the neighbor's row — always in [0, K)"),
    RangeFact(
        ".topo.edge_perm", 0, lambda c: c["n"] * c["k"] - 1,
        "dynamic overlay flat involution nbr*K + rev — a flat [N*K] "
        "edge id (absent slots self-point)"),
    RangeFact(
        ".topo.epoch", 0, lambda c: 2 ** 31 - 1,
        "mutation epoch stamps: grow by at most one per applied write "
        "batch (the ISSUE's declared mutation-epoch growth fact) — "
        "dtype-top is the honest bound; epochs are compared, never "
        "used as indices"),
)


def _fact_ctx(name: str, n: int) -> dict:
    hb = PHASE_R if name in ("gossipsub_phase", "phase_csr") else 1
    return {"n": n, "k": 16, "m": AUDIT_M, "t": 1, "heartbeat_every": hb}


def seed_ivals(state, ctx):
    """(in_ivals, fact_hits): per-leaf intervals — FACTS where matched,
    dtype-top otherwise — in tree-flatten order."""
    import jax.tree_util as jtu

    flat = jtu.tree_flatten_with_path(state)[0]
    ivals, hits = [], []
    for path, leaf in flat:
        key = jtu.keystr(path)
        fact = next((f for f in FACTS if key.endswith(f.match)), None)
        if fact is None:
            ivals.append(_dtype_top(getattr(leaf, "dtype", "float32")))
            continue
        lo = fact.lo(ctx) if callable(fact.lo) else fact.lo
        hi = fact.hi(ctx) if callable(fact.hi) else fact.hi
        ivals.append((float(lo), float(hi)))
        hits.append({"leaf": key, "fact": fact.match,
                     "lo": int(lo), "hi": int(hi)})
    return ivals, hits


def leaf_paths(tree) -> list:
    import jax.tree_util as jtu

    return [jtu.keystr(p) for p, _ in jtu.tree_flatten_with_path(tree)[0]]


# ---------------------------------------------------------------------------
# build cells (the costmodel registry + the three range-only cells)


def range_cell(name: str):
    from .costmodel import build_cell

    if name in ("gossipsub", "csr", "lifted", "floodsub", "randomsub",
                "window"):
        return build_cell(name, RANGE_N)
    if name == "gossipsub_phase":
        return build_cell("gossipsub_phase", RANGE_N)
    if name == "phase_csr":
        return build_cell("phase_csr", RANGE_N)
    if name == "dynamic":
        return _dynamic_cell()
    if name == "narrow":
        return _narrow_cell()
    if name == "events":
        return _events_cell()
    raise ValueError(f"unknown build {name!r}; expected one of "
                     f"{RANGE_BUILDS}")


def _narrow_cell():
    """The narrow_counters=True gossipsub build — the int16 cell whose
    non-wrap proof is contract narrow-nonwrap's whole point."""
    import dataclasses as _dc

    from .costmodel import BuildCell, _pub_args, _ring_net
    from ..config import GossipSubParams, PeerScoreThresholds
    from ..models.gossipsub import (
        GossipSubConfig,
        GossipSubState,
        make_gossipsub_step,
    )
    from ..perf.sweep import bench_score_params

    net = _ring_net(RANGE_N)
    _tp, sp = bench_score_params("default", 1)
    cfg = GossipSubConfig.build(
        _dc.replace(GossipSubParams(), flood_publish=False),
        PeerScoreThresholds(), score_enabled=True, narrow_counters=True)
    cfg = _dc.replace(cfg, count_events=False, fanout_slots=0)
    st = GossipSubState.init(net, AUDIT_M, cfg, score_params=sp)
    step = make_gossipsub_step(cfg, net, score_params=sp)
    raw = getattr(step, "__wrapped__", step)
    args = _pub_args((PUB_WIDTH,), RANGE_N)
    return BuildCell("narrow", lambda s: raw(s, *args), st, 1, 1)


def _events_cell():
    """The count_events=True bench build: EV counters live, so the
    events output's hi (seeded from zero) is the per-round delta bound
    the overflow-horizon leg divides by."""
    from .costmodel import BuildCell, _pub_args
    from ..perf.sweep import build_bench

    st, step, _, _ = build_bench(
        RANGE_N, AUDIT_M, heartbeat_every=1, rounds_per_phase=1,
        count_events=True)
    raw = getattr(step, "__wrapped__", step)
    args = _pub_args((PUB_WIDTH,), RANGE_N)
    return BuildCell("events", lambda s: raw(s, *args), st, 1, 1)


def _dynamic_cell():
    """The dynamic-overlay build (guards.build_dynamic_harness's shape
    at RANGE_N): mutation write batches ride as trace constants, so
    apply_mutation's drop scatters land in this build's site records."""
    import dataclasses as _dc

    import jax.numpy as jnp

    from .costmodel import BuildCell, _pub_args
    from .. import graph
    from ..config import GossipSubParams, PeerScoreThresholds
    from ..models.gossipsub import (
        GossipSubConfig,
        GossipSubState,
        make_gossipsub_step,
    )
    from ..perf.sweep import bench_score_params, bench_wire_coalesced
    from ..state import Net
    from ..topo.dynamics import churn_storm

    topo = graph.ring_lattice(RANGE_N, d=8)
    subs = graph.subscribe_all(RANGE_N, 1)
    net = Net.build(topo, subs, dynamic=True)
    params = _dc.replace(GossipSubParams(), flood_publish=False)
    _tp, sp = bench_score_params("default", 1)
    cfg = GossipSubConfig.build(
        params, PeerScoreThresholds(), score_enabled=True,
        heartbeat_every=1, wire_coalesced=bench_wire_coalesced(None))
    cfg = _dc.replace(cfg, count_events=False, fanout_slots=0)
    st = GossipSubState.init(net, AUDIT_M, cfg, score_params=sp, seed=0,
                             dynamic_topo=True)
    step = make_gossipsub_step(cfg, net, score_params=sp,
                               dynamic_peers=True, dynamic_topo=True)
    sched = churn_storm(topo, n_dispatches=4, kill_frac=0.1, rewires=4,
                        joins=1, join_links=2, seed=0)
    writes, up = sched.build()
    args = _pub_args((PUB_WIDTH,), RANGE_N) + (
        jnp.asarray(up[0]), jnp.asarray(writes[0]))
    raw = getattr(step, "__wrapped__", step)
    return BuildCell("dynamic", lambda s: raw(s, *args), st, 1, 1)


# ---------------------------------------------------------------------------
# contracts (pure functions over the recorded sites — the negative
# tests feed them doctored records)


def check_narrow_nonwrap(build: str, sites: list) -> None:
    """Every recorded sub-i32 integer site must fit its dtype."""
    for s in sites:
        if not s.fits:
            raise RangeContractViolation(
                build, "narrow-nonwrap",
                f"{s.path} ({s.primitive}) produces {s.dtype} with "
                f"value bounds [{s.lo:.0f}, {s.hi:.0f}] outside the "
                "dtype — the narrowed counter can wrap")


def check_index_bounds(build: str, sites: list, catalog: dict) -> dict:
    """PROVEN / SANCTIONED_DROP / VIOLATION triage of one build's
    gather+scatter sites; unproven sites must be drop/clip-moded AND
    named in the catalog, else the violation names the eqn."""
    proven = 0
    sanctioned = []
    for s in sites:
        if s.proven:
            proven += 1
            continue
        if s.mode not in ("fill_or_drop", "clip", "fill", "drop"):
            raise RangeContractViolation(
                build, "index-bounds",
                f"{s.path} ({s.primitive}, mode={s.mode}) index bounds "
                f"[{s.index_lo:.0f}, {s.index_hi:.0f}] not proven "
                f"inside [0, {s.bound:.0f}] and the mode promises "
                "in-bounds — undefined behavior on device")
        reason = catalog.get(s.primitive)
        if reason is None:
            raise RangeContractViolation(
                build, "index-bounds",
                f"{s.path} ({s.primitive}, mode={s.mode}) is unproven "
                "and has NO sanctioned-drop catalog entry — name it in "
                "analysis/ranges.py SANCTIONED_DROPS or tighten the "
                "seeding facts")
        sanctioned.append({
            "path": s.path, "primitive": s.primitive, "mode": s.mode,
            "index_lo": _j(s.index_lo), "index_hi": _j(s.index_hi),
            "bound": _j(s.bound), "reason": reason,
        })
    return {"proven": proven, "sanctioned": sanctioned,
            "checked": len(sites)}


def scale_leg(sites=INDEX_SITES, targets=SCALE_TARGETS,
              geometries=SCALE_GEOMETRIES) -> dict:
    """The symbolic index-width table: exact-int max index per site ×
    geometry × peer-count, with an explicit verdict each."""
    out = {}
    for geo_name, geo in geometries.items():
        k, m = int(geo["k"]), int(geo["m"])
        w = _w_of(m)
        rows = {}
        for name, formula, fn in sites:
            verdicts = {}
            for n in targets:
                e = n * k
                mx = int(fn(n, k, m, w, e))
                verdicts[str(n)] = {
                    "max_index": mx,
                    "verdict": ("PROVEN_I32" if mx < 2 ** 31
                                else "NEEDS_I64"),
                }
            rows[name] = {"formula": formula, "by_n": verdicts}
        out[geo_name] = {"k": k, "m": m, "w": w, "sites": rows}
    return out


def check_index_width(leg: dict, acknowledged=I64_ACKNOWLEDGED) -> list:
    """No silent pass: every site×scale row must carry an explicit
    verdict, and an AUDIT-geometry NEEDS_I64 fails until acknowledged
    (acknowledging one is what puts the qualifier into MEM_AUDIT's
    headroom table). Returns the refuted (geometry, site, n) keys."""
    refuted = []
    for geo_name, geo in leg.items():
        for site, row in geo["sites"].items():
            for n, cell in row["by_n"].items():
                v = cell.get("verdict")
                if v not in ("PROVEN_I32", "NEEDS_I64"):
                    raise RangeContractViolation(
                        "scale", "index-width",
                        f"index_width.{geo_name}.sites.{site}.by_n.{n}"
                        f".verdict is {v!r} — every flat-index site "
                        "must carry an explicit PROVEN_I32/NEEDS_I64 "
                        "verdict (no silent pass)")
                if v == "NEEDS_I64":
                    refuted.append(f"{geo_name}.{site}.{n}")
                    if geo_name == "audit" and site not in acknowledged:
                        raise RangeContractViolation(
                            "scale", "index-width",
                            f"index_width.audit.sites.{site}.by_n.{n}: "
                            f"max index {cell['max_index']} NEEDS_I64 "
                            "at the AUDIT geometry — the MEM_AUDIT "
                            "headroom table overclaims; acknowledge "
                            "the site (I64_ACKNOWLEDGED) and qualify "
                            "the headroom table, or widen the plane")
    return refuted


def index_width_verdict(n: int, geometry: str = "audit") -> str:
    """Worst verdict over all flat-index sites at one peer count — the
    MEM_AUDIT headroom table's ``index_width`` column (scripts/
    memstat.py)."""
    leg = scale_leg(targets=(int(n),))
    geo = leg[geometry]
    verdicts = {row["by_n"][str(int(n))]["verdict"]
                for row in geo["sites"].values()}
    return "NEEDS_I64" if "NEEDS_I64" in verdicts else "PROVEN_I32"


def horizons_from_deltas(deltas: dict, *,
                         floor: int = HORIZON_FLOOR_ROUNDS) -> dict:
    """Per-EV overflow horizons from per-round delta bounds: rounds
    until an i32 counter wraps and until an f32 telemetry column stops
    counting exactly (2^24). A zero delta never wraps (null horizon);
    any finite horizon under the floor is a contract failure."""
    out = {}
    for name, delta in deltas.items():
        d = int(delta)
        if d <= 0:
            out[name] = {"per_round_delta_hi": d,
                         "i32_horizon_rounds": None,
                         "f32_exact_horizon_rounds": None}
            continue
        h32 = (2 ** 31 - 1) // d
        h24 = F32_EXACT_LIMIT // d
        out[name] = {"per_round_delta_hi": d,
                     "i32_horizon_rounds": h32,
                     "f32_exact_horizon_rounds": h24}
        if h32 < floor:
            raise RangeContractViolation(
                "events", "overflow-horizon",
                f"horizons.events.{name}.i32_horizon_rounds = {h32} < "
                f"floor {floor} — an always-on cell wraps this counter "
                "within one session; widen it or drain more often")
    return out


def check_narrow_manifest(found: dict, manifest=None) -> None:
    """Source scan vs the declared manifest, positionally per file."""
    manifest = NARROW_ASTYPE_MANIFEST if manifest is None else manifest
    for rel in sorted(set(found) | set(manifest)):
        got = tuple(found.get(rel, ()))
        want = tuple(manifest.get(rel, ()))
        if got != want:
            raise RangeContractViolation(
                "source", "narrow-manifest",
                f"narrow_astype_manifest.{rel}: source has sub-i32 "
                f".astype sites {list(got)} but the declared manifest "
                f"says {list(want)} — extend NARROW_ASTYPE_MANIFEST "
                "(analysis/ranges.py) with the new site's range "
                "justification")


def narrow_astype_scan(pkg_root: str | None = None) -> dict:
    """Device-scope source scan for ``.astype(<sub-i32 int>)`` sites —
    shared with simlint's ``narrow-dtype`` rule (ordered dtypes per
    file, the manifest's shape)."""
    from . import simlint

    root = pkg_root or os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    found: dict = {}
    for rel, src in simlint.iter_device_sources(root):
        sites = simlint.narrow_astype_sites(src, rel)
        if sites:
            found[rel] = tuple(dt for _line, dt in sites)
    return found


# ---------------------------------------------------------------------------
# the audit artifact


def _j(x):
    """JSON-safe number: exact int when finite, None on +-inf."""
    import math

    f = float(x)
    if math.isinf(f) or math.isnan(f):
        return None
    if f == int(f):
        return int(f)
    return f


def audit_build(name: str) -> dict:
    """Trace + walk one build; returns its artifact row (contracts
    raised, not recorded — a failing build aborts the audit)."""
    import jax

    cell = range_cell(name)
    jpr = jax.make_jaxpr(cell.call)(cell.state)
    ctx = _fact_ctx(name, RANGE_N)
    ivals, fact_hits = seed_ivals(cell.state, ctx)
    rec = Recorder()
    outs = interp_closed(jpr, ivals, rec)

    check_narrow_nonwrap(name, rec.narrow)
    index = check_index_bounds(name, rec.index,
                               SANCTIONED_DROPS.get(name, {}))

    row = {
        "eqn_count": len(jpr.jaxpr.eqns),
        "facts_seeded": fact_hits,
        "narrow": {
            "checked": len(rec.narrow),
            "sites": [{
                "path": s.path, "primitive": s.primitive,
                "dtype": s.dtype, "lo": _j(s.lo), "hi": _j(s.hi),
                "fits": s.fits,
            } for s in rec.narrow],
        },
        "index": index,
    }
    if name == "events":
        row["event_deltas"] = _event_deltas(cell, jpr, outs)
    return row


def _event_deltas(cell, jpr, outs) -> dict:
    """Map the events output leaf (seeded [0,0]) to per-EV per-round
    delta bounds."""
    import jax

    from ..trace.events import EV

    out_tree = jax.eval_shape(cell.call, cell.state)
    paths = leaf_paths(out_tree)
    idx = next(i for i, p in enumerate(paths) if p.endswith(".events"))
    hi = outs[idx][1]
    return {e.name: _j(hi.reshape(-1)[int(e)]) for e in EV}


def build_audit() -> dict:
    """The full audit: per-build site verdicts + the symbolic scale leg
    + the overflow horizons + the source manifest. Deterministic trace
    + interval arithmetic — committed RANGE_AUDIT.json must reproduce
    byte-identical (the COST_AUDIT pattern)."""
    builds = {}
    for name in RANGE_BUILDS:
        builds[name] = audit_build(name)

    leg = scale_leg()
    refuted = check_index_width(leg)

    deltas = builds["events"]["event_deltas"]
    horizons = horizons_from_deltas(deltas)

    found = narrow_astype_scan()
    check_narrow_manifest(found)

    narrow_total = sum(b["narrow"]["checked"] for b in builds.values())
    sanctioned_total = sum(len(b["index"]["sanctioned"])
                           for b in builds.values())
    return {
        "schema": 1,
        "note": ("static range/overflow audit (analysis/ranges.py; "
                 "RANGE_UPDATE=1 rewrites). Interval abstract "
                 "interpretation over every engine jaxpr: narrow-dtype "
                 "non-wrap proofs, gather/scatter bound triage with a "
                 "named sanctioned-drop catalog, symbolic 100k/1M/10M "
                 "index-width verdicts, EV-counter overflow horizons."),
        "shape": {"n_peers": RANGE_N, "msg_slots": AUDIT_M,
                  "rounds_per_phase": PHASE_R, "pub_width": PUB_WIDTH,
                  "window_dispatches": WINDOW_D},
        "facts": [{"match": f.match, "why": f.why} for f in FACTS],
        "builds": builds,
        "index_width": {
            "targets": list(SCALE_TARGETS),
            "geometries": leg,
            "needs_i64": sorted(refuted),
            "acknowledged_audit_sites": sorted(I64_ACKNOWLEDGED),
        },
        "horizons": {
            "floor_rounds": HORIZON_FLOOR_ROUNDS,
            "events": horizons,
            "tick": {"dtype": "int32",
                     "i32_horizon_rounds": 2 ** 31 - 1,
                     "note": ("the round counter itself: one "
                             "increment per round")},
            "telemetry_f32_note": (
                "f32 telemetry columns (telemetry/panel.py EV_METRICS) "
                "count exactly until 2^24; the per-EV "
                "f32_exact_horizon_rounds rows divide that limit by "
                "the same per-round delta bounds"),
        },
        "narrow_astype_manifest": {
            rel: list(dts) for rel, dts in
            sorted(NARROW_ASTYPE_MANIFEST.items())},
        "contracts": {
            "narrow_nonwrap": {
                "pass": True, "sites_checked": narrow_total},
            "index_bounds": {
                "pass": True,
                "proven": sum(b["index"]["proven"]
                              for b in builds.values()),
                "sanctioned": sanctioned_total},
            "index_width": {
                "pass": True, "needs_i64": sorted(refuted)},
            "overflow_horizon": {
                "pass": True,
                "floor_rounds": HORIZON_FLOOR_ROUNDS,
                "min_i32_horizon_rounds": min(
                    (h["i32_horizon_rounds"]
                     for h in horizons.values()
                     if h["i32_horizon_rounds"] is not None),
                    default=None)},
            "narrow_manifest": {
                "pass": True, "files": len(NARROW_ASTYPE_MANIFEST)},
        },
        "summary": {
            "builds": len(builds),
            "narrow_sites": narrow_total,
            "index_sanctioned": sanctioned_total,
        },
    }


def audit_path(repo_root: str | None = None) -> str:
    root = repo_root or os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, AUDIT_NAME)
