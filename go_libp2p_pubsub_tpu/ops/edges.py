"""Edge-permutation gathers and topic-bit packing.

The protocol's cross-peer reads all have the shape "receiver j reads the
sender's per-edge outbox at [nbr[j,k], rev[j,k]]". A naive multi-index
gather lowers to per-element gather HLO — pathologically slow on TPU. But
(n,k) -> (nbr[n,k], rev[n,k]) is a *permutation* (an involution) of the
N*K edge-slot space, so every such read is a 1-D row gather through a
static flat index `perm = nbr*K + rev` — the fast TPU gather path.

That gather pays per ROW it addresses, not per byte, and a slot that holds
no edge is a row like any other (it points at itself). Where the graph's
degrees are uneven the gather is TIERED (`plan_tiers`, planned once per
static net by `Net.build` from the column histogram): the head columns
[0, K0) are gathered whole, of the tail columns [K0, K) only the slots
that hold an edge move, as a short list scattered onto the columns
themselves; where the full table is too large to be read cheaply the
head's gather reads a COMPACT one (the head columns with the tail's
present rows appended). The plan addresses its table K-MAJOR over a
lane-padded peer axis (slot (n, k) is row `k*Np + n`, `Np` = N rounded up
to 128): the planes live N-minor on the chip, and only a minor axis that
is a whole number of lanes merges into the gather's row axis without a
copy loop. Every slot comes out bit for bit as `flat[perm]` gives it.
K0 = K is the one full gather. A plane wider than one sublane tile of
words crosses in tile-wide column slices, one gather a slice, where only
the slices' tables are small enough to be read cheaply (`word_slices`).
`_tally` counts the rows every gather set addresses, the rows and tile-rows
of the table it reads and whether it was sliced, for the window's
`edge_rows_per_dispatch`, `edge_table_rows`, `edge_table_tile_rows`,
`edge_sliced_calls_per_dispatch` and `peer_rows_per_dispatch`.

Topic-slot payloads ([N,S,K] per-slot bools) are moved across edges by
packing the S axis into *topic-id bit positions* of uint32 words (T bits
total), permuting the [N,K,Wt] words, and re-extracting bits at the
receiver's own slot->topic mapping — the two peers' compressed topic axes
never meet, only topic ids cross the wire (exactly like the reference's
per-topic control messages).
"""

from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

WORD = 32

# ---------------------------------------------------------------------------
# halo-gather tally: each cross-peer gather below is ONE "gather set" — on a
# banded topology it lowers to len(offsets) rolled halo collective-permutes
# under GSPMD (parallel/sharding.py), so counting gather calls at trace time
# IS measuring the per-phase permute budget the v5e-8 projection charges
# (perf/projection.py). The counter is None outside `tally_halo_gathers`,
# keeping the hot path untouched.

_TALLY: list | None = None
_BYTES_TALLY: list | None = None
_ROWS_TALLY: list | None = None


class TallyCacheHit(RuntimeError):
    """``tally_step`` traced a step and recorded ZERO halo seams.

    Every engine body routes its cross-peer movement through the tally
    seams above, so an empty tally means the trace never actually ran
    the body: jax caches jaxprs per jitted callable, and a callable that
    hides a jit INSIDE it (a wrapper without ``__wrapped__``, a window
    closing over a jitted step) can satisfy ``eval_shape`` from that
    cache without re-executing the Python — silently reading zero into
    every halo-budget gate built on the tally (hlo-audit's equal-tally
    legs, topo-smoke's audited bytes, the cost audit). The round-16
    CHANGES NOTE documented the footgun; since round 19 it is a typed
    error instead of a zero."""


def _tally(kind: str, moved=None, rows: int = 0, table_rows: int = 0,
           tile_rows: int = 0, sliced: bool = False) -> None:
    """One cross-peer gather SET: ``moved`` is the tensor it moves,
    ``rows`` the rows it addresses by index (a gather's output rows plus
    a scatter's rows; 0 for rolls, which address none), ``table_rows``
    the rows of the table its largest gather reads and ``tile_rows`` the
    same in the unit the chip charges (``tile_rows``; entries of their
    own, ``("table", n)`` and ``("tile_rows", n)``, and none for rolls),
    ``sliced`` whether ``word_slices`` split the call (``("sliced", 1)``)."""
    if _TALLY is not None:
        _TALLY.append(kind)
    if _ROWS_TALLY is not None:
        _ROWS_TALLY.append((kind, int(rows)))
        if table_rows:
            _ROWS_TALLY.append(("table", int(table_rows)))
            _ROWS_TALLY.append(("tile_rows", int(tile_rows)))
        if sliced:
            _ROWS_TALLY.append(("sliced", 1))
    if _BYTES_TALLY is not None:
        nbytes = None
        if moved is not None and hasattr(moved, "size"):
            # traced shapes are static, so the audited volume is exact
            nbytes = int(moved.size) * moved.dtype.itemsize
        _BYTES_TALLY.append((kind, nbytes))


@contextlib.contextmanager
def tally_halo_gathers(out: list):
    """Collect one entry per cross-peer gather traced inside the block
    (``"edge"``/``"peer"`` tags). Use with ``jax.eval_shape`` to measure a
    step's gather-set count without compiling; ``len(out)`` × the band
    direction count is the permute count the sharded lowering will emit."""
    global _TALLY
    prev = _TALLY
    _TALLY = out
    try:
        yield out
    finally:
        _TALLY = prev


@contextlib.contextmanager
def tally_halo_bytes(out: list):
    """Collect ``(kind, nbytes)`` per cross-peer gather traced inside
    the block — the AUDITED bytes-moved accounting (round 18): nbytes
    is the byte volume of the moved tensor (the edge involution moves
    its whole operand, a peer gather moves its neighbor-view output),
    so on the flat CSR layout the same seam audits E-sized movement
    where the dense layout audits N·K — the topo-smoke A/B's second
    leg. Entries whose seam predates the accounting read None."""
    global _BYTES_TALLY
    prev = _BYTES_TALLY
    _BYTES_TALLY = out
    try:
        yield out
    finally:
        _BYTES_TALLY = prev


@contextlib.contextmanager
def tally_index_rows(out: list):
    """Collect ``(kind, rows)`` per cross-peer gather traced inside the
    block: the rows addressed by index, the unit the general gather pays
    in whatever the bytes (PERF.md §6, PR 30: the gather's law).
    ``driver._jit_window`` arms it around a window's trace
    and ``mark_dispatch`` cuts the list by step call, so the window's
    entry in ``perf.stages`` holds the rows of one dispatch."""
    global _ROWS_TALLY
    prev = _ROWS_TALLY
    _ROWS_TALLY = out
    try:
        yield out
    finally:
        _ROWS_TALLY = prev


def mark_dispatch(key=None) -> None:
    """A window body says that the next step call begins; ``key`` is
    what the call's trace is keyed by besides its shapes (the static
    ``do_heartbeat``). No gather set: only the rows tally hears it."""
    if _ROWS_TALLY is not None:
        _ROWS_TALLY.append(("dispatch", key))


def edge_rows_per_dispatch(tally: list, of: str = "edge") -> float | None:
    """Mean ``"edge"`` rows per step call of a ``tally_index_rows`` list
    cut by ``mark_dispatch`` (``of="sliced"``: the calls ``word_slices``
    split; ``of="peer"``: the rows its peer gathers address). A jitted
    step is traced once per key: a later call with the same key replays
    the cached jaxpr and tallies nothing, so it counts what the first
    call with its key counted.
    ``None`` where no call tallied anything (no window body marked a
    dispatch, or every trace was a replay)."""
    calls, first = [], {}
    for kind, val in tally:
        if kind == "dispatch":
            calls.append([val, None])
        elif calls:
            calls[-1][1] = (calls[-1][1] or 0) + (val if kind == of else 0)
    for key, rows in calls:
        if rows is not None:
            first.setdefault(key, rows)
    rows = [first[key] for key, _ in calls if key in first]
    return sum(rows) / len(rows) if rows else None


def edge_table_rows(tally: list, of: str = "table") -> int | None:
    """The largest table an edge gather of a ``tally_index_rows`` list
    reads, in rows: N*K through the full ``edge_perm``; of a planned net
    K*Np through the full table, K0*Np + T where the compact one engaged
    (``of="tile_rows"``: in tile-rows, at the width it is read in).
    ``None`` where no gather read one (rolls, or every trace was a
    replay)."""
    return max((val for kind, val in tally if kind == of), default=None)


def tally_step(step, state, args=(), kwargs=None, *, net=None,
               count_bytes: bool = False) -> list:
    """Trace ONE step call under the armed halo tally and return the
    raw tally list — the shared harness behind `make hlo-audit`'s
    equal-tally legs, mesh2d_dryrun's halo census, topo-smoke's
    audited-bytes leg and the cost audit's halo cross-check. Unwraps to
    the UNJITTED body itself because the caveat lives here, once: jax's
    tracing cache is keyed on the jitted function, so eval_shape of the
    jit can hit a cached jaxpr from an earlier trace and silently
    record ZERO seams — the raw body re-traces every time. A body the
    unwrap cannot reach (a jit hidden INSIDE a plain wrapper) can still
    satisfy the trace from the cache, so an EMPTY tally raises the
    typed :class:`TallyCacheHit` instead of returning zero — no gate
    built on the tally can mistake a cache hit for a seam-free engine.
    ``net`` is threaded as the leading positional for engine bodies
    that take it (the guards harness convention); ``count_bytes``
    switches the tally to (kind, nbytes) entries."""
    import jax

    raw = getattr(step, "__wrapped__", step)
    kwargs = dict(kwargs or {})
    out: list = []
    ctx = tally_halo_bytes(out) if count_bytes else tally_halo_gathers(out)
    with ctx:
        if net is not None:
            jax.eval_shape(lambda s: raw(net, s, *args, **kwargs), state)
        else:
            jax.eval_shape(lambda s: raw(s, *args, **kwargs), state)
    if not out:
        raise TallyCacheHit(
            f"halo tally of {getattr(step, '__name__', step)!r} recorded "
            "ZERO cross-peer seams — either an inner jit satisfied the "
            "trace from a cached jaxpr (pass the raw body; the unwrap "
            "only reaches __wrapped__) or the engine stopped routing "
            "through the ops/edges seams; both break every halo-budget "
            "gate, so this is an error, never a silent zero")
    return out


def fold_tally(tally: list) -> dict:
    """{"total": n, kind: count, ...} of a tally_halo_gathers list."""
    out = {"total": len(tally)}
    for kind in tally:
        out[kind] = out.get(kind, 0) + 1
    return out


def n_topic_words(n_topics: int) -> int:
    return (n_topics + WORD - 1) // WORD


def build_edge_perm(nbr: np.ndarray, rev: np.ndarray, nbr_ok: np.ndarray) -> np.ndarray:
    """[N,K] i32 flat index into the edge-slot space; self-pointing where
    no edge exists (harmless — callers mask with nbr_ok)."""
    n, k = nbr.shape
    own = np.arange(n * k, dtype=np.int32).reshape(n, k)
    perm = np.clip(nbr, 0, None).astype(np.int32) * k + rev.astype(np.int32)
    return np.where(nbr_ok, perm, own)


def involution_wf(nbr: jax.Array, rev: jax.Array, nbr_ok: jax.Array,
                  edge_perm: jax.Array) -> jax.Array:
    """Scalar bool: the (nbr, rev, nbr_ok, edge_perm) planes form a
    well-formed capacity-bounded edge pool — the structural contract
    ``build_edge_perm``/``build_csr`` establish at build time and the
    dynamic overlay (topo/dynamics.py) must PRESERVE under every
    mutation batch:

      * edge_perm is a self-inverse permutation of [0, N*K);
      * absent slots self-point (the junk convention every masked
        gather relies on);
      * present slots agree with their partner: partner present, the
        partner's nbr points back, perm == nbr*K + rev, no self-edges,
        nbr/rev in range.

    Device-side (jit-safe) — the oracle's edge-involution-wf predicate
    body (oracle/invariants.py)."""
    n, k = nbr.shape
    e = n * k
    ar = jnp.arange(e, dtype=jnp.int32)
    pf = edge_perm.reshape(e).astype(jnp.int32)
    okf = nbr_ok.reshape(e)
    nbrf = nbr.reshape(e).astype(jnp.int32)
    revf = rev.reshape(e).astype(jnp.int32)
    in_range = jnp.all((pf >= 0) & (pf < e))
    ps = jnp.clip(pf, 0, e - 1)  # clip-safe partner index
    invol = jnp.all(pf[ps] == ar)
    absent_self = jnp.all(okf | (pf == ar))
    partner_ok = jnp.all(~okf | okf[ps])
    back = jnp.all(~okf | (nbrf[ps] == (ar // k)))
    agree = jnp.all(~okf | (pf == nbrf * k + revf))
    no_self = jnp.all(~okf | (nbrf != (ar // k)))
    bounds = jnp.all(~okf | ((nbrf >= 0) & (nbrf < n)
                             & (revf >= 0) & (revf < k)))
    return (in_range & invol & absent_self & partner_ok & back & agree
            & no_self & bounds)


def edge_permute(x: jax.Array, perm: jax.Array) -> jax.Array:
    """x[N, K, ...] -> x[nbr[j,k], rev[j,k], ...] as a flat row gather."""
    n, k = perm.shape
    _tally("edge", x, rows=n * k, table_rows=n * k,
           tile_rows=tile_rows(n * k, math.prod(x.shape[2:])))
    flat = x.reshape((n * k,) + x.shape[2:])
    return flat[perm.reshape(-1)].reshape(x.shape)


# ---------------------------------------------------------------------------
# the tiered gather. ``flat[perm]`` pays per ROW it addresses, whatever the
# row holds, and most of all for a row that points at itself
# (scripts/gather_law.py; PERF.md §6, PR 30). Slots are left-packed, so on
# a graph of uneven degree the high columns hold next to no edge, yet every
# slot of them is a row of the gather. So columns [0, K0) are gathered
# whole (the head: K0*Np rows, Np = N rounded up to the lanes), and of
# columns [K0, K) only the T slots that hold an edge move (the tail: T more
# rows of the one gather, one scatter onto the columns themselves).
#
# Which table the head's gather reads is the plan's too (PR 32). Out of the
# full N*K-row table a head row of 5 words costs half again what it costs
# out of a table of its own once that table is large, so there the gather
# reads a COMPACT table: the head columns as they are with the tail's T
# present rows appended (one short gather), K0*Np + T rows, and its index
# plane, which lives in that space, ends in the T tail slots' sources: one
# gather, no patch. A smaller full table costs no more than a compact one,
# which then only adds its own steps; and a compact table that is itself
# large is read whole at the price of the law's cliff (``TABLE_CLIFF_ROWS``).

#: The tiered gather's cost on a TPU v5e, fitted to the whole tiered gather
#: of the 100k-peer ``random_connect`` graph at 5 words a row: ns per head
#: row out of the full table and ns per tail row (its gather and its
#: scatter: ten head rows), from K0 = 20, 24, 28 (36.0 / 28.5 / 31.1 ms; my
#: chip run, PR 30; PR 32's points at K0 = 20, 24, 26, 28, 35.8 / 28.6 /
#: 29.2 / 30.9 ms, fit 11.0 and 110.6); ns per head row out of the compact
#: table, from the same four K0 (30.7 / 21.8 / 21.9 / 22.2 ms: 8.1 with
#: 117.0 a tail row; my chip run, PR 32). The fixed cost (a slice, a short
#: gather, a scatter and a join more than the one full gather) is an
#: estimate: the host's clock cannot resolve it.
HEAD_ROW_NS = 11.3
COMPACT_HEAD_ROW_NS = 8.1
TAIL_ROW_NS = 113.0
TIER_FIXED_NS = 15_000.0

#: Where the gather's price doubles, in TILE-ROWS: a table's rows times the
#: sublane tiles a row of its words pads to (``tile_rows``; 32 B a tile-row).
#: It is where the padded table stops fitting the chip's fast memory space
#: beside the indices and whatever else is live (``S(1)`` on the gather's
#: operands in the compiled text; ``scripts/window_whiles.py``). Same script,
#: the whole tiered gather K-major, my chip runs, PR 36
#: (``docs/data/gather_law_v5e_*_pr36.json``), nothing else live:
#: - flat in the width inside one tile: a compact table of 2,512,481 rows
#:   gives 15.37 / 15.74 / 16.02 / 15.00 ms at 5 / 6 / 7 / 8 words, and
#:   44.32 / 45.26 / 46.67 ms at 9 / 13 / 16 (two tiles, 5.02 M tile-rows);
#: - one tile: compact tables of 2.71, 2.91, 3.11, 3.32 and 3,515,070 rows
#:   give a row up at 5.8-6.4 ns at 5 and 8 words, those of 3,765,605 and
#:   4,016,456 rows at 9.4-10.1;
#: - two tiles (13 words): 2.42, 3.01 and 3,515,382 tile-rows read at
#:   9.4-9.6 ns a row (1.55 x the one-tile price, where two slices cost
#:   2.0-2.3 x: under the cliff a wide plane crosses whole), 4,016,332 and
#:   every larger one at 17.7-18.4.
#: So alone the cliff lies between 3.52 and 3.77 M tile-rows at either width.
#: Inside a window it comes sooner: ``sybil-50k``'s 13-word table of 3,503,360
#: tile-rows is read from HBM at 22 ns a row with five more K-wide planes live
#: (PERF.md §5), ``random-100k``'s 5-word tables of 2.51 M from the fast
#: space. The constant is the round number under both: no table up to it was
#: read slowly alone, the smallest a window read slowly lies 0.1 % over it
#: (smaller ones go to HBM for their neighbours' sake, as four of that cell's
#: eight sub-round tables do: no rule of sizes sees that). Earlier points, in
#: rows of one tile (PR 30, 32): full tables of 0.36-2.7 M rows as cheap as a
#: compact one; 3.5 M rows out of 3.5 M at 5.3 ns; the full table of 4.1 M
#: rows 7.7 ns a head row against 5.1; a compact table of 4.62 M rows read
#: whole (``eth2-100k``'s graph, 6 words) 19 ns a row, 88.4 ms against 52.0
#: out of the full 6.5 M-row table. So the compact table pays where it brings
#: the table from beyond the cliff to within it (``compact_pays``), and a
#: plane wider than a tile crosses in tile-wide slices where that brings each
#: slice's table within it (``word_slices``).
TABLE_CLIFF_ROWS = 3_500_000


#: Lanes of a TPU tile. XLA merges ``[K, Np] -> [K*Np]`` under a tiled
#: layout as a bitcast only where the minor axis is a whole number of them;
#: else it goes through a 1-D buffer one word an iteration, and back (a
#: quarter of the round at 100k peers, PERF.md §6, PR 34).
LANES = 128

#: Words of a sublane tile: the table lies words-major on the chip, a row
#: pads to whole tiles of them, and the gather's price is flat in the width
#: inside one: 5 to 8 words cost the same (above).
TILE_WORDS = 8


def lane_padded(n: int) -> int:
    """``n`` rounded up to a whole number of lanes: the plan's ``Np``."""
    return -(-n // LANES) * LANES


@struct.dataclass
class Tiers:
    """The plan of a tiered edge gather (``plan_tiers``): index planes of
    one static graph, baked into the program like ``edge_perm``. K0 is
    ``head.shape[0]``, Np ``head.shape[1]``, T ``tail_dst.size``. ``head``
    and ``tail_src`` address the table the big gather reads, K-major over
    the lane-padded peer axis. ``compact``: the table ``[K0*Np + T]`` of
    the head columns with the tail's present rows appended, where slot
    (n, k) of a head column is row ``k*Np + n`` and the t-th present tail
    slot (K-major) is row ``K0*Np + t``. Else the full slot table
    ``[K*Np]``, row ``k*Np + n``."""

    head: jax.Array       # [K0, Np] i32: where each head slot's partner
                          # sits in the table; an absent slot points at
                          # itself, a pad slot (n >= N) at row 0
    tail_src: jax.Array   # [T] i32: the same for the present tail slots
    tail_dst: jax.Array   # [T] i32 into the tail's own (k-K0)*Np + n,
                          # ascending and duplicate-free: where the tail's
                          # rows go, and the rows a compact table appends
    compact: bool = struct.field(pytree_node=False, default=False)
    #: the cliff ``word_slices`` holds a wide plane's table against
    cliff: int = struct.field(pytree_node=False, default=TABLE_CLIFF_ROWS)

    @property
    def rows(self) -> int:
        """Rows one gather addresses by index: the big gather's (head and
        tail), the tail's scatter, and the T rows a compact table
        appends."""
        return (self.head.size
                + (3 if self.compact else 2) * self.tail_dst.size)

    def table_rows(self, k: int) -> int:
        """Rows of the table the big gather of a ``[N, k]`` plane reads."""
        return (self.head.size + self.tail_dst.size if self.compact
                else self.head.shape[1] * k)


def tile_rows(rows: int, words: int) -> int:
    """What a table of ``rows`` rows of ``words`` words weighs against the
    cliff: its rows times the sublane tiles a row pads to."""
    return rows * -(-words // TILE_WORDS)


def compact_pays(full_rows, compact_rows, cliff=TABLE_CLIFF_ROWS):
    """Whether the big gather should read the compact table of
    ``compact_rows`` rows and not the full one of ``full_rows``, by the
    law above (elementwise over arrays; both one tile wide)."""
    return (compact_rows <= cliff) & (cliff < full_rows)


def word_slices(table_rows: int, words: int,
                cliff: int = TABLE_CLIFF_ROWS) -> list[tuple[int, int]]:
    """The column slices ``[lo, hi)`` a plane of ``words`` words a row
    crosses in, out of a table of ``table_rows`` rows: one tile of words a
    slice where the whole table lies beyond the cliff and a slice's table
    does not, else the plane whole (always, up to one tile of words)."""
    if tile_rows(table_rows, words) > cliff >= table_rows:
        return [(lo, min(lo + TILE_WORDS, words))
                for lo in range(0, words, TILE_WORDS)]
    return [(0, words)]


def tier_cost_ns(col_fill, n: int) -> np.ndarray:
    """``[K+1]`` modelled ns of one gather for every K0 over the column
    histogram ``nbr_ok.sum(0)``: ``N*K0`` head rows, at the price of the
    table ``compact_pays`` gives that K0, and the ``tail(K0)`` present
    slots right of them. K0 = K is the one full gather: no tail, no fixed
    cost, every row at the full table's head price (the law prices a row
    of the full gather at up to three times that: the model errs against
    tiering)."""
    col_fill = np.asarray(col_fill, np.int64)
    k = col_fill.size
    tail = np.append(np.cumsum(col_fill[::-1])[::-1], 0)
    k0 = np.arange(k + 1)
    # the tables at the size the plan gives them (at K0 = K the "compact"
    # one is the full one: never the cheaper); the rows at the prices they
    # were fitted at, before the peer axis was padded
    n_pad = lane_padded(n)
    head_ns = np.where(compact_pays(n_pad * k, n_pad * k0 + tail),
                       COMPACT_HEAD_ROW_NS, HEAD_ROW_NS)
    return (n * k0 * head_ns + tail * TAIL_ROW_NS
            + np.where(k0 < k, TIER_FIXED_NS, 0.0))


def pick_k0(col_fill, n: int) -> int:
    """The K0 of least ``tier_cost_ns``; K0 = K wins ties."""
    cost = tier_cost_ns(col_fill, n)
    k = cost.size - 1
    best = int(np.argmin(cost))
    return k if cost[k] <= cost[best] else best


def plan_tiers(perm: np.ndarray, nbr_ok: np.ndarray, k0: int | None = None,
               compact: bool | None = None,
               cliff: int = TABLE_CLIFF_ROWS) -> Tiers | None:
    """Plan the tiered gather of one static graph, on the host. ``None``
    is K0 = K: the one full gather, today's program. ``k0``, ``compact``
    and ``cliff`` are for the tests and the law's script; ``Net.build``
    lets ``pick_k0`` and ``compact_pays`` derive the first two from the
    graph and leaves the cliff where the chip put it."""
    n, k = perm.shape
    if k0 is None:
        k0 = pick_k0(nbr_ok.sum(axis=0), n)
    if k0 >= k:
        return None
    n_pad = lane_padded(n)
    cols, rows = np.nonzero(nbr_ok[:, k0:].T)   # K-major: ascending
    if compact is None:
        compact = compact_pays(n_pad * k, n_pad * k0 + rows.size, cliff)
    # every slot's row in the table, by its full-space index n*K + k
    addr = (np.arange(k, dtype=np.int32)[None, :] * n_pad
            + np.arange(n, dtype=np.int32)[:, None])
    if compact:
        # an absent tail slot has no row (-1) and nobody asks: a present
        # slot's partner is present, an absent head slot points at itself
        addr[:, k0:] = -1
        addr[rows, cols + k0] = n_pad * k0 + np.arange(rows.size,
                                                       dtype=np.int32)
    src = addr.reshape(-1)[perm]
    # the pad slots point at row 0: a run of self-pointing rows is the
    # dearest index pattern the law found
    head = np.zeros((k0, n_pad), np.int32)
    head[:, :n] = src[:, :k0].T
    # cast on the host: a device-side convert is one more program to compile
    i32 = lambda a: jnp.asarray(np.asarray(a, np.int32))
    return Tiers(head=i32(head), tail_src=i32(src[rows, cols + k0]),
                 tail_dst=i32(cols * n_pad + rows), compact=bool(compact),
                 cliff=int(cliff))


def edge_permute_tiered(x: jax.Array, tiers: Tiers) -> jax.Array:
    """``edge_permute(x, edge_perm)`` bit for bit on every slot, absent
    ones included, addressing ``tiers.rows`` rows instead of N*K: an
    absent slot of the tail keeps its own entry, as its self-pointing row
    of ``edge_perm`` gives it. Two parameters the plan and the plane give:
    ``tiers.compact``, which table the big gather reads, and
    ``word_slices``, whether a ``[N, K, w]`` plane wider than a tile
    crosses in column slices, each out of a table of its own through the
    same index plane. The tail stays one scatter."""
    n, k = x.shape[:2]
    k0, n_pad = tiers.head.shape
    trail = x.shape[2:]
    table_rows, words = tiers.table_rows(k), math.prod(trail)
    # no plane of more than one trailing axis is a tile wide
    cuts = (word_slices(table_rows, words, tiers.cliff) if len(trail) == 1
            else [(0, words)])
    whole = len(cuts) == 1
    # every slice gathers (and appends its rows); the scatter is one
    _tally("edge", x, table_rows=table_rows, sliced=not whole,
           rows=tiers.rows
           + (len(cuts) - 1) * (tiers.rows - tiers.tail_dst.size),
           tile_rows=tile_rows(table_rows, max(hi - lo for lo, hi in cuts)))
    # a table a slice; and the slices' rows side by side again
    cols = lambda a: [a] if whole else [a[..., lo:hi] for lo, hi in cuts]
    join = lambda parts: (parts[0] if whole
                          else jnp.concatenate(parts, axis=-1))
    # the [K, Np, ...] view: the planes live N-minor, so XLA keeps it
    k_major = (1, 0) + tuple(range(2, x.ndim))
    xt = jnp.pad(x, ((0, n_pad - n),) + ((0, 0),) * (x.ndim - 1)
                 ).transpose(k_major)
    tail = xt[k0:].reshape(((k - k0) * n_pad,) + trail)
    onto_tail = lambda rows: tail.at[tiers.tail_dst].set(
        rows, unique_indices=True, indices_are_sorted=True)
    if tiers.compact:
        tables = [jnp.concatenate([h, t[tiers.tail_dst]]) for h, t in zip(
            cols(xt[:k0].reshape((k0 * n_pad,) + trail)), cols(tail))]
        # ONE gather a table: the head block, then the T rows the tail
        # slots take
        index = jnp.concatenate([tiers.head.reshape(-1), tiers.tail_src])
        moved = join([table[index] for table in tables])
        tail = onto_tail(moved[k0 * n_pad:])
        head = moved[:k0 * n_pad]
    else:
        # the head's gather gives its block whole: no slice of a joined one
        tables = cols(xt.reshape((k * n_pad,) + trail))
        tail = onto_tail(join([table[tiers.tail_src] for table in tables]))
        head = join([table[tiers.head.reshape(-1)] for table in tables])
    out = jnp.concatenate(
        [head.reshape((k0, n_pad) + trail),
         tail.reshape((k - k0, n_pad) + trail)], axis=0)
    return out.transpose(k_major)[:n]


def detect_banded(
    nbr: np.ndarray, rev: np.ndarray, nbr_ok: np.ndarray
) -> tuple[tuple[int, ...], tuple[int, ...]] | None:
    """(offsets, rev_slots) when the topology is banded-regular: every edge
    present, slot k of every node holding ring offset off[k] with a constant
    reverse slot. Gathers along such a topology are static rolls — the fast
    TPU path (roll = slice+concat, fully fusable; gather is ~9x slower)."""
    n, k = nbr.shape
    if k == 0 or not nbr_ok.all():
        return None
    off = (nbr.astype(np.int64) - np.arange(n)[:, None]) % n
    if not (off == off[0]).all() or not (rev == rev[0]).all():
        return None
    return tuple(int(o) for o in off[0]), tuple(int(r) for r in rev[0])


def edge_permute_banded(
    x: jax.Array, off: tuple[int, ...], rev: tuple[int, ...]
) -> jax.Array:
    """Banded-regular edge_permute: out[j,k] = x[(j+off[k]) % N, rev[k]]."""
    _tally("edge", x)
    cols = [jnp.roll(x[:, r], -o, axis=0) for o, r in zip(off, rev)]
    return jnp.stack(cols, axis=1)


def edge_permute_banded_flat(
    x: jax.Array, off: tuple[int, ...], rev: tuple[int, ...]
) -> jax.Array:
    """edge_permute_banded for [N,K,C] payloads via 8-aligned flat pieces.

    The stack-of-[N,1,C] formulation gives every rolled piece a degenerate
    T(1,128) sublane tile on the TPU's preferred N-minor layout; padding C
    to a multiple of 8 and concatenating [N,Cp] pieces keeps every piece an
    aligned sublane group of the N-minor [N,K*Cp] result.

    Status: NOT the default. Measured end-to-end on the bench this wins
    ~5x on the gather itself (2.1ms -> 0.4ms of device time) but loses
    globally (322 -> 293 ticks/s): the flat result's layout propagates
    into every downstream consumer of the [N,K,W] word planes, degrading
    their tiles (T(2,128) on the W=2 slices). Kept for a future pass that
    migrates the consumers to flat [N,K*W] planes wholesale."""
    n, k, c = x.shape
    pad = -c % 8
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((n, k, pad), x.dtype)], axis=-1
        )
    cp = c + pad
    flat = x.reshape(n, k * cp)
    pieces = [
        jnp.roll(flat[:, r * cp : (r + 1) * cp], -o, axis=0)
        for o, r in zip(off, rev)
    ]
    out = jnp.concatenate(pieces, axis=1).reshape(n, k, cp)
    return out[..., :c] if pad else out


def peer_gather_banded(v: jax.Array, off: tuple[int, ...]) -> jax.Array:
    """Banded-regular v[nbr]: out[j,k] = v[(j+off[k]) % N]."""
    out = jnp.stack([jnp.roll(v, -o, axis=0) for o in off], axis=1)
    _tally("peer", out)
    return out


def topic_pack(x: jax.Array, my_topics: jax.Array, n_topics: int) -> jax.Array:
    """x[N,S,K] bool -> [N,K,Wt] u32 with bit t set on edge k iff the
    sender's slot for topic t has x true."""
    wt = n_topic_words(n_topics)
    t = my_topics  # [N,S]
    live = (t >= 0)[:, :, None]  # [N,S,1]
    shift = (jnp.clip(t, 0) % WORD).astype(jnp.uint32)[:, :, None]
    val = jnp.where(x & live, jnp.uint32(1) << shift, jnp.uint32(0))  # [N,S,K]
    words = []
    for w in range(wt):
        in_word = ((jnp.clip(t, 0) // WORD) == w)[:, :, None]
        contrib = jnp.where(in_word, val, jnp.uint32(0))
        words.append(jax.lax.reduce(contrib, jnp.uint32(0), lambda a, b: a | b, (1,)))
    return jnp.stack(words, axis=-1)  # [N,K,Wt]


def topic_unpack(words: jax.Array, my_topics: jax.Array) -> jax.Array:
    """[N,K,Wt] u32 -> [N,S,K] bool at the receiver's slot->topic mapping."""
    t = my_topics  # [N,S]
    tc = jnp.clip(t, 0)
    shift = (tc % WORD).astype(jnp.uint32)[:, :, None]  # [N,S,1]
    # static Wt loop: pick the word holding topic t's bit
    out = jnp.zeros(t.shape + (words.shape[1],), jnp.uint32)  # [N,S,K]
    for w in range(words.shape[-1]):
        sel = ((tc // WORD) == w)[:, :, None]  # [N,S,1]
        out = out | jnp.where(sel, words[..., w][:, None, :], jnp.uint32(0))
    bits = (out >> shift) & jnp.uint32(1)
    return bits.astype(bool) & (t >= 0)[:, :, None]
