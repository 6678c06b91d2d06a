"""Capacity-bounded CSR edge layout: the sparse data plane (round 15).

The dense edge involution (ops/edges.py) spends every cross-peer gather
on the full padded ``[N, K]`` slot space — on a capacity-padded ragged
topology (power-law / random graphs padded to the max degree) most of
those slots are dead, yet every exchange moves, masks, and re-reads
them. This module is the sparse-regime alternative (Topiary,
arXiv:2312.06800, is the scalable-pubsub exemplar): the E present edges
packed flat in row-major ``(owner, slot)`` order with a row-pointer —
a *capacity-bounded* CSR, meaning every row holds at most K entries,
which is what lets ragged reductions compile to bounded-width gathers
instead of sorts or data-dependent loops.

Layout (host-built once per topology, ``build_csr``):

  row_ptr[N+1]   edges of peer n are the contiguous span
                 ``[row_ptr[n], row_ptr[n+1])``
  col[E]         neighbor peer id of each edge (the CSR column index)
  row[E]         owner peer id (the expanded row index; sorted)
  e2nk[E]        flat ``n*K + k`` dense-slot address of each edge — the
                 PACK gather (dense plane -> flat edge plane)
  e_of_nk[N,K]   flat edge id of each dense slot, -1 where absent — the
                 UNPACK gather (flat -> dense, absent slots filled)
  eperm[E]       the edge involution in FLAT edge space:
                 ``eperm[e_of_nk[n,k]] == e_of_nk[nbr[n,k], rev[n,k]]``
                 — an [E] permutation (its own inverse), the sparse
                 counterpart of ops/edges.build_edge_perm

Cross-peer data movement in this layout is E-sized, not N*K-sized:
``edge_permute_flat`` (the involution) and ``peer_gather_flat`` (the
neighbor view) are 1-D row gathers over [E, ...] arrays — dead slots
never cross the wire. Pack/unpack are LOCAL relayouts (each peer reads
its own slots), so they add nothing to the halo-permute budget the
v5e-8 projection charges (only the two flat gathers tally, exactly like
their dense counterparts).

Reductions back to peers come in two exact-equivalent forms:

  * ``segment_sum_edges`` — ``jax.ops.segment_sum`` over the sorted row
    ids (arithmetic reductions: counts, scores);
  * ``segment_or_words`` / ``segment_or_scan`` — bitwise-OR has no
    exact segment_sum decomposition (bits collide), so the packed-word
    OR reduction is either a segmented associative scan (log-depth
    passes over [E, W] — the fully-flat form) or the capacity-bounded
    gather (``unpack_edges`` + ``bitset.word_or_reduce`` — one
    bounded-width pass). Both are property-tested equal. Which one the
    delivery engine uses follows the STATE residency (round 18): a
    CSR-RESIDENT state (flat [E, W] fe_words) takes the fully-flat
    commit (models/common.finish_delivery_flat — one scan yields both
    the receive OR and the first-arrival isolation, and the dense
    [N, K, W] transmit tensor never materializes: the low-density win
    `make topo-smoke` measures), while a dense-resident state against
    a csr Net keeps the bounded-gather form (its [N, K, W]
    intermediate feeds RoundInfo's dense consumers — the gossipsub
    scoring path; docs/DESIGN.md §15/§18 have the tradeoff table).

Sharding (round 18): the flat edge space partitions WITH the peer
axis — row-owner order means block boundaries chosen at row_ptr
entries (``block_boundaries``) give each shard whole rows, and
``pad_csr_blocks`` equalizes the blocks with inert padding edges so
GSPMD block sharding is legal on any ragged graph
(state.Net.build(edge_shards=...), parallel.state_shardings).

Word-dtype hygiene: every literal in a packed-word op below is an
explicit ``jnp.uint32`` (simlint ``word-dtype``); no traced Python
branches (``traced-branch``) — layout selection is trace-time static
(state.Net.edge_layout, a pytree-aux field).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import edges as _edges


@dataclasses.dataclass(frozen=True)
class CsrTopology:
    """Host-side CSR build of one padded adjacency (see module doc)."""

    row_ptr: np.ndarray   # [N+1] i32
    col: np.ndarray       # [E] i32
    row: np.ndarray       # [E] i32 (sorted ascending)
    slot: np.ndarray      # [E] i32 — dense slot k of each edge
    e2nk: np.ndarray      # [E] i32 — flat n*K + k
    e_of_nk: np.ndarray   # [N, K] i32, -1 absent
    eperm: np.ndarray     # [E] i32 — flat involution

    @property
    def n_peers(self) -> int:
        return self.row_ptr.shape[0] - 1

    @property
    def max_degree(self) -> int:
        return self.e_of_nk.shape[1]

    @property
    def n_edges(self) -> int:
        return self.col.shape[0]

    @property
    def n_real_edges(self) -> int:
        """Present (non-padding) edge count — equals ``n_edges`` except
        on block-padded builds (pad_csr_blocks), whose inert padding
        edges never appear in ``e_of_nk``."""
        return int((self.e_of_nk >= 0).sum())

    @property
    def density(self) -> float:
        """Real E / (N*K): the fraction of padded slots that hold an
        edge — the dense-vs-CSR byte ratio for per-edge exchange
        traffic. Padding edges don't count."""
        return self.n_real_edges / float(self.n_peers * self.max_degree)

    @property
    def seg_start(self) -> np.ndarray:
        """[E] bool: True at the first edge of each flat row segment —
        the segmented-scan reset flags. Derived from the flat ``row``
        ordering (NOT row_ptr, which no longer indexes the edge axis on
        block-padded builds): padding edges extend their block's last
        row segment and carry zeros, so reductions never see them."""
        s = np.ones(self.n_edges, bool)
        if self.n_edges:
            s[1:] = self.row[1:] != self.row[:-1]
        return s

    @property
    def row_last(self) -> np.ndarray:
        """[N] i32: flat index of each row's last edge (clip-safe junk
        for empty rows — pair with ``row_nonempty``). searchsorted over
        the sorted flat ``row``, so padded builds resolve to the end of
        the row's segment (trailing padding edges carry zeros inside
        the same segment — the inclusive scan's value is unchanged)."""
        return np.maximum(
            np.searchsorted(self.row, np.arange(self.n_peers),
                            side="right") - 1, 0).astype(np.int32)

    @property
    def row_nonempty(self) -> np.ndarray:
        """[N] bool: rows owning at least one REAL edge."""
        return (self.e_of_nk >= 0).any(axis=1)


def block_boundaries(row_ptr: np.ndarray, n_blocks: int) -> np.ndarray:
    """[n_blocks+1] edge indices partitioning [0, E) into ``n_blocks``
    row-ptr-ALIGNED spans: every boundary is a ``row_ptr`` entry (each
    block owns whole rows), each chosen as the row boundary nearest the
    ideal equal split ``E*i/n_blocks``. Monotone by construction —
    blocks can be empty on pathologically skewed graphs (one hub row
    holding more than E/n_blocks edges), which padding then equalizes."""
    row_ptr = np.asarray(row_ptr, np.int64)
    e = int(row_ptr[-1])
    bounds = np.zeros(n_blocks + 1, np.int64)
    bounds[-1] = e
    for i in range(1, n_blocks):
        ideal = (e * i) // n_blocks
        # nearest row boundary to the ideal split
        j = int(np.searchsorted(row_ptr, ideal))
        lo = row_ptr[j - 1] if j > 0 else row_ptr[0]
        hi = row_ptr[j] if j < row_ptr.shape[0] else row_ptr[-1]
        bounds[i] = int(hi if (hi - ideal) <= (ideal - lo) else lo)
    # enforce monotonicity (degenerate skew can make neighbors cross)
    np.maximum.accumulate(bounds, out=bounds)
    return bounds.astype(np.int32)


def pad_csr_blocks(ct: CsrTopology, n_blocks: int
                   ) -> tuple["CsrTopology", np.ndarray]:
    """Pad a CSR build so the edge axis splits into ``n_blocks`` EQUAL
    row-owner-aligned blocks — the shape contract GSPMD block sharding
    needs (parallel: the [E] planes partition by row owner, so each
    shard's halo is its boundary rows, never a row split mid-way).

    Padding edges are inert by construction: ``e_valid`` is False,
    ``eperm`` self-points (the involution stays an involution),
    ``e_of_nk`` never maps a dense slot to them (unpack ignores them),
    and ``pack_edges``/``peer_gather_flat`` mask them to zero via
    ``e_valid`` — so every flat plane carries 0 there forever and
    segment reductions see no contribution. ``row`` takes the owning
    block's last real row (keeps the sorted-row invariant segment_sum
    relies on). Returns ``(padded_topology, e_valid[E'])``."""
    bounds = block_boundaries(ct.row_ptr, n_blocks)
    seg_lens = np.diff(bounds)
    block = int(seg_lens.max()) if n_blocks else 0
    e_new = block * n_blocks
    n, k = ct.e_of_nk.shape

    col = np.zeros(e_new, np.int32)
    row = np.zeros(e_new, np.int32)
    slot = np.zeros(e_new, np.int32)
    e2nk = np.zeros(e_new, np.int32)
    eperm = np.zeros(e_new, np.int32)
    e_valid = np.zeros(e_new, bool)
    e_of_nk = np.full((n, k), -1, np.int32)
    new_of_old = np.zeros(ct.n_edges, np.int32)
    for b in range(n_blocks):
        lo, hi = int(bounds[b]), int(bounds[b + 1])
        dst = b * block
        sl = slice(dst, dst + (hi - lo))
        new_of_old[lo:hi] = np.arange(dst, dst + (hi - lo), dtype=np.int32)
        col[sl] = ct.col[lo:hi]
        row[sl] = ct.row[lo:hi]
        slot[sl] = ct.slot[lo:hi]
        e2nk[sl] = ct.e2nk[lo:hi]
        e_valid[sl] = True
        pad = slice(dst + (hi - lo), dst + block)
        # inert rows: the block's last owned row (sorted-row invariant);
        # an empty block inherits the previous boundary's row
        pad_row = int(ct.row[hi - 1]) if hi > lo else (
            int(ct.row[lo - 1]) if lo > 0 else 0)
        row[pad] = pad_row
        col[pad] = pad_row
        e2nk[pad] = pad_row * k  # junk target; masked by e_valid
        eperm[pad] = np.arange(dst + (hi - lo), dst + block, dtype=np.int32)
    eperm[e_valid] = new_of_old[ct.eperm]
    e_of_nk[ct.row, ct.slot] = new_of_old

    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(ct.row, minlength=n), out=row_ptr[1:])
    # row_ptr keeps addressing the REAL edges of each row — but the flat
    # axis is no longer contiguous per row across block boundaries, so
    # the padded build keeps the original row_ptr only as degree info
    padded = CsrTopology(
        row_ptr=row_ptr.astype(np.int32),
        col=col, row=row, slot=slot, e2nk=e2nk,
        e_of_nk=e_of_nk, eperm=eperm,
    )
    if not (padded.eperm[padded.eperm] == np.arange(e_new)).all():
        raise AssertionError("pad_csr_blocks: padded eperm lost involution")
    return padded, e_valid


def build_csr(nbr: np.ndarray, rev: np.ndarray,
              nbr_ok: np.ndarray) -> CsrTopology:
    """Build the CSR layout from the padded adjacency (graph.Topology
    fields). Requires a symmetric topology (every present edge's
    reverse present — the graph builders' invariant); raises otherwise,
    because the flat involution would have nowhere to point."""
    nbr = np.asarray(nbr)
    rev = np.asarray(rev)
    nbr_ok = np.asarray(nbr_ok, bool)
    n, k = nbr.shape
    rows, slots = np.nonzero(nbr_ok)  # row-major: sorted by (n, k)
    e = rows.shape[0]
    if e == 0:
        raise ValueError("build_csr: topology has no edges")
    e_of_nk = np.full((n, k), -1, np.int32)
    e_of_nk[rows, slots] = np.arange(e, dtype=np.int32)
    col = nbr[rows, slots].astype(np.int32)
    eperm = e_of_nk[col, rev[rows, slots]]
    if (eperm < 0).any():
        bad = int(np.flatnonzero(eperm < 0)[0])
        raise ValueError(
            f"build_csr: edge {int(rows[bad])}->{int(col[bad])} has no "
            "present reverse edge — the topology is not symmetric"
        )
    if not (eperm[eperm] == np.arange(e)).all():
        raise ValueError("build_csr: rev mapping is not an involution")
    counts = nbr_ok.sum(axis=1).astype(np.int64)
    row_ptr = np.zeros(n + 1, np.int32)
    np.cumsum(counts, out=row_ptr[1:])
    return CsrTopology(
        row_ptr=row_ptr,
        col=col,
        row=rows.astype(np.int32),
        slot=slots.astype(np.int32),
        e2nk=(rows * k + slots).astype(np.int32),
        e_of_nk=e_of_nk,
        eperm=eperm.astype(np.int32),
    )


def build_csr_full(nbr: np.ndarray, rev: np.ndarray,
                   nbr_ok: np.ndarray) -> tuple[CsrTopology, np.ndarray]:
    """FULL-CAPACITY identity CSR (round 22 dynamic overlay): every
    padded ``[N, K]`` slot — present or absent — owns a flat edge,
    E = N*K in row-major slot order. The flat structure (e2nk, e_of_nk,
    seg_start, row_last) is then a pure function of the CAPACITY, never
    of the edge list, which is what lets the overlay rewire on device
    without reshaping anything: only col/eperm/e_valid change, as traced
    [E] planes (state.Net.with_overlay). Absent slots are inert exactly
    like pad_csr_blocks padding edges — the returned ``e_valid``
    (= nbr_ok flat) masks them in the flat gathers and every flat plane
    carries 0 there; their eperm self-points (the dense absent-slot junk
    convention, ops/edges.build_edge_perm)."""
    nbr = np.asarray(nbr)
    rev = np.asarray(rev)
    nbr_ok = np.asarray(nbr_ok, bool)
    n, k = nbr.shape
    e = n * k
    ar = np.arange(e, dtype=np.int32)
    perm = _edges.build_edge_perm(nbr, rev, nbr_ok).reshape(e)
    if not (perm[perm] == ar).all():
        raise ValueError("build_csr_full: rev mapping is not an involution")
    okf = nbr_ok.reshape(e)
    nbrf = nbr.reshape(e)
    row = (ar // k).astype(np.int32)
    if not (okf[perm] == okf).all() or not (nbrf[perm][okf] == row[okf]).all():
        raise ValueError("build_csr_full: topology is not symmetric")
    ct = CsrTopology(
        row_ptr=(np.arange(n + 1, dtype=np.int64) * k).astype(np.int32),
        col=np.clip(nbrf, 0, None).astype(np.int32),
        row=row,
        slot=(ar % k).astype(np.int32),
        e2nk=ar.copy(),
        e_of_nk=ar.reshape(n, k).copy(),
        eperm=perm.astype(np.int32),
    )
    return ct, okf.copy()


# ---------------------------------------------------------------------------
# device kernels — local relayouts (no halo cost)


def pack_edges(x: jax.Array, e2nk: jax.Array, k: int) -> jax.Array:
    """[N, K, ...] dense plane -> [E, ...] flat edge plane (present
    slots only, row-major order). A local take — each peer reads its
    own slots, so this never crosses the peer axis."""
    n = x.shape[0]
    flat = x.reshape((n * k,) + x.shape[2:])
    return flat[e2nk]


def unpack_edges(x_e: jax.Array, e_of_nk: jax.Array,
                 fill=None) -> jax.Array:
    """[E, ...] flat edge plane -> [N, K, ...] dense plane; absent
    slots take ``fill`` (default: the dtype's zero). Local scatter-by-
    gather (each peer writes its own slots)."""
    n, k = e_of_nk.shape
    idx = jnp.clip(e_of_nk, 0).reshape(-1)
    got = x_e[idx].reshape((n, k) + x_e.shape[1:])
    present = (e_of_nk >= 0).reshape((n, k) + (1,) * (x_e.ndim - 1))
    if fill is None:
        fill = jnp.zeros((), x_e.dtype)
    return jnp.where(present, got, fill)


# ---------------------------------------------------------------------------
# device kernels — cross-peer gathers (one halo tally each, exactly
# like their dense counterparts in ops/edges.py)


def edge_permute_flat(x_e: jax.Array, eperm: jax.Array) -> jax.Array:
    """The edge involution in flat space: out[e] = x_e[eperm[e]] —
    E-sized cross-peer movement (the dense form moves N*K)."""
    _edges._tally("edge", x_e, rows=eperm.shape[0],
                  table_rows=x_e.shape[0])
    return x_e[eperm]


def peer_gather_flat(v: jax.Array, col: jax.Array) -> jax.Array:
    """Flat neighbor view: out[e] = v[col[e]] ([N, ...] -> [E, ...])."""
    out = v[col]
    _edges._tally("peer", out, rows=col.shape[0])
    return out


# ---------------------------------------------------------------------------
# segment reductions over the sorted row ids


def segment_sum_edges(x_e: jax.Array, row: jax.Array,
                      n_peers: int) -> jax.Array:
    """Arithmetic per-peer reduction of a flat edge plane:
    out[n] = sum of x_e over peer n's edges (``jax.ops.segment_sum``
    over the sorted row ids — the CSR-native reduction)."""
    return jax.ops.segment_sum(
        x_e, row, num_segments=n_peers, indices_are_sorted=True
    )


def segment_popcount(words_e: jax.Array, row: jax.Array,
                     n_peers: int) -> jax.Array:
    """[E, W] packed words -> [N] i32 per-peer set-bit counts."""
    per_edge = jnp.sum(
        jax.lax.population_count(words_e).astype(jnp.int32), axis=-1
    )
    return segment_sum_edges(per_edge, row, n_peers)


def segment_or_scan(words_e: jax.Array, seg_start: jax.Array,
                    cap: int | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """Segmented prefix-OR over a flat packed-word plane.

    Returns ``(inclusive, exclusive)`` [E, W] prefix ORs within each
    row segment — ``exclusive`` is the word-OR of all earlier edges of
    the same row (zero at row starts), which is exactly the mask the
    first-arrival isolation needs (``x & ~exclusive`` keeps each bit's
    first carrying edge, the flat analogue of
    ``bitset.first_set_per_bit``).

    ``cap=None`` (default) runs the log2(E)-depth associative scan.
    With ``cap`` (the capacity bound K of the edge pool — every row
    segment has length <= cap by construction, ops/csr.build) the scan
    runs as ceil(log2(cap)) shifted OR levels instead (the round-21
    fused composite, ``cfg.fused``): at E=8k/K=16 that is 4 levels vs
    13, and the cost audit charges each level's [E, W] operand bytes,
    so the bounded form is the one whose hbm_bytes/round the fusion
    contract pins. Bit-exact with the unbounded scan for any legal
    ``cap`` (tests/test_fused_composites.py) — both realize the same
    segmented-OR monoid, the bound only truncates provably-masked
    levels."""
    flags = jnp.asarray(seg_start, bool)
    if cap is None:
        def comb(a, b):
            av, af = a
            bv, bf = b
            return jnp.where(bf[..., None], bv, av | bv), af | bf

        inc, _ = jax.lax.associative_scan(comb, (words_e, flags), axis=0)
    else:
        # Hillis-Steele over the segmented monoid: element e folds in
        # element e-d unless a segment start lies in (e-d, e]. Shift
        # distances 1, 2, 4, .. cover lookback 2^L - 1 >= cap - 1, which
        # reaches every element's segment start. Out-of-range positions
        # contribute (0, started=True) — global edge 0 starts a segment.
        inc, started = words_e, flags
        d = 1
        while d < cap:
            prev_inc = jnp.concatenate(
                [jnp.zeros_like(inc[:d]), inc[:-d]], axis=0
            )
            prev_started = jnp.concatenate(
                [jnp.ones((d,), bool), started[:-d]], axis=0
            )
            inc = jnp.where(started[:, None], inc, inc | prev_inc)
            started = started | prev_started
            d *= 2
    shifted = jnp.concatenate(
        [jnp.zeros_like(inc[:1]), inc[:-1]], axis=0
    )
    exc = jnp.where(flags[:, None], jnp.uint32(0), shifted)
    return inc, exc


def segment_or_words(words_e: jax.Array, seg_start: jax.Array,
                     row_last: jax.Array,
                     row_nonempty: jax.Array,
                     cap: int | None = None) -> jax.Array:
    """[E, W] -> [N, W] per-peer word-OR via the segmented scan (the
    fully-flat form; property-tested equal to unpack +
    ``bitset.word_or_reduce``)."""
    inc, _ = segment_or_scan(words_e, seg_start, cap=cap)
    out = inc[jnp.clip(row_last, 0)]
    return jnp.where(
        jnp.asarray(row_nonempty, bool)[:, None], out, jnp.uint32(0)
    )
