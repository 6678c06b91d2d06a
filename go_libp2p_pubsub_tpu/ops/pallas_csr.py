"""Fused Pallas TPU kernels for the flat-[E] CSR plane (round 21).

Three kernels extend the fused-delivery approach of pallas_delivery.py
(banded-dense-only) to the capacity-bounded CSR edge space:

  * ``csr_delivery`` — the whole flat delivery commit as THREE
    ``pallas_call``s (edge phase / row phase / edge commit) replacing the
    ~15 XLA kernels of ``models/common.delivery_round``'s CSR branch: the
    neighbor-forward and echo gathers, the link-deny chaos fold, the
    capacity-bounded segmented word-OR, first-arrival isolation, and the
    seen/forward/first-round commit — the [E, W] fwd/echo/mask
    intermediates never round-trip HBM between passes.
  * the edge phase optionally folds the chaos plane's per-edge link-deny
    mask into the SAME gather pass (``link_ok_e``), so the fault plane
    costs no extra traffic (the XLA path ANDs it into the dense edge
    mask and re-packs).
  * ``select_topk_pallas`` — the heartbeat's top-k/shuffle selection
    block (ops/select.rank_desc + select_topk_mask, including the
    masked-width traced-k form tune/ relies on): the O(K^2) pairwise
    compare stays entirely in VMEM — same math as the XLA pairwise
    form, zero HBM compare-plane intermediates.

Blocking: the edge axis is cut into ``block``-row tiles; each grid step
sees two wrapped views (blocks i-1, i modulo the grid) of the
edge-indexed inputs. Because every row segment of the capacity-bounded
edge pool has length <= cap (ops/csr.build_csr), a segment reaches back
at most cap-1 edges, so with block >= cap the previous-block view is
the only halo the segmented scan needs; the scan itself runs as the
same ceil(log2 cap) shifted-OR levels as the composite
(ops/csr.segment_or_scan with ``cap``). Block 0's wrapped "previous"
view carries junk from the last block — harmless, because global edge
0 starts a segment and the scan's start flags cut every lookback there.
Peer-indexed planes ([N, W]) and the gather index vectors ride as
whole-array VMEM refs: flat CSR gathers (col/eperm) are unstructured,
so there is no banded-roll halo to exploit.

Bit-exactness: each kernel is proven equal to its XLA composite twin in
interpret mode on ragged, banded and power-law topologies, chaos masks
on and off (tests/test_pallas_csr.py).

Status on the v5e compiler (jax 0.9.0 / libtpu 0.0.34, ``csr_delivery``
asked with a described `v5e:2x2` device at the E = 1,600,000 of a CSR
bench net, N=100,000 — tests/test_chip_compile.py pins the first two):
REFUSED, by the Pallas TPU lowering before Mosaic is reached, three
times over:

  1. at the blocks models/common.py picks (2000): "The Pallas TPU
     lowering currently requires that rank 1 block shapes, either 1) the
     first (and only) dimension of the block shape is equal to the first
     (and only) dimension of the array shape, or 2) ... is a multiple of
     the tiling size (128 ...)" — the [B] col/eperm/row/seg_start index
     blocks. An aligned edge block exists (2560 | E) but N=100,000 has no
     128-multiple divisor, so the row phase cannot be blocked at all;
  2. past that: "Loads are only allowed on VMEM and SMEM references. ANY
     memory space can only be accessed using async_copy." — the
     whole-array gather sources (``pl.ANY``) are read with ``ref[:]``;
  3. with those in VMEM instead (12.8 MB for the [E, W] plane alone):
     "Shape mismatch in input, indices and output" — the unstructured
     ``fwd[col]`` row gather has no TPU lowering.

None is a local repair: 2 and 3 are the kernel's design (unstructured
gathers from whole arrays). The kernels run in interpret mode only, i.e.
never on a TPU (models/common.py derives interpret mode from the
backend); the restructured XLA composite (``cfg.fused``, ops/select +
ops/csr) is what runs on hardware and is the form `make cost-audit`'s
fusion contract prices. ``select_topk_pallas`` is called by no engine
(tests only) and fails on refusal 1 as well. ROADMAP queue 3 item 3 has
this as its evidence for deletion.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WORD = 32


def pallas_csr_supported(n_edges: int, block: int, cap: int) -> bool:
    """Static preconditions of the fused CSR kernels: the block tiles the
    edge axis and one previous-block view covers the longest segment."""
    return n_edges % block == 0 and block >= cap and n_edges >= 2 * block


def _bounded_segment_or(x, flags, cap):
    """In-VMEM capacity-bounded segmented prefix-OR (the same shifted
    Hillis-Steele levels as ops/csr.segment_or_scan's ``cap`` form)."""
    inc, started = x, flags
    d = 1
    while d < cap:
        prev = jnp.concatenate([jnp.zeros_like(inc[:d]), inc[:-d]], axis=0)
        pst = jnp.concatenate(
            [jnp.ones((d,), bool), started[:-d]], axis=0
        )
        inc = jnp.where(started[:, None], inc, inc | prev)
        started = started | pst
        d *= 2
    return inc


def _edge_phase_kernel(
    # whole-array refs (unstructured gather sources)
    fwd_ref,       # [N, W] u32 — dlv.fwd
    fe_ref,        # [E, W] u32 — flat first-arrival plane (echo source)
    nm_ref,        # [N, W] u32 — not-mine words
    # 2-view (blocks i-1, i) edge-blocked inputs
    mask_m1, mask_0,   # [B, W] u32 edge mask (packed)
    col_m1, col_0,     # [B] i32
    ep_m1, ep_0,       # [B] i32
    row_m1, row_0,     # [B] i32
    ss_m1, ss_0,       # [B] bool segment starts
    *rest,
    cap, b, deny,
):
    if deny:
        ok_m1, ok_0, trans_out, inc_out, exc_out = rest
    else:
        trans_out, inc_out, exc_out = rest
    col = jnp.concatenate([col_m1[:], col_0[:]])
    ep = jnp.concatenate([ep_m1[:], ep_0[:]])
    row = jnp.concatenate([row_m1[:], row_0[:]])
    ss = jnp.concatenate([ss_m1[:], ss_0[:]])
    mask_e = jnp.concatenate([mask_m1[:], mask_0[:]], axis=0)

    fwd = fwd_ref[:]
    fe = fe_ref[:]
    nm = nm_ref[:]

    # one gather pass composes the transmit plane for the 2B window (the
    # i-1 half is recomputed halo — same global values either block)
    trans = fwd[col] & ~fe[ep] & mask_e & nm[row]
    if deny:
        link_ok = jnp.concatenate([ok_m1[:], ok_0[:]])
        trans = trans & jnp.where(
            link_ok[:, None], jnp.uint32(0xFFFFFFFF), jnp.uint32(0)
        )

    inc = _bounded_segment_or(trans, ss, cap)
    shifted = jnp.concatenate([jnp.zeros_like(inc[:1]), inc[:-1]], axis=0)
    exc = jnp.where(ss[:, None], jnp.uint32(0), shifted)

    trans_out[:] = trans[b:]
    inc_out[:] = inc[b:]
    exc_out[:] = exc[b:]


def _row_phase_kernel(
    inc_ref,       # [E, W] u32 whole-array (row_last gathers anywhere)
    rl_blk,        # [Bn] i32 row_last
    ne_blk,        # [Bn] bool row_nonempty
    have_blk,      # [Bn, W] u32
    fr_blk,        # [Bn, M] i32 first_round
    valid_row,     # [1, W] u32
    tick_row,      # [1, 1] i32
    recv_out, new_out, have_out, fwd_out, fr_out,
    *, m,
):
    inc = inc_ref[:]
    rl = rl_blk[:]
    recv = jnp.where(
        ne_blk[:][:, None], inc[jnp.clip(rl, 0)], jnp.uint32(0)
    )
    have = have_blk[:]
    new = recv & ~have
    have2 = have | new
    fwd2 = new & valid_row[0][None, :]

    # unpack the new bits in VMEM for the first_round stamp
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)[0]
    word = new[:, idx // WORD]
    bit = (word >> (idx % WORD).astype(jnp.uint32)) & jnp.uint32(1)
    fr2 = jnp.where(bit == 1, tick_row[0, 0], fr_blk[:])

    recv_out[:] = recv
    new_out[:] = new
    have_out[:] = have2
    fwd_out[:] = fwd2
    fr_out[:] = fr2


def _edge_commit_kernel(
    new_ref,       # [N, W] u32 whole-array (owner gathers)
    trans_blk, exc_blk, fe_blk,   # [B, W] u32
    row_blk,       # [B] i32
    fe_out, fa_out,
):
    new_r = new_ref[:][row_blk[:]]
    fa = trans_blk[:] & ~exc_blk[:] & new_r
    fa_out[:] = fa
    fe_out[:] = (fe_blk[:] & ~new_r) | fa


def csr_delivery(
    fwd,           # [N, W] u32 — dlv.fwd
    fe_e,          # [E, W] u32 — flat first-arrival plane
    mask_e,        # [E, W] u32 — packed edge mask
    not_mine,      # [N, W] u32
    have,          # [N, W] u32
    first_round,   # [N, M] i32
    valid_row,     # [1, W] u32
    tick,          # i32 scalar
    col, row, eperm, seg_start, row_last, row_nonempty,
    *, cap, block, block_rows, interpret=True, link_ok_e=None,
):
    """The fused flat delivery commit. Returns a dict with trans_e, recv,
    new, have, fwd, first_round (post-round peer planes) and fe, fa_e
    (post-round flat planes) — the exact quantities
    ``models/common.finish_delivery_flat`` commits, computed in three
    pallas_calls instead of the composite's unfused chain."""
    e, w = fe_e.shape
    n = fwd.shape[0]
    m = first_round.shape[1]
    assert pallas_csr_supported(e, block, cap), (e, block, cap)
    assert n % block_rows == 0, (n, block_rows)
    nb = e // block
    nbr_ = n // block_rows
    deny = link_ok_e is not None

    full2 = lambda a: pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim,
                                   memory_space=pl.ANY)
    eb = lambda cols: pl.BlockSpec((block, cols), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM)
    eb1 = pl.BlockSpec((block,), lambda i: (i,), memory_space=pltpu.VMEM)
    eb_m1 = lambda cols: pl.BlockSpec(
        (block, cols), lambda i: ((i - 1) % nb, 0), memory_space=pltpu.VMEM
    )
    eb1_m1 = pl.BlockSpec((block,), lambda i: ((i - 1) % nb,),
                          memory_space=pltpu.VMEM)

    in_specs = [
        full2(fwd), full2(fe_e), full2(not_mine),
        eb_m1(w), eb(w),
        eb1_m1, eb1,   # col
        eb1_m1, eb1,   # eperm
        eb1_m1, eb1,   # row
        eb1_m1, eb1,   # seg_start
    ]
    args = [
        fwd, fe_e, not_mine,
        mask_e, mask_e,
        col, col, eperm, eperm, row, row, seg_start, seg_start,
    ]
    if deny:
        in_specs += [eb1_m1, eb1]
        args += [link_ok_e, link_ok_e]

    trans_e, inc, exc = pl.pallas_call(
        functools.partial(_edge_phase_kernel, cap=cap, b=block, deny=deny),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=[eb(w), eb(w), eb(w)],
        out_shape=[jax.ShapeDtypeStruct((e, w), jnp.uint32)] * 3,
        interpret=interpret,
    )(*args)

    rb = lambda cols: pl.BlockSpec((block_rows, cols), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM)
    rb1 = pl.BlockSpec((block_rows,), lambda i: (i,),
                       memory_space=pltpu.VMEM)
    one = lambda cols: pl.BlockSpec((1, cols), lambda i: (0, 0),
                                    memory_space=pltpu.VMEM)
    recv, new, have2, fwd2, fr2 = pl.pallas_call(
        functools.partial(_row_phase_kernel, m=m),
        grid=(nbr_,),
        in_specs=[full2(inc), rb1, rb1, rb(w), rb(m), one(w), one(1)],
        out_specs=[rb(w), rb(w), rb(w), rb(w), rb(m)],
        out_shape=[
            jax.ShapeDtypeStruct((n, w), jnp.uint32),
            jax.ShapeDtypeStruct((n, w), jnp.uint32),
            jax.ShapeDtypeStruct((n, w), jnp.uint32),
            jax.ShapeDtypeStruct((n, w), jnp.uint32),
            jax.ShapeDtypeStruct((n, m), jnp.int32),
        ],
        interpret=interpret,
    )(inc, row_last, row_nonempty, have, first_round, valid_row,
      jnp.asarray(tick, jnp.int32).reshape(1, 1))

    fe2, fa_e = pl.pallas_call(
        _edge_commit_kernel,
        grid=(nb,),
        in_specs=[full2(new), eb(w), eb(w), eb(w), eb1],
        out_specs=[eb(w), eb(w)],
        out_shape=[jax.ShapeDtypeStruct((e, w), jnp.uint32)] * 2,
        interpret=interpret,
    )(new, trans_e, exc, fe_e, row)

    return {
        "trans_e": trans_e,
        "recv": recv,
        "new": new,
        "have": have2,
        "fwd": fwd2,
        "first_round": fr2,
        "fe": fe2,
        "fa_e": fa_e,
    }


def _topk_kernel(v_blk, mask_blk, k_blk, noise_blk, out_blk, *, k_dim):
    primary = jnp.where(
        mask_blk[:], v_blk[:].astype(jnp.float32), jnp.float32(-jnp.inf)
    )
    noise = noise_blk[:]
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, k_dim), 1)[0]
    pi, pj = primary[:, :, None], primary[:, None, :]
    ni, nj = noise[:, :, None], noise[:, None, :]
    ties = pj == pi
    nties = nj == ni
    outranks = (
        (pj > pi) | (ties & (nj > ni))
        | (ties & nties & (idx[None, :] < idx[:, None]))
    )
    rank = jnp.sum(outranks.astype(jnp.int32), axis=-1)
    out_blk[:] = (rank < k_blk[:][:, None]) & mask_blk[:]


def select_topk_pallas(values, mask, k_arr, noise, *, block,
                       interpret=True):
    """The fused heartbeat selection block: per-row top-k over the padded
    neighbor axis with the (value, noise, index)-descending tie order of
    ops/select.rank_desc. ``k_arr`` is a per-row [R] i32 width — the
    traced masked-width form (clip before calling); rows and the K axis
    arrive pre-flattened ([R, K]). The pairwise compare planes live only
    in VMEM."""
    r, k_dim = values.shape
    assert r % block == 0, (r, block)
    rb = lambda cols: pl.BlockSpec((block, cols), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM)
    rb1 = pl.BlockSpec((block,), lambda i: (i,), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_topk_kernel, k_dim=k_dim),
        grid=(r // block,),
        in_specs=[rb(k_dim), rb(k_dim), rb1, rb(k_dim)],
        out_specs=rb(k_dim),
        out_shape=jax.ShapeDtypeStruct((r, k_dim), bool),
        interpret=interpret,
    )(values, mask, jnp.asarray(k_arr, jnp.int32), noise)
