"""Fused Pallas TPU kernel for the banded-topology delivery round.

One `pallas_call` replaces the ~15 XLA kernels of `common.delivery_round`
(neighbor-forward gather, echo suppression, edge masking, OR-reduce,
first-arrival attribution, seen-cache/forward updates) when the topology is
banded-regular (ops/edges.detect_banded — the bench's ring lattice).

Blocking: the peer axis is cut into `block`-row tiles; each grid step sees
three wrapped views of the neighbor-read arrays (blocks i-1, i, i+1 modulo
the grid), so every ring offset in [-block, block] resolves to a static
in-VMEM slice — the halo-exchange idiom without manual DMA. Requires
max |offset| <= block and block | N.

Packed [., W] word tensors keep HBM traffic minimal; all bit work happens
unpacked in VMEM registers. The kernel is exact — bit-identical to the
XLA path (tests/test_pallas.py proves it in interpret mode and the banded
parity suite covers the surrounding step).

Status on the v5e compiler (jax 0.9.0 / libtpu 0.0.34, asked with a
described `v5e:2x2` device at the bench shape N=100,000 block=2000 —
tests/test_chip_compile.py pins it): REFUSED. The message:

    MosaicError: INTERNAL: Mosaic failed to compile TPU kernel:
    infer-vector-layout: unsupported shape cast
    %160 = "tpu.reshape"(%159) :
        (vector<2000x64xi32>) -> vector<2000x2x32xi32>

That cast is `_pack_bits` / `_unpack_words` — the word<->bit reshape the
packed layout is built on — so there is no local repair (block size,
alignment, VMEM budget do not touch it); the kernel runs in interpret
mode only, i.e. never on a TPU (models/common.py derives interpret mode
from the backend). The XLA path is the default. ROADMAP queue 3 item 3
has this as its evidence for deletion.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

WORD = 32


def signed_offsets(offsets: tuple, n: int) -> tuple:
    """Ring offsets stored mod n -> signed offsets for static slicing."""
    return tuple(o if o <= n // 2 else o - n for o in offsets)


def pallas_supported(offsets: tuple, n: int, block: int) -> bool:
    """Whether the fused kernel's static preconditions hold: the block tiles
    the peer axis, the halo fits one block, and edge slots fit int8."""
    if n % block != 0:
        return False
    if len(offsets) > 127:  # first-arrival sentinel must not collide
        return False
    return max(abs(o) for o in signed_offsets(offsets, n)) <= block


def _unpack_words(words, m):
    """u32[..., W] -> int32 0/1 [..., m] inside the kernel."""
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, WORD), 1)
    bits = (words[..., None] >> shifts[0]) & jnp.uint32(1)
    flat = bits.reshape(words.shape[:-1] + (words.shape[-1] * WORD,))
    return flat[..., :m].astype(jnp.int32)


def _pack_bits(bits):
    """int32 0/1 [..., m] -> u32 [..., ceil(m/32)] inside the kernel.
    Unrolled OR accumulation — Mosaic has no unsigned reductions."""
    m = bits.shape[-1]
    w = (m + WORD - 1) // WORD
    pad = w * WORD - m
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros(bits.shape[:-1] + (pad,), bits.dtype)], axis=-1
        )
    b = bits.reshape(bits.shape[:-1] + (w, WORD)).astype(jnp.uint32)
    acc = b[..., 0]
    for s in range(1, WORD):
        acc = acc | (b[..., s] << jnp.uint32(s))
    return acc


def _kernel(
    # inputs
    fwd_m1, fwd_0, fwd_p1,          # [B, W] u32 — neighbor halo views of dlv.fwd
    fe_m1, fe_0, fe_p1,             # [B, M] i8 — halo views of dlv.first_edge
    emask,                          # [B, K*W] u32 — edge_mask (pre-ANDed with nbr_ok)
    have_in,                        # [B, W] u32
    fr_in,                          # [B, M] i32 first_round
    origin_vec,                     # [1, M] i32 — msgs.origin
    valid_row,                      # [1, W] u32 — packed msgs.valid
    tick_ref,                       # [1, 1] i32 (SMEM)
    # outputs
    trans_out,                      # [B, K*W] u32
    have_out,                       # [B, W] u32
    fwd_out,                        # [B, W] u32
    fr_out,                         # [B, M] i32
    fe_out,                         # [B, M] i8
    *, block, m, offsets, revs,
):
    b = block
    k_dim = len(offsets)
    w = have_in.shape[-1]
    fwd3 = jnp.concatenate([fwd_m1[:], fwd_0[:], fwd_p1[:]], axis=0)   # [3B, W]
    fe3 = jnp.concatenate([fe_m1[:], fe_0[:], fe_p1[:]], axis=0)       # [3B, M]

    have_bits = _unpack_words(have_in[:], m)       # [B, M]
    # origin exclusion computed in-registers from my global row index
    rows = pl.program_id(0) * b + jax.lax.broadcasted_iota(jnp.int32, (b, m), 0)
    not_mine = (origin_vec[0, :][None, :] != rows).astype(jnp.int32)  # [B, M]

    acc = jnp.zeros((b, m), jnp.int32)
    # no-arrival sentinel = k_dim (pallas_supported caps k_dim at 127, so
    # the sentinel never collides with a real slot)
    arrival = jnp.full((b, m), k_dim, jnp.int32)
    trans_words = []
    for k in range(k_dim):
        o, rk = offsets[k], revs[k]
        fw = _unpack_words(fwd3[b + o : 2 * b + o, :], m)       # sender fwd
        echo = (fe3[b + o : 2 * b + o, :] == jnp.int8(rk)).astype(jnp.int32)
        em = _unpack_words(emask[:, k * w : (k + 1) * w], m)
        t = fw * (1 - echo) * em * not_mine                      # [B, M] 0/1
        trans_words.append(_pack_bits(t))
        arrival = jnp.where((t == 1) & (arrival == k_dim), k, arrival)
        acc = acc | t

    trans_out[:] = jnp.concatenate(trans_words, axis=-1)

    new = acc & (1 - have_bits)
    new_words = _pack_bits(new)
    have_out[:] = have_in[:] | new_words
    fwd_out[:] = new_words & valid_row[0, :]
    tick = tick_ref[0, 0]
    fr_out[:] = jnp.where(new == 1, tick, fr_in[:])
    fe_out[:] = jnp.where(
        (new == 1) & (arrival < k_dim), arrival.astype(jnp.int8), fe_0[:]
    )


@functools.partial(
    jax.jit,
    static_argnames=("block", "m", "offsets", "revs", "interpret"),
)
def delivery_round_banded(
    fwd, first_edge, emask_flat, have, first_round, origin,
    valid_words, tick, *, block, m, offsets, revs, interpret=False,
):
    """Run the fused delivery round. All arrays as in _kernel, full-length
    [N, ...]; returns (trans[N,K,W], have', fwd', first_round', first_edge').

    `emask_flat` is edge_mask reshaped [N, K*W] and already ANDed with the
    live-edge words (ok_words in the XLA path)."""
    n, w = fwd.shape
    assert pallas_supported(offsets, n, block), "preconditions not met"
    nb = n // block
    k_dim = len(offsets)
    soff = signed_offsets(offsets, n)

    row = pl.BlockSpec((block, w), lambda i: (i, 0), memory_space=pltpu.VMEM)
    row_m1 = pl.BlockSpec((block, w), lambda i: ((i - 1) % nb, 0), memory_space=pltpu.VMEM)
    row_p1 = pl.BlockSpec((block, w), lambda i: ((i + 1) % nb, 0), memory_space=pltpu.VMEM)
    fe_spec = lambda f: pl.BlockSpec((block, m), f, memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(
            _kernel, block=block, m=m, offsets=soff, revs=revs
        ),
        grid=(nb,),
        in_specs=[
            row_m1, row, row_p1,
            fe_spec(lambda i: ((i - 1) % nb, 0)),
            fe_spec(lambda i: (i, 0)),
            fe_spec(lambda i: ((i + 1) % nb, 0)),
            pl.BlockSpec((block, k_dim * w), lambda i: (i, 0), memory_space=pltpu.VMEM),
            row,
            fe_spec(lambda i: (i, 0)),
            pl.BlockSpec((1, m), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, w), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((block, k_dim * w), lambda i: (i, 0), memory_space=pltpu.VMEM),
            row,
            row,
            fe_spec(lambda i: (i, 0)),
            fe_spec(lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, k_dim * w), jnp.uint32),
            jax.ShapeDtypeStruct((n, w), jnp.uint32),
            jax.ShapeDtypeStruct((n, w), jnp.uint32),
            jax.ShapeDtypeStruct((n, m), jnp.int32),
            jax.ShapeDtypeStruct((n, m), jnp.int8),
        ],
        interpret=interpret,
    )(
        fwd, fwd, fwd,
        first_edge, first_edge, first_edge,
        emask_flat,
        have,
        first_round,
        jnp.asarray(origin, jnp.int32).reshape(1, m),
        valid_words.reshape(1, w),
        jnp.asarray(tick, jnp.int32).reshape(1, 1),
    )
    trans, have2, fwd2, fr2, fe2 = out
    return trans.reshape(n, k_dim, w), have2, fwd2, fr2, fe2
