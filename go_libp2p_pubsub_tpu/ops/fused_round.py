"""Fused Pallas TPU kernel: the whole edge-crossing data plane of one round.

One `pallas_call` replaces the round's entire wire exchange on banded
topologies — the merged control gather, the delivery round (mesh/fanout/
flood push, echo suppression, seen-cache dedup, first-arrival attribution),
the IWANT service with retransmission counters, and the neighbor-score
exchange. Profiling round 1 put ~55% of device time in exactly this data
movement: every `edge_gather`/`peer_gather` materialized K rolled copies +
a concatenate of [N,K,*] tensors plus layout-conversion copies
(BASELINE.md "what moved the number"); the kernel reads neighbor blocks
from VMEM halo views instead, so none of that traffic exists.

Design rules that keep Mosaic happy (the round-1 kernel was rejected over
packed<->bit shape casts, ops/pallas_delivery.py):
  * everything stays in packed uint32 words — no unpack/pack in-kernel;
  * per-edge results are written to output-ref column slices (a
    `jnp.concatenate` of differently-shifted slices trips a Mosaic layout
    bug — probed on the real chip);
  * neighbor reads use the 3-view halo trick: each grid step sees blocks
    i-1, i, i+1 of every neighbor-read array, so ring offsets in
    [-block, block] are static row slices of the concatenated view.

Semantics are bit-identical to the XLA path (delivery_round +
iwant_responses + merge_extra_tx + the merged wire gather in
models/gossipsub._round); tests/test_fused_round.py drives both paths
through full simulations and compares state trees exactly.

Status on the v5e compiler (jax 0.9.0 / libtpu 0.0.34): both kernels
COMPILE at the bench shape (N=100,000, block = pick_block = 400, one
`tpu_custom_call` each — tests/test_chip_compile.py, against a described
`v5e:2x2` device).

Reference semantics covered (citations as in the XLA path):
  mesh push + fanout + flood edges     gossipsub.go:943-1013, 973-978
  flood-publish (sender-side fold)     gossipsub.go:957-963
  echo suppression / origin exclusion  floodsub.go:85-88
  seen-cache dedup                     pubsub.go:1076-1081 (markSeen)
  IWANT service + retransmission cap   gossipsub.go:679-716
  responder score gate                 gossipsub.go:681-685
  control piggyback in one exchange    gossipsub.go:1096-1141
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import numpy as np

from . import edges as _edges

WORD = 32
# plain numpy scalars: jnp constants at module scope would be captured by
# kernel closures as device arrays, which pallas_call rejects
_ALL = np.uint32(0xFFFFFFFF)
_Z = np.uint32(0)


def signed_offsets(offsets: tuple, n: int) -> tuple:
    return tuple(o if o <= n // 2 else o - n for o in offsets)


def pick_block(n: int, offsets: tuple) -> int | None:
    """Largest block size <= PUBSUB_FUSED_BLOCK (default 400) dividing n
    with the halo (max |offset|) fitting inside one block. Pallas TPU
    requires the sublane block dim divisible by 8 unless it spans the
    whole array."""
    # default sized so the delivery kernel's halo views + lane-padded refs
    # stay under the ~16M VMEM scoped limit (504 measured 17.9M at M=64)
    cap = int(os.environ.get("PUBSUB_FUSED_BLOCK", "400"))
    halo = max((abs(o) for o in signed_offsets(offsets, n)), default=0)
    for b in range(min(cap, n), 0, -1):
        if n % b == 0 and halo <= b and (b % 8 == 0 or b == n):
            return b
    return None


def fused_supported(n: int, offsets: tuple | None, k_dim: int) -> bool:
    if offsets is None or k_dim == 0:
        return False
    return pick_block(n, offsets) is not None


def _gate(cond):
    """bool [B,1] -> u32 word gate broadcastable over [B,W]."""
    return jnp.where(cond, _ALL, _Z)


def served_capped_mask(retrans_cap: int, lo, hi):
    """Word-mask of slots whose 2-bit served count reached the
    retransmission cap (single source for the XLA path's _served_capped
    and the fused kernel — plain jnp ops work in both)."""
    cap = min(max(retrans_cap, 0), 3)
    if cap >= 3:
        return hi & lo
    if cap == 2:
        return hi
    if cap == 1:
        return hi | lo
    return jnp.full_like(lo, _ALL)


def _bit(flags_col, b: int):
    return ((flags_col >> jnp.uint32(b)) & jnp.uint32(1)) != 0


# flags bit assignments (built by make_flags)
F_ACC_MSG = 0    # AcceptFrom message plane (score graylist + gater)
F_FLOOD_FROM = 1  # far end is a floodsub-only peer (static)
F_I_AM_FLOODSUB = 2  # this peer is floodsub-only (static, per-peer)
F_SENDER_FWD = 3  # edge's sender transmits data (adversary vector)
F_LIVE = 4       # edge alive (nbr_ok x churn x edge_live)


def make_flags(acc_msg, flood_from, i_am_floodsub, sender_fwd_ok, live):
    """[N,K] u32 per-edge flag words from the round's bool masks."""
    f = acc_msg.astype(jnp.uint32) << F_ACC_MSG
    f = f | (flood_from.astype(jnp.uint32) << F_FLOOD_FROM)
    f = f | (i_am_floodsub.astype(jnp.uint32)[:, None] << F_I_AM_FLOODSUB)
    f = f | (sender_fwd_ok.astype(jnp.uint32) << F_SENDER_FWD)
    f = f | (live.astype(jnp.uint32) << F_LIVE)
    return f


def _exchange_kernel(
    wire_m1, wire_0, wire_p1,   # [B, K*C] u32 — per-edge outboxes
    *rest, b, k_dim, c, offsets, revs, score_enabled,
):
    if score_enabled:
        sc_m1, sc_0, sc_p1, live, wire_out, nbrsc_out = rest
    else:
        live, wire_out = rest
    wire3 = jnp.concatenate([wire_m1[:], wire_0[:], wire_p1[:]], axis=0)
    if score_enabled:
        sc3 = jnp.concatenate([sc_m1[:], sc_0[:], sc_p1[:]], axis=0)
    for k in range(k_dim):
        o, rk = offsets[k], revs[k]
        base = b + o
        lv = live[:, k : k + 1] != 0
        wire_out[:, k * c : (k + 1) * c] = (
            wire3[base : base + b, rk * c : (rk + 1) * c] & _gate(lv)
        )
        if score_enabled:
            s_k = sc3[base : base + b, rk : rk + 1]
            nbrsc_out[:, k : k + 1] = jnp.where(lv, s_k, jnp.float32(0.0))


@functools.partial(
    jax.jit,
    static_argnames=("block", "offsets", "revs", "c", "score_enabled",
                     "interpret"),
)
def edge_exchange(
    wire_pack,   # [N, K*C] u32 — control outboxes, k-major
    scores,      # [N, K] f32 or None
    live_u32,    # [N, K] u32 — 1 where the edge is alive
    *, block, offsets, revs, c, score_enabled, interpret=False,
):
    """The merged control-wire gather across the edge involution:
    wire_in[j, k] = wire_pack[nbr(j,k), rev(j,k)] (zeroed on dead edges),
    plus the neighbor-score exchange nbr_score[j,k] = scores[nbr, rev].
    Runs before GRAFT/PRUNE ingest — the ingest result feeds the delivery
    kernel's sender mesh, which is why exchange and delivery are two
    pallas calls, not one."""
    # one halo-exchange set (the kernel's block-neighbor DMAs move the
    # same band-edge rows a rolled gather would) — counted so the
    # permute-budget measurement (edges.tally_halo_gathers) stays honest
    # on fused builds
    _edges._tally("edge")
    n = wire_pack.shape[0]
    b = block
    nb = n // b
    k_dim = len(offsets)
    soff = signed_offsets(offsets, n)

    def spec(cols, f):
        return pl.BlockSpec((b, cols), f, memory_space=pltpu.VMEM)

    i0 = lambda i: (i, 0)
    im1 = lambda i: ((i - 1) % nb, 0)
    ip1 = lambda i: ((i + 1) % nb, 0)

    in_specs = [spec(k_dim * c, im1), spec(k_dim * c, i0), spec(k_dim * c, ip1)]
    args = [wire_pack, wire_pack, wire_pack]
    if score_enabled:
        in_specs += [spec(k_dim, im1), spec(k_dim, i0), spec(k_dim, ip1)]
        args += [scores, scores, scores]
    in_specs.append(spec(k_dim, i0))
    args.append(live_u32)

    out_specs = [spec(k_dim * c, i0)]
    out_shape = [jax.ShapeDtypeStruct((n, k_dim * c), jnp.uint32)]
    if score_enabled:
        out_specs.append(spec(k_dim, i0))
        out_shape.append(jax.ShapeDtypeStruct((n, k_dim), jnp.float32))

    outs = pl.pallas_call(
        functools.partial(
            _exchange_kernel, b=b, k_dim=k_dim, c=c, offsets=soff,
            revs=revs, score_enabled=score_enabled,
        ),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    if score_enabled:
        return outs[0], outs[1]
    return outs[0], None


def _delivery_kernel(
    # halo inputs (3 views each: blocks i-1, i, i+1)
    carry_m1, carry_0, carry_p1,   # [B, K*W] u32 sender push outboxes
    fe_m1, fe_0, fe_p1,            # [B, K*W] u32 first-arrival edge plane
    hp_m1, hp_0, hp_p1,            # [B, 2W] u32: fwd | mcache-window
    # local inputs
    nbrsc,                         # [B, K] f32 (score variant; else absent)
    *rest,
    b, k_dim, w, offsets, revs, score_enabled, want_cohorts,
    retrans_cap,
):
    if not score_enabled:
        rest = (nbrsc,) + rest
        nbrsc = None
    (asked, slo, shi, flags, have_ref, origin_ref, joined_ref, valid_ref,
     thr_ref, *outs) = rest
    (trans_out, fe_out, slo_out, shi_out, peer_out) = outs[0:5]
    outs = outs[5:]
    if want_cohorts:
        mesh_t_out, extra_out = outs[0:2]
        outs = outs[2:]
    # scratch for the per-edge first-arrival cohorts: stashing them as SSA
    # values keeps K lane-padded vregs live across the loop (~6 MB at
    # K=16), which blew the 16M scoped-VMEM limit
    ft_scr, fe_scr = outs[0:2]

    carry3 = jnp.concatenate([carry_m1[:], carry_0[:], carry_p1[:]], axis=0)
    fe3 = jnp.concatenate([fe_m1[:], fe_0[:], fe_p1[:]], axis=0)
    hp3 = jnp.concatenate([hp_m1[:], hp_0[:], hp_p1[:]], axis=0)

    have = have_ref[:]
    not_mine = ~origin_ref[:]
    joined = joined_ref[:]

    acc_t = jnp.zeros((b, w), jnp.uint32)
    acc_e = jnp.zeros((b, w), jnp.uint32)

    for k in range(k_dim):
        o, rk = offsets[k], revs[k]
        base = b + o
        fwd_s = hp3[base : base + b, 0:w]
        mcw_s = hp3[base : base + b, w : 2 * w]
        carry_k = carry3[base : base + b, rk * w : (rk + 1) * w]
        echo_k = fe3[base : base + b, rk * w : (rk + 1) * w]

        f = flags[:, k : k + 1]
        live = _bit(f, F_LIVE)
        live_g = _gate(live)
        accmsg_g = _gate(_bit(f, F_ACC_MSG))
        sfo_g = _gate(_bit(f, F_SENDER_FWD))

        if score_enabled:
            s_k = nbrsc[:, k : k + 1]
            recv_ok = s_k >= thr_ref[0, 1]
        else:
            recv_ok = live
        flood = _gate(_bit(f, F_FLOOD_FROM)) | (
            _gate(_bit(f, F_I_AM_FLOODSUB)) & _gate(recv_ok)
        )
        emask = (carry_k | flood) & accmsg_g & joined
        t_k = fwd_s & ~echo_k & emask & live_g & sfo_g & not_mine

        # IWANT service (requests I sent last round; the neighbor serves
        # from its full mcache window, capped per (edge, msg))
        asked_k = asked[:, k * w : (k + 1) * w]
        slo_k = slo[:, k * w : (k + 1) * w]
        shi_k = shi[:, k * w : (k + 1) * w]
        capped = served_capped_mask(retrans_cap, slo_k, shi_k)
        resp = asked_k & mcw_s & ~capped & live_g
        if score_enabled:
            resp = resp & _gate(s_k >= thr_ref[0, 0])
        sat = shi_k & slo_k
        inc = resp & ~sat
        cy = slo_k & inc
        slo_out[:, k * w : (k + 1) * w] = slo_k ^ inc
        shi_out[:, k * w : (k + 1) * w] = shi_k | cy

        extra_k = resp & accmsg_g & sfo_g & not_mine
        all_k = t_k | extra_k
        trans_out[:, k * w : (k + 1) * w] = all_k
        if want_cohorts:
            mesh_t_out[:, k * w : (k + 1) * w] = t_k
            extra_out[:, k * w : (k + 1) * w] = extra_k

        # first-arrival chains: mesh-push arrivals take precedence over
        # IWANT responses (delivery_round then merge_extra_tx ordering);
        # within each cohort, lowest edge slot wins
        ft_scr[:, k * w : (k + 1) * w] = t_k & ~acc_t
        acc_t = acc_t | t_k
        fe_scr[:, k * w : (k + 1) * w] = extra_k & ~acc_e
        acc_e = acc_e | extra_k

    new_t = acc_t & ~have
    new_e = acc_e & ~(have | new_t)
    new = new_t | new_e
    have2 = have | new
    valid = valid_ref[:]
    peer_out[:, 0:w] = new
    peer_out[:, w : 2 * w] = have2
    peer_out[:, 2 * w : 3 * w] = new & valid

    for k in range(k_dim):
        fe_old = fe_0[:, k * w : (k + 1) * w]
        fe_out[:, k * w : (k + 1) * w] = (
            (fe_old & ~new)
            | (ft_scr[:, k * w : (k + 1) * w] & new_t)
            | (fe_scr[:, k * w : (k + 1) * w] & new_e)
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "block", "offsets", "revs", "w", "score_enabled", "want_cohorts",
        "retrans_cap", "interpret",
    ),
)
def fused_delivery(
    carry_out,   # [N, K*W] u32 — sender per-edge push outbox (post-graft)
    fe_words,    # [N, K*W] u32
    fwd,         # [N, W] u32
    mcache_win,  # [N, W] u32 — OR of the full mcache history window
    nbr_score,   # [N, K] f32 (edge_exchange output) or None
    asked,       # [N, K*W] u32 — iwant_out
    served_lo,   # [N, K*W] u32
    served_hi,   # [N, K*W] u32
    flags,       # [N, K] u32 — make_flags
    have,        # [N, W] u32
    origin_w,    # [N, W] u32
    joined_w,    # [N, W] u32
    valid_row,   # [1, W] u32
    gossip_thr=0.0, publish_thr=0.0,
    *, block, offsets, revs, w, score_enabled, want_cohorts,
    retrans_cap, interpret=False,
):
    """The full delivery plane of one round. Returns a dict with trans,
    fe, served_lo, served_hi, new, have, fwd (all post-round), plus
    mesh_trans/extra cohorts when want_cohorts (event accounting needs
    per-cohort popcounts to match the XLA path's split counters)."""
    # the kernel's carry/fe/hp block-neighbor views are one coalesced
    # halo-exchange set (see edge_exchange's tally note)
    _edges._tally("edge")
    n = fwd.shape[0]
    b = block
    nb = n // b
    k_dim = len(offsets)
    kw = k_dim * w
    soff = signed_offsets(offsets, n)

    def spec(cols, f):
        return pl.BlockSpec((b, cols), f, memory_space=pltpu.VMEM)

    i0 = lambda i: (i, 0)
    im1 = lambda i: ((i - 1) % nb, 0)
    ip1 = lambda i: ((i + 1) % nb, 0)

    hp = jnp.concatenate([fwd, mcache_win], axis=-1)  # [N, 2W]

    in_specs = [
        spec(kw, im1), spec(kw, i0), spec(kw, ip1),          # carry
        spec(kw, im1), spec(kw, i0), spec(kw, ip1),          # fe
        spec(2 * w, im1), spec(2 * w, i0), spec(2 * w, ip1),  # hp
    ]
    args = [
        carry_out, carry_out, carry_out,
        fe_words, fe_words, fe_words,
        hp, hp, hp,
    ]
    if score_enabled:
        in_specs.append(spec(k_dim, i0))
        args.append(nbr_score)
    in_specs += [
        spec(kw, i0), spec(kw, i0), spec(kw, i0),  # asked, slo, shi
        spec(k_dim, i0),                            # flags
        spec(w, i0), spec(w, i0), spec(w, i0),      # have, origin, joined
        pl.BlockSpec((1, w), lambda i: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 2), lambda i: (0, 0), memory_space=pltpu.VMEM),
    ]
    # thresholds ride as a TRACED [1, 2] f32 row (gossip, publish) —
    # round 21 closes the float(threshold) SHAPE seam that excluded this
    # kernel from lifted ScoreParams builds (LIFT_AUDIT round 16): a
    # lifted plane's traced thresholds now reach the kernel as values,
    # so one compile serves every weight set here too
    thr_row = jnp.stack([
        jnp.asarray(gossip_thr, jnp.float32),
        jnp.asarray(publish_thr, jnp.float32),
    ]).reshape(1, 2)
    args += [asked, served_lo, served_hi, flags, have, origin_w, joined_w,
             valid_row, thr_row]

    out_specs = [
        spec(kw, i0),   # trans
        spec(kw, i0),   # fe'
        spec(kw, i0),   # served_lo'
        spec(kw, i0),   # served_hi'
        spec(3 * w, i0),  # peer: new | have' | fwd'
    ]
    out_shape = [
        jax.ShapeDtypeStruct((n, kw), jnp.uint32),
        jax.ShapeDtypeStruct((n, kw), jnp.uint32),
        jax.ShapeDtypeStruct((n, kw), jnp.uint32),
        jax.ShapeDtypeStruct((n, kw), jnp.uint32),
        jax.ShapeDtypeStruct((n, 3 * w), jnp.uint32),
    ]
    if want_cohorts:
        out_specs += [spec(kw, i0), spec(kw, i0)]
        out_shape += [
            jax.ShapeDtypeStruct((n, kw), jnp.uint32),
            jax.ShapeDtypeStruct((n, kw), jnp.uint32),
        ]

    outs = pl.pallas_call(
        functools.partial(
            _delivery_kernel, b=b, k_dim=k_dim, w=w, offsets=soff,
            revs=revs, score_enabled=score_enabled,
            want_cohorts=want_cohorts, retrans_cap=retrans_cap,
        ),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((b, kw), jnp.uint32),
            pltpu.VMEM((b, kw), jnp.uint32),
        ],
        interpret=interpret,
    )(*args)

    res = {
        "trans": outs[0],
        "fe": outs[1],
        "served_lo": outs[2],
        "served_hi": outs[3],
        "new": outs[4][:, 0:w],
        "have": outs[4][:, w : 2 * w],
        "fwd": outs[4][:, 2 * w : 3 * w],
    }
    if want_cohorts:
        res["mesh_trans"] = outs[5]
        res["extra"] = outs[6]
    return res
