"""Peer gater — random-early-drop admission control (peer_gater.go).

When the validation pipeline is overloaded (throttled/validated ratio above
threshold, peer_gater.go:320-363), incoming *messages* from a peer are
accepted with probability (1 + deliver) / (1 + weighted total of its
delivery outcomes); control traffic still flows (AcceptControl).

Vector form: per-edge outcome counters [N,K] with per-source-IP sharing
(stats are aggregated over edges whose far end shares an ip-group —
peer_gater.go:133-137 keys stats by source IP) and a per-peer global
validate/throttle pair. One bernoulli draw per edge per round.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import struct

from ..config import PeerGaterParams
from ..perf import stages
from ..state import Net


@struct.dataclass
class GaterState:
    validate: jax.Array       # [N] f32 — messages entering validation
    throttle: jax.Array       # [N] f32 — throttle events
    last_throttle: jax.Array  # [N] i32 tick (-inf when never)
    deliver: jax.Array        # [N,K] f32 per-edge outcome counters
    duplicate: jax.Array      # [N,K] f32
    ignore: jax.Array         # [N,K] f32
    reject: jax.Array         # [N,K] f32

    @classmethod
    def empty(cls, n: int, k: int) -> "GaterState":
        z = lambda: jnp.zeros((n, k), jnp.float32)
        return cls(
            validate=jnp.zeros((n,), jnp.float32),
            throttle=jnp.zeros((n,), jnp.float32),
            last_throttle=jnp.full((n,), -(2**30), jnp.int32),
            deliver=z(), duplicate=z(), ignore=z(), reject=z(),
        )


def same_source_matrix(net: Net) -> jax.Array:
    """[N,K,K] f32: neighbors k and k' share a source ip-group (static
    topology => precompute once). Used to share outcome stats per source IP
    (peer_gater.go:261-278)."""
    groups = net.peer_gather(net.ip_group)  # [N,K]
    same = (groups[:, :, None] == groups[:, None, :]) & net.nbr_ok[:, None, :] & net.nbr_ok[:, :, None]
    return same.astype(jnp.float32)


@stages.part("gater")
def gater_decay(gs: GaterState, params: PeerGaterParams) -> GaterState:
    """Per-decay-interval counter decay (peer_gater.go:219-259)."""
    dtz = params.decay_to_zero

    def dec(x, d):
        y = x * d
        return jnp.where(y < dtz, 0.0, y)

    return gs.replace(
        validate=dec(gs.validate, params.global_decay),
        throttle=dec(gs.throttle, params.global_decay),
        deliver=dec(gs.deliver, params.source_decay),
        duplicate=dec(gs.duplicate, params.source_decay),
        ignore=dec(gs.ignore, params.source_decay),
        reject=dec(gs.reject, params.source_decay),
    )


@stages.part("gater")
def gater_accept(
    gs: GaterState,
    net: Net,
    params: PeerGaterParams,
    quiet_ticks: int,
    tick,
    key: jax.Array,
) -> jax.Array:
    """[N,K] bool: True = AcceptAll, False = AcceptControl (drop messages)
    for this round (peer_gater.go:320-363)."""
    # circuit breaker off: quiet period elapsed, no throttle pressure, or
    # ratio below threshold
    calm = (tick - gs.last_throttle) > quiet_ticks
    calm = calm | (gs.throttle == 0.0)
    calm = calm | ((gs.validate != 0.0) & (gs.throttle / jnp.maximum(gs.validate, 1e-9) < params.threshold))

    # per-source shared outcome totals (stats keyed by source ip-group,
    # peer_gater.go:261-278); the [N,K,K] compare is built in-place and
    # fused into the contraction
    groups = net.peer_gather(net.ip_group)  # [N,K]
    same = (
        (groups[:, :, None] == groups[:, None, :])
        & net.nbr_ok[:, None, :]
        & net.nbr_ok[:, :, None]
    ).astype(jnp.float32)

    def share(x):
        return jnp.einsum("nkj,nj->nk", same, x)

    deliver = share(gs.deliver)
    total = (
        deliver
        + params.duplicate_weight * share(gs.duplicate)
        + params.ignore_weight * share(gs.ignore)
        + params.reject_weight * share(gs.reject)
    )
    p = (1.0 + deliver) / (1.0 + total)
    u = jax.random.uniform(key, p.shape)
    accept = (u < p) | (total == 0.0)
    return calm[:, None] | accept


@stages.part("gater")
def gater_on_round(
    gs: GaterState,
    n_validated: jax.Array,   # [N] i32 — receipts entering validation
    n_throttled: jax.Array,   # [N] i32 — receipts refused (queue full)
    deliver_inc: jax.Array,   # [N,K] f32 — first deliveries per edge
    duplicate_inc: jax.Array, # [N,K] f32
    reject_inc: jax.Array,    # [N,K] f32 — rejected-message deliveries
    tick,
    ignore_inc: jax.Array | None = None,  # [N,K] f32 — ValidationIgnore
                                          # verdicts (peer_gater.go:427-429)
) -> GaterState:
    """Fold a round's validation outcomes into the counters (the RawTracer
    hooks, peer_gater.go:365-443)."""
    throttled_any = n_throttled > 0
    return gs.replace(
        validate=gs.validate + n_validated.astype(jnp.float32),
        throttle=gs.throttle + n_throttled.astype(jnp.float32),
        last_throttle=jnp.where(throttled_any, tick, gs.last_throttle),
        deliver=gs.deliver + deliver_inc,
        duplicate=gs.duplicate + duplicate_inc,
        reject=gs.reject + reject_inc,
        ignore=gs.ignore if ignore_inc is None else gs.ignore + ignore_inc,
    )
