"""Masked ranking and selection.

Every peer-selection in the reference is one of two shapes:

  * score-ordered keep/drop with random tie-break — the over-subscription
    prune shuffles then stable-sorts by score (gossipsub.go:1389-1399);
  * uniform random-k over an eligibility filter — `getPeers` +
    `shufflePeers` (gossipsub.go:1852-1909), emitGossip target choice
    (gossipsub.go:1697-1708).

Both reduce to `rank_desc`: a dense per-slot descending rank with masked
slots pushed to the end and ties broken by fresh uniform noise. Selecting
"the top k" (k may be a per-row traced array, e.g. ineed = D - |mesh|) is
then just `rank < k`. This keeps all selection kernels O(K log K) sorts over
the padded neighbor axis — XLA-friendly, no data-dependent shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import bitset


def _rank_desc_pairwise(primary: jax.Array, noise: jax.Array) -> jax.Array:
    """O(K^2) pairwise comparison count — the latency-lean form (see
    :func:`rank_desc`)."""
    k = primary.shape[-1]
    idx = jnp.arange(k, dtype=jnp.int32)
    pi, pj = primary[..., :, None], primary[..., None, :]
    ni, nj = noise[..., :, None], noise[..., None, :]
    # strict lexicographic "j outranks i": (p, noise, index) descending
    ties = pj == pi
    nties = nj == ni
    outranks = (pj > pi) | (ties & (nj > ni)) | (ties & nties & (idx[None, :] < idx[:, None]))
    return jnp.sum(outranks, axis=-1).astype(jnp.int32)


def _rank_desc_sorted(primary: jax.Array, noise: jax.Array) -> jax.Array:
    """O(K log K) sort form — the bandwidth-lean fused composite.

    Two ``lax.sort`` calls replace the pairwise form's materialized
    [.., K, K] compare planes (the round-19 cost audit priced those
    intermediates as the single largest hbm_bytes term of the csr
    engine row): a stable 2-key sort on ``(-p, -noise)`` carrying the
    slot index gives the descending order, and a second sort on the
    permutation inverts it back to per-slot ranks. Bit-exact with the
    pairwise count for NaN-free inputs: a stable ascending sort on
    negated keys realizes exactly the strict order "(p, noise, index)
    descending" — stability IS the index tie-break. The one hazard is
    the sort's total order on floats distinguishing -0.0 < +0.0 where
    ``==`` does not; adding +0.0 to the negated keys canonicalizes
    every zero before the compare.
    """
    k = primary.shape[-1]
    idx = jnp.broadcast_to(
        jnp.arange(k, dtype=jnp.int32), noise.shape
    )
    negp = jnp.negative(primary) + 0.0
    negn = jnp.negative(noise.astype(jnp.float32)) + 0.0
    _, _, perm = jax.lax.sort(
        (negp, negn, idx), dimension=-1, num_keys=2, is_stable=True
    )
    # invert the permutation: sorting (perm, iota) by perm puts, at output
    # position p, the sorted-position t with perm[t] == p — i.e. p's rank
    _, rank = jax.lax.sort((perm, idx), dimension=-1, num_keys=1)
    return rank


def rank_desc(values: jax.Array, mask: jax.Array, key: jax.Array | None = None,
              fused: bool = False) -> jax.Array:
    """Dense descending rank along the last axis.

    Returns int32 ranks: the highest masked value gets 0. Unmasked slots get
    ranks after all masked ones. Ties are broken uniformly at random when
    `key` is given (otherwise by slot index), matching the reference's
    shuffle-before-sort idiom (gossipsub.go:1391-1395).

    Two statically-selected forms (``cfg.fused``, round 21 — bit-exact,
    tests/test_fused_composites.py):

      * ``fused=False`` (default): an O(K^2) pairwise comparison count —
        the neighbor axis K is small (<= 64) and padded-static, so the
        [.., K, K] compare lowers to pure vector work on TPU; profiling
        showed the lexsort/argsort formulation dominating the heartbeat
        wall-clock at these shapes. Latency-lean, bandwidth-heavy: the
        compare planes are K× the row data.
      * ``fused=True``: the sort composite (:func:`_rank_desc_sorted`) —
        O(K) bytes per row instead of O(K^2), the form the round-19
        cost audit's hbm_bytes fits select.
    """
    if key is not None:
        noise = jax.random.uniform(key, values.shape)
    else:
        noise = jnp.zeros(values.shape)
    neg = jnp.float32(-jnp.inf)
    primary = jnp.where(mask, values.astype(jnp.float32), neg)
    if fused:
        return _rank_desc_sorted(primary, noise)
    return _rank_desc_pairwise(primary, noise)


def select_topk_mask(
    values: jax.Array, mask: jax.Array, k, key: jax.Array | None = None,
    fused: bool = False,
) -> jax.Array:
    """Bool mask choosing the (up to) k highest masked values per row.

    `k` may be a scalar or an array broadcastable to values.shape[:-1]."""
    ranks = rank_desc(values, mask, key, fused=fused)
    # unconditional trailing broadcast axis: a scalar k becomes shape (1,),
    # which compares against [..., K] ranks identically to the raw scalar.
    # (An `if jnp.ndim(k)` conditional expression here would make the width
    # a SHAPE decision in the liftability audit — this form keeps every
    # degree knob a pure VALUE read, so it can ride a traced plane.)
    k_arr = jnp.asarray(k)[..., None]
    return (ranks < k_arr) & mask


def select_random_mask(key: jax.Array, mask: jax.Array, k,
                       fused: bool = False) -> jax.Array:
    """Bool mask choosing (up to) k uniform-random masked slots per row —
    `getPeers`/`shufflePeers` (gossipsub.go:1852-1909)."""
    noise = jax.random.uniform(key, mask.shape)
    return select_topk_mask(noise, mask, k, fused=fused)


def masked_width_topk(
    values: jax.Array, mask: jax.Array, width, width_max: int,
    key: jax.Array | None = None, fused: bool = False,
) -> jax.Array:
    """Top-k selection at a TRACED width, bounded by a static ceiling.

    The masked-width contract (docs/DESIGN.md §20): the selection kernel
    always ranks the full padded axis (so program shape depends only on
    ``width_max``, the search space's Dhi ceiling), and the candidate's
    actual width arrives as a traced value clipped into [0, width_max].
    At ``width == k`` for any static k <= width_max this is bit-exact
    with ``select_topk_mask(values, mask, k, key)`` — the rank compare
    is the only consumer of the width, and clipping a legal width is the
    identity. This is what lets D/Dlo/Dhi/Dscore/Dout ride the traced
    mesh plane: one compiled program serves every degree profile.
    """
    w = jnp.clip(jnp.asarray(width, jnp.int32), 0, jnp.int32(width_max))
    return select_topk_mask(values, mask, w, key, fused=fused)


def masked_width_random(
    key: jax.Array, mask: jax.Array, width, width_max: int,
    fused: bool = False,
) -> jax.Array:
    """Random-k selection at a traced width bounded by a static ceiling —
    the `select_random_mask` counterpart of :func:`masked_width_topk`."""
    w = jnp.clip(jnp.asarray(width, jnp.int32), 0, jnp.int32(width_max))
    return select_random_mask(key, mask, w, fused=fused)


def count_true(mask: jax.Array, axis: int = -1) -> jax.Array:
    return jnp.sum(mask.astype(jnp.int32), axis=axis)


def median_masked(values: jax.Array, mask: jax.Array) -> jax.Array:
    """Median over masked slots per row, computed as the reference does for
    opportunistic grafting: sort ascending, take element at index
    len(peers)/2 (gossipsub.go:1488-1493) — i.e. the upper median.

    Rows with no masked slots return +inf (so a `median < threshold` guard
    is never triggered for them).
    """
    big = jnp.float32(jnp.inf)
    v = jnp.where(mask, values.astype(jnp.float32), big)
    v_sorted = jnp.sort(v, axis=-1)
    n = count_true(mask)
    idx = jnp.clip(n // 2, 0, values.shape[-1] - 1)
    med = bitset.take_word(v_sorted, idx)
    return jnp.where(n > 0, med, big)
