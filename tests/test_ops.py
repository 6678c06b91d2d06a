"""Unit tests for the kernel building blocks (packed bitsets, masked
selection) against numpy oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from go_libp2p_pubsub_tpu.ops import (
    bit_get,
    bit_set,
    count_true,
    make_mask_below,
    median_masked,
    n_words,
    pack,
    popcount,
    rank_desc,
    select_random_mask,
    select_topk_mask,
    unpack,
)


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    bits = rng.random((5, 70)) < 0.3
    words = pack(jnp.asarray(bits))
    assert words.shape == (5, n_words(70))
    out = np.asarray(unpack(words, 70))
    np.testing.assert_array_equal(out, bits)


def test_popcount():
    rng = np.random.default_rng(1)
    bits = rng.random((4, 100)) < 0.5
    words = pack(jnp.asarray(bits))
    np.testing.assert_array_equal(np.asarray(popcount(words)), bits.sum(axis=1))


def test_bit_get_set():
    bits = np.zeros((3, 64), dtype=bool)
    words = pack(jnp.asarray(bits))
    idx = jnp.asarray([5, 33, 63])
    on = jnp.asarray([True, False, True])
    words2 = bit_set(words, idx, on)
    got = np.asarray(bit_get(words2, idx))
    np.testing.assert_array_equal(got, [True, False, True])
    # untouched bits stay zero
    assert int(popcount(words2).sum()) == 2


def test_make_mask_below():
    m = make_mask_below(jnp.int32(40), 64)
    bits = np.asarray(unpack(m, 64))
    np.testing.assert_array_equal(bits, np.arange(64) < 40)


def test_rank_desc_basic():
    v = jnp.asarray([[3.0, 1.0, 2.0, 9.0]])
    mask = jnp.asarray([[True, True, True, False]])
    r = np.asarray(rank_desc(v, mask))
    # 3.0 is rank 0, 2.0 rank 1, 1.0 rank 2; masked-out 9.0 last
    np.testing.assert_array_equal(r, [[0, 2, 1, 3]])


def test_select_topk_mask_per_row_k():
    v = jnp.asarray([[5.0, 4.0, 3.0, 2.0], [1.0, 2.0, 3.0, 4.0]])
    mask = jnp.ones((2, 4), dtype=bool)
    k = jnp.asarray([1, 2])
    sel = np.asarray(select_topk_mask(v, mask, k))
    np.testing.assert_array_equal(sel, [[True, False, False, False], [False, False, True, True]])


def test_select_topk_respects_mask_and_short_rows():
    v = jnp.asarray([[5.0, 4.0, 3.0, 2.0]])
    mask = jnp.asarray([[False, True, False, True]])
    sel = np.asarray(select_topk_mask(v, mask, 3))
    # only 2 eligible; both selected, none outside mask
    np.testing.assert_array_equal(sel, [[False, True, False, True]])


def test_select_random_mask_uniformity():
    key = jax.random.key(0)
    mask = jnp.ones((2000, 8), dtype=bool)
    sel = np.asarray(select_random_mask(key, mask, 3))
    assert (sel.sum(axis=1) == 3).all()
    freq = sel.mean(axis=0)
    # each slot picked ~3/8 of the time
    assert np.all(np.abs(freq - 3 / 8) < 0.05)


def test_random_tiebreak_varies():
    key = jax.random.key(1)
    v = jnp.zeros((500, 6))
    mask = jnp.ones((500, 6), dtype=bool)
    keys = jax.random.split(key, 500)
    sel = np.asarray(
        jax.vmap(lambda k, vv, mm: select_topk_mask(vv, mm, 2, key=k))(keys, v, mask)
    )
    freq = sel.mean(axis=0)
    assert np.all(np.abs(freq - 2 / 6) < 0.07)


def test_count_true():
    m = jnp.asarray([[True, False, True]])
    assert int(count_true(m)[0]) == 2


def test_median_masked_upper_median():
    # reference uses plst[len/2] after ascending sort (gossipsub.go:1492)
    v = jnp.asarray([[1.0, 2.0, 3.0, 4.0, 0.0]])
    mask = jnp.asarray([[True, True, True, True, False]])
    # n=4 -> index 2 -> value 3.0 (upper median)
    assert float(median_masked(v, mask)[0]) == 3.0
    # empty mask -> +inf
    assert np.isinf(float(median_masked(v, jnp.zeros((1, 5), bool))[0]))


def test_pytest_env_has_8_devices():
    assert len(jax.devices()) == 8


def test_first_edge_of_matches_scan_incl_k128():
    # slot 127 at K=128 must be reported, not confused with the sentinel
    import numpy as np

    from go_libp2p_pubsub_tpu.ops import bitset

    rng = np.random.default_rng(7)
    for n, k, m in [(4, 16, 40), (3, 128, 33)]:
        w = (m + 31) // 32
        trans = rng.integers(0, 2**32, size=(n, k, w), dtype=np.uint64).astype(np.uint32)
        # zero out invalid high bits
        trans = np.asarray(bitset.pack(bitset.unpack(jnp.asarray(trans), m)))
        got = np.asarray(bitset.first_edge_of(jnp.asarray(trans), m))
        bits = np.asarray(bitset.unpack(jnp.asarray(trans), m))  # [n,k,m]
        want = np.full((n, m), -1, np.int8)
        for kk in range(k - 1, -1, -1):
            want = np.where(bits[:, kk, :], kk, want)
        assert (got == want).all()
    # slot-127-only case
    trans = np.zeros((1, 128, 1), np.uint32)
    trans[0, 127, 0] = 0b1000
    got = np.asarray(bitset.first_edge_of(jnp.asarray(trans), 4))
    assert got[0, 3] == 127 and (got[0, :3] == -1).all()


def test_first_set_per_bit_matches_naive():
    from go_libp2p_pubsub_tpu.ops import bitset

    rng = np.random.default_rng(13)
    for n, k, w in [(5, 16, 2), (3, 7, 1), (2, 1, 3)]:
        words = rng.integers(0, 2**32, size=(n, k, w), dtype=np.uint64).astype(
            np.uint32
        )
        got = np.asarray(bitset.first_set_per_bit(jnp.asarray(words), axis=1))
        # naive: for each bit, keep it only on the lowest k that has it
        seen = np.zeros((n, w), np.uint32)
        want = np.zeros_like(words)
        for kk in range(k):
            want[:, kk] = words[:, kk] & ~seen
            seen |= words[:, kk]
        assert (got == want).all(), (n, k, w)
        # exactly one surviving copy of each present bit
        assert (
            np.asarray(bitset.popcount(jnp.asarray(got), axis=None)).sum()
            == np.asarray(
                bitset.popcount(
                    jnp.asarray(seen), axis=None
                )
            ).sum()
        )


def test_lowest_bit_matches_naive():
    from go_libp2p_pubsub_tpu.ops import bitset

    rng = np.random.default_rng(17)
    words = rng.integers(0, 2**32, size=(64, 3), dtype=np.uint64).astype(np.uint32)
    words[5] = 0  # empty row
    words[6, 0] = 0  # first word empty, later set
    idx, has = bitset.lowest_bit(jnp.asarray(words))
    idx, has = np.asarray(idx), np.asarray(has)
    for i in range(64):
        flat = [w * 32 + b for w in range(3) for b in range(32) if (int(words[i, w]) >> b) & 1]
        if flat:
            assert has[i] and idx[i] == min(flat), i
        else:
            assert not has[i] and idx[i] == 0, i


@pytest.mark.parametrize("wire_coalesced", [True, False])
def test_allocate_publishes_scatter_plane_equivalence(monkeypatch,
                                                      wire_coalesced):
    """The publish stamp has two trace-time forms (plane selects, or
    column/word scatters from state.SCATTER_FORM_MIN_PEERS peers on in
    the phase engine — measured crossover on the real chip, see
    allocate_publishes' docstring). They must be bit-identical; this
    drives a full phase-engine sim under each by moving the crossover,
    through both callers (the head plan's apply_to_delivery on the
    stacked wire, allocate_publishes on the per-plane one), and compares
    every state plane."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from go_libp2p_pubsub_tpu import graph, state
    from go_libp2p_pubsub_tpu.config import (
        GossipSubParams,
        PeerScoreThresholds,
    )
    from go_libp2p_pubsub_tpu.models.gossipsub import (
        GossipSubConfig,
        GossipSubState,
    )
    from go_libp2p_pubsub_tpu.models.gossipsub_phase import (
        make_gossipsub_phase_step,
    )
    from go_libp2p_pubsub_tpu.state import Net

    n, m, r, phases = 48, 32, 2, 5
    topo = graph.random_connect(n, 6, seed=9)
    subs = graph.subscribe_random(n, n_topics=2, topics_per_peer=2, seed=9)
    net = Net.build(topo, subs)
    cfg = GossipSubConfig.build(
        GossipSubParams(), PeerScoreThresholds(), score_enabled=False,
        heartbeat_every=r, wire_coalesced=wire_coalesced,
    )
    rng = np.random.default_rng(9)
    po = rng.integers(-1, n, size=(phases, r, 4)).astype(np.int32)
    pt = rng.integers(0, 2, size=(phases, r, 4)).astype(np.int32)
    pv = np.ones((phases, r, 4), bool)

    def run(min_peers):
        monkeypatch.setattr(state, "SCATTER_FORM_MIN_PEERS", min_peers)
        st = GossipSubState.init(net, m, cfg, seed=9)
        step = make_gossipsub_phase_step(cfg, net, r)
        for i in range(phases):
            st = step(st, jnp.asarray(po[i]), jnp.asarray(pt[i]),
                      jnp.asarray(pv[i]), do_heartbeat=True)
        return st

    sa, sb = run(n + 1), run(0)
    lb, _ = jax.tree_util.tree_flatten(sb)
    paths = jax.tree_util.tree_flatten_with_path(sa)[0]
    for (path, xa), xb in zip(paths, lb):
        if jnp.issubdtype(getattr(xa, "dtype", None), jax.dtypes.prng_key):
            xa, xb = jax.random.key_data(xa), jax.random.key_data(xb)
        assert np.array_equal(np.asarray(xa), np.asarray(xb)), \
            jax.tree_util.keystr(path)


def test_keep_lowest_bits_equals_prefix_cap_bits():
    """The static-cap clear-lowest-bit chain (keep_lowest_bits) must
    match prefix_cap_bits with a full(cap) plane for every cap, shape,
    and bit density — including empty rows, rows with fewer set bits
    than the cap, the >64 fallback, and DIRTY PADDING (m % 32 != 0 with
    the last word's pad bits set: prefix_cap_bits' unpack(m) drops
    pads, so keep_lowest_bits must mask them via its m parameter)."""
    from go_libp2p_pubsub_tpu.ops import bitset

    rng = np.random.default_rng(5)
    for shape, m in (((17,), 64), ((9, 5), 96), ((4, 3), 32), ((7,), 48)):
        for density in (0.0, 0.1, 0.5, 0.95):
            bits = rng.random(shape + (m,)) < density
            words = bitset.pack(jnp.asarray(bits))
            if m % 32 != 0:
                # dirty pads: set bits >= m in the last word
                words = words.at[..., -1].set(
                    words[..., -1] | jnp.uint32(0xFFFF0000)
                )
            for cap in (0, 1, 3, 8, 31, 32, 63, 64, 65, 100, m):
                ref = bitset.prefix_cap_bits(
                    words, jnp.full(shape, cap, jnp.int32), m
                )
                # prefix_cap_bits' output has clean pads by construction;
                # compare on the valid region
                got = bitset.keep_lowest_bits(words, cap, m)
                assert np.array_equal(np.asarray(ref), np.asarray(got)), \
                    (shape, m, density, cap)


def test_masked_keep_matches_per_plane():
    """The round-7 stacked recycled-slot clear == per-plane ANDs, for
    mixed [N,W]/[N,K,W]/[N,V,W] planes, None passthrough, and the
    single-plane fast path."""
    from go_libp2p_pubsub_tpu.ops import bitset

    rng = np.random.default_rng(0)
    n, k, v, w = 5, 3, 2, 4
    keep = jnp.asarray(rng.integers(0, 2**32, size=(w,), dtype=np.uint32))
    a = jnp.asarray(rng.integers(0, 2**32, size=(n, w), dtype=np.uint32))
    b = jnp.asarray(rng.integers(0, 2**32, size=(n, k, w), dtype=np.uint32))
    c = jnp.asarray(rng.integers(0, 2**32, size=(n, v, w), dtype=np.uint32))
    got = bitset.masked_keep([a, None, b, c], keep)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(a & keep[None, :]))
    assert got[1] is None
    np.testing.assert_array_equal(
        np.asarray(got[2]), np.asarray(b & keep[None, None, :]))
    np.testing.assert_array_equal(
        np.asarray(got[3]), np.asarray(c & keep[None, None, :]))
    # single live plane takes the direct path
    (only,) = bitset.masked_keep([b], keep)
    np.testing.assert_array_equal(
        np.asarray(only), np.asarray(b & keep[None, None, :]))
    assert bitset.masked_keep([None, None], keep) == [None, None]
