"""Sparse data plane (round 15): capacity-bounded CSR edge exchange.

Pins the docs/DESIGN.md §15 contracts:

  * the CSR kernels (ops/csr.py) are exact: the flat involution is an
    involution, pack/unpack round-trips, and both segment-reduction
    forms (segmented scan, segment_sum) equal their dense word-algebra
    counterparts;
  * dense-vs-CSR engine parity is BIT-EXACT for all four engines —
    full state trees, ragged AND banded topologies, chaos masks on,
    ensemble S>1, scanned windows — because the layout only changes
    HOW the exchange is computed, never what. Since round 18 the csr
    build carries the CSR-RESIDENT state tier (fe_words/served_* as
    [E, W], peerhave/iasked as [E] — docs/DESIGN.md §18), so parity
    compares under state.densify_edge_planes (exact: dense per-edge
    planes are zero on absent slots by construction);
  * the layout touches the state tree ONLY through that sanctioned
    tier: checkpoint v6 round-trips a CSR-run tree with no version
    bump, and the guards' csr row matches the committed gossipsub
    schema under the derived csr_variant_rows transformation;
  * the narrowing contract: ``narrow_counters`` stores the IHAVE
    flood-protection counters as int16 with bit-identical VALUES
    (exact by range analysis), and build() refuses configs whose caps
    don't fit;
  * the N-scaling projection (perf.projection.project_at_scale)
    reproduces the committed shard table at its anchor points and
    prices the memory term.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import pytest

from go_libp2p_pubsub_tpu import checkpoint, driver, graph
from go_libp2p_pubsub_tpu import topo as topo_mod
from go_libp2p_pubsub_tpu.chaos.faults import ChaosConfig
from go_libp2p_pubsub_tpu.config import (
    GossipSubParams,
    PeerScoreThresholds,
    default_peer_score_params,
)
from go_libp2p_pubsub_tpu.models import floodsub
from go_libp2p_pubsub_tpu.models.gossipsub import (
    GossipSubConfig,
    GossipSubState,
    make_gossipsub_step,
)
from go_libp2p_pubsub_tpu.models.gossipsub_phase import make_gossipsub_phase_step
from go_libp2p_pubsub_tpu.models.randomsub import make_randomsub_step
from go_libp2p_pubsub_tpu.ops import bitset
from go_libp2p_pubsub_tpu.ops import csr as csrops
from go_libp2p_pubsub_tpu.state import (
    Net,
    SimState,
    densify_edge_planes,
)

N = 96
M = 32
PUBW = 3

CHAOS = ChaosConfig(generator="iid", loss_rate=0.3)


def ragged_topo(n=N, d=4, seed=2):
    """random_connect pads uneven degrees — real absent slots."""
    return graph.random_connect(n, d=d, seed=seed)


def canon(net, st, batched=False):
    """Canonicalize a state for dense-vs-csr comparison: densify the
    CSR-resident planes (a no-op on dense builds)."""
    if net.edge_layout != "csr":
        return st
    if batched:
        return jax.vmap(lambda s: densify_edge_planes(net, s))(st)
    return densify_edge_planes(net, st)


def assert_trees_equal(a, b, tag=""):
    la = jtu.tree_flatten_with_path(a)[0]
    lb = jtu.tree_flatten_with_path(b)[0]
    assert len(la) == len(lb), f"{tag}: leaf count differs"
    for (p, x), (_, y) in zip(la, lb):
        if hasattr(x, "dtype") and "key" in str(x.dtype):
            x, y = jax.random.key_data(x), jax.random.key_data(y)
        assert np.asarray(x).dtype == np.asarray(y).dtype, (
            f"{tag}: dtype differs at {jtu.keystr(p)}")
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"{tag}: mismatch at {jtu.keystr(p)}")


def publish_schedule(rounds, n=N, seed=0):
    rng = np.random.default_rng(seed)
    po = rng.integers(-1, n, size=(rounds, PUBW)).astype(np.int32)
    pt = np.zeros((rounds, PUBW), np.int32)
    pv = np.ones((rounds, PUBW), bool)
    return jnp.asarray(po), jnp.asarray(pt), jnp.asarray(pv)


# ---------------------------------------------------------------------------
# kernel exactness


def test_build_csr_structure():
    topo = ragged_topo()
    ct = csrops.build_csr(topo.nbr, topo.rev, topo.nbr_ok)
    e = ct.n_edges
    assert e == int(topo.nbr_ok.sum())
    assert 0 < ct.density <= 1.0
    # flat involution is an involution with no fixed points (no self
    # edges) and maps each edge to its reverse endpoint pair
    assert (ct.eperm[ct.eperm] == np.arange(e)).all()
    assert (ct.eperm != np.arange(e)).all()
    assert (ct.row[ct.eperm] == ct.col).all()
    assert (ct.col[ct.eperm] == ct.row).all()
    # row spans cover the edges in sorted owner order
    assert (np.diff(ct.row) >= 0).all()
    assert ct.row_ptr[-1] == e
    counts = np.bincount(ct.row, minlength=ct.n_peers)
    assert (np.diff(ct.row_ptr) == counts).all()


def test_build_csr_rejects_asymmetry():
    topo = ragged_topo()
    nbr_ok = topo.nbr_ok.copy()
    i, k = np.argwhere(nbr_ok)[0]
    nbr_ok[i, k] = False  # drop one direction only
    j, rk = topo.nbr[i, k], topo.rev[i, k]
    assert nbr_ok[j, rk]
    with pytest.raises(ValueError, match="not symmetric"):
        csrops.build_csr(topo.nbr, topo.rev, nbr_ok)


def test_pack_unpack_roundtrip_and_gather_parity():
    topo = ragged_topo()
    subs = graph.subscribe_all(N, 1)
    net_d = Net.build(topo, subs)
    net_c = Net.build(topo, subs, edge_layout="csr")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 2 ** 32, size=(N, topo.max_degree, 2),
                                 dtype=np.uint32))
    v = jnp.asarray(rng.integers(0, 2 ** 32, size=(N,), dtype=np.uint32))
    # pack -> unpack restores present slots, zeros absent ones
    back = net_c.unpack_edges(net_c.pack_edges(x))
    ok3 = jnp.asarray(topo.nbr_ok)[:, :, None]
    np.testing.assert_array_equal(
        np.asarray(back), np.asarray(jnp.where(ok3, x, jnp.uint32(0))))
    # the two layouts' gathers are bit-identical INCLUDING the junk
    # convention on absent slots (self-pointing / v[0])
    np.testing.assert_array_equal(
        np.asarray(net_d.edge_gather(x)), np.asarray(net_c.edge_gather(x)))
    np.testing.assert_array_equal(
        np.asarray(net_d.peer_gather(v)), np.asarray(net_c.peer_gather(v)))


def test_segment_reductions_match_dense():
    topo = ragged_topo()
    ct = csrops.build_csr(topo.nbr, topo.rev, topo.nbr_ok)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(0, 2 ** 32, size=(N, topo.max_degree, 2),
                                 dtype=np.uint32))
    ok3 = jnp.asarray(topo.nbr_ok)[:, :, None]
    x_masked = jnp.where(ok3, x, jnp.uint32(0))
    xe = csrops.pack_edges(x, jnp.asarray(ct.e2nk), topo.max_degree)

    # segmented-scan OR == dense word_or_reduce
    got = csrops.segment_or_words(
        xe, jnp.asarray(ct.seg_start), jnp.asarray(ct.row_last),
        jnp.asarray(ct.row_nonempty))
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(bitset.word_or_reduce(x_masked, axis=1)))

    # exclusive scan isolates first-per-bit == bitset.first_set_per_bit
    _inc, exc = csrops.segment_or_scan(xe, jnp.asarray(ct.seg_start))
    fa_flat = csrops.unpack_edges(xe & ~exc, jnp.asarray(ct.e_of_nk))
    fa_dense = jnp.where(
        ok3, bitset.first_set_per_bit(x_masked, axis=1), jnp.uint32(0))
    np.testing.assert_array_equal(np.asarray(fa_flat), np.asarray(fa_dense))

    # segment_sum == masked dense sum; popcount likewise
    vals = jnp.asarray(rng.normal(size=ct.n_edges).astype(np.float32))
    dense_sum = np.zeros(N, np.float32)
    np.add.at(dense_sum, ct.row, np.asarray(vals))
    np.testing.assert_allclose(
        np.asarray(csrops.segment_sum_edges(vals, jnp.asarray(ct.row), N)),
        dense_sum, rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(csrops.segment_popcount(xe, jnp.asarray(ct.row), N)),
        np.asarray(bitset.popcount(x_masked, axis=None).sum(axis=-1)))


# ---------------------------------------------------------------------------
# engine parity, dense vs csr (bit-exact, chaos on)


def _run_floodsub(net, rounds=6):
    po, pt, pv = publish_schedule(rounds)
    # n_edges=net.n_edges allocates the CSR-RESIDENT flat fe plane on a
    # csr net (None on dense — the same call covers both layouts)
    st = SimState.init(N, M, k=net.max_degree, n_edges=net.n_edges)
    for i in range(rounds):
        st = floodsub.floodsub_step(net, st, po[i], pt[i], pv[i],
                                    chaos=CHAOS)
    return canon(net, st)


def powerlaw_topo(n=N):
    """A heavy-tailed degree draw: most rows far under the K=16 cap."""
    el = topo_mod.powerlaw(n, exponent=2.2, d_min=2, max_degree=16, seed=0)
    return topo_mod.build_nets(el, graph.subscribe_all(n, 1),
                               max_degree=16)[0]


@pytest.mark.parametrize("topo_kind", ["ragged", "banded", "powerlaw"])
def test_floodsub_parity(topo_kind):
    topo = {"ragged": ragged_topo,
            "banded": lambda: graph.ring_lattice(N, d=4),
            "powerlaw": powerlaw_topo}[topo_kind]()
    subs = graph.subscribe_all(N, 1)
    net_d = Net.build(topo, subs)
    net_c = Net.build(topo, subs, edge_layout="csr")
    if topo_kind == "banded":
        assert net_d.band_off is not None and net_c.band_off is None
    assert_trees_equal(_run_floodsub(net_d), _run_floodsub(net_c),
                       f"floodsub/{topo_kind}")


def test_randomsub_parity():
    topo = ragged_topo()
    subs = graph.subscribe_all(N, 1)
    po, pt, pv = publish_schedule(6)

    def run(layout):
        net = Net.build(topo, subs, edge_layout=layout)
        step = make_randomsub_step(net, chaos=CHAOS)
        st = SimState.init(N, M, k=net.max_degree, n_edges=net.n_edges)
        for i in range(6):
            st = step(st, po[i], pt[i], pv[i])
        return canon(net, st)

    assert_trees_equal(run("dense"), run("csr"), "randomsub")


def _gossip_cfg(layout, **kw):
    return GossipSubConfig.build(
        GossipSubParams(), PeerScoreThresholds(), score_enabled=True,
        chaos=CHAOS, edge_layout=layout, **kw)


def test_gossipsub_parity():
    topo = ragged_topo()
    subs = graph.subscribe_all(N, 1)
    sp = default_peer_score_params(1)
    po, pt, pv = publish_schedule(8)

    def run(layout):
        net = Net.build(topo, subs, edge_layout=layout)
        cfg = _gossip_cfg(layout)
        st = GossipSubState.init(net, M, cfg, score_params=sp, seed=0)
        step = make_gossipsub_step(cfg, net, score_params=sp)
        for i in range(8):
            st = step(st, po[i], pt[i], pv[i])
        return canon(net, st)

    assert_trees_equal(run("dense"), run("csr"), "gossipsub")


@pytest.mark.parametrize("r", [4, pytest.param(8, marks=pytest.mark.slow)])
def test_gossipsub_phase_parity(r):
    topo = ragged_topo()
    subs = graph.subscribe_all(N, 1)
    sp = default_peer_score_params(1)
    po, pt, pv = publish_schedule(2 * r)

    def run(layout):
        net = Net.build(topo, subs, edge_layout=layout)
        cfg = _gossip_cfg(layout, heartbeat_every=r)
        st = GossipSubState.init(net, M, cfg, score_params=sp, seed=0)
        step = make_gossipsub_phase_step(cfg, net, r, score_params=sp)
        for p in range(2):
            st = step(st, po[p * r:(p + 1) * r], pt[:r], pv[:r],
                      do_heartbeat=True)
        return canon(net, st)

    assert_trees_equal(run("dense"), run("csr"), f"phase r={r}")


@pytest.mark.parametrize("compact", [False, True])
def test_gossipsub_phase_tiered_gather_parity(compact):
    """The phase engine over a tiered edge gather (K0 < K, forced through
    the plan helper, out of the full table or the compact one) leaves the
    same full state tree as the same net with the one full gather
    (K0 = K), after 3 phases with lossy links."""
    from go_libp2p_pubsub_tpu.ops import edges

    r = 4
    topo = ragged_topo()
    subs = graph.subscribe_all(N, 1)
    sp = default_peer_score_params(1)
    po, pt, pv = publish_schedule(3 * r)
    base = Net.build(topo, subs)
    k = base.max_degree
    perm = np.asarray(base.edge_perm)

    def run(tiers):
        net = base.replace(tiers=tiers)
        cfg = _gossip_cfg("dense", heartbeat_every=r)
        st = GossipSubState.init(net, M, cfg, score_params=sp, seed=0)
        step = make_gossipsub_phase_step(cfg, net, r, score_params=sp)
        for p in range(3):
            st = step(st, po[p * r:(p + 1) * r], pt[:r], pv[:r],
                      do_heartbeat=True)
        return st

    full = run(None)
    assert int(full.core.tick) == 3 * r
    for k0 in (k // 2, 2):
        tiers = edges.plan_tiers(perm, topo.nbr_ok, k0, compact=compact)
        assert tiers.head.shape[0] == k0 < k and tiers.tail_dst.size
        assert tiers.compact is compact
        assert_trees_equal(full, run(tiers), f"phase tiers K0={k0}")


@pytest.mark.parametrize("compact", [False, True])
def test_gossipsub_phase_sliced_control_head_parity(compact):
    """A scored phase window whose control head's stacked plane (12 words
    at 128 message slots: two tiles) crosses in column slices, the cliff
    forced down to its table's size, leaves the same full state tree as
    the same plan crossing it whole: the slicing does not reach
    ``control_unpack``'s word offsets."""
    from go_libp2p_pubsub_tpu.ops import edges

    r, m = 4, 128
    topo = ragged_topo()
    subs = graph.subscribe_all(N, 1)
    sp = default_peer_score_params(1)
    po, pt, pv = publish_schedule(3 * r)
    base = Net.build(topo, subs)
    k = base.max_degree
    whole = edges.plan_tiers(np.asarray(base.edge_perm), topo.nbr_ok, k // 2,
                             compact=compact)

    def run(tiers):
        net = base.replace(tiers=tiers)
        cfg = _gossip_cfg("dense", heartbeat_every=r)
        st = GossipSubState.init(net, m, cfg, score_params=sp, seed=0)
        step = make_gossipsub_phase_step(cfg, net, r, score_params=sp)
        tally: list = []
        with edges.tally_index_rows(tally):
            for p in range(3):
                st = step(st, po[p * r:(p + 1) * r], pt[:r], pv[:r],
                          do_heartbeat=True)
        return st, sum(v for kind, v in tally if kind == "sliced")

    full, none_sliced = run(whole)
    got, one_sliced = run(whole.replace(cliff=whole.table_rows(k)))
    assert int(full.core.tick) == 3 * r
    # the one trace of the step: the control head's call, no sub-round's
    assert (none_sliced, one_sliced) == (0, 1)
    assert_trees_equal(full, got, f"phase sliced compact={compact}")


def test_scanned_window_parity():
    """driver.make_scan over a CSR step == the dense python loop — the
    scanned window carries the sparse exchange inside one program."""
    topo = ragged_topo()
    subs = graph.subscribe_all(N, 1)
    sp = default_peer_score_params(1)
    rounds = 8
    po, pt, pv = publish_schedule(rounds)

    net_d = Net.build(topo, subs)
    cfg_d = _gossip_cfg("dense")
    st = GossipSubState.init(net_d, M, cfg_d, score_params=sp, seed=0)
    step_d = make_gossipsub_step(cfg_d, net_d, score_params=sp)
    for i in range(rounds):
        st = step_d(st, po[i], pt[i], pv[i])

    net_c = Net.build(topo, subs, edge_layout="csr")
    cfg_c = _gossip_cfg("csr")
    stc = GossipSubState.init(net_c, M, cfg_c, score_params=sp, seed=0)
    scan = driver.make_scan(
        make_gossipsub_step(cfg_c, net_c, score_params=sp),
        heartbeat_every=1, rounds_per_phase=1, static_heartbeat=False)
    stc = scan(stc, po, pt, pv)
    assert_trees_equal(st, canon(net_c, stc),
                       "scanned csr window vs dense loop")


def test_ensemble_parity_s3():
    """S=3 vmapped CSR ensemble == vmapped dense ensemble, bit-exact
    (threefry — the parity-gate PRNG — vmaps elementwise)."""
    from go_libp2p_pubsub_tpu.ensemble import batch as ebatch

    topo = ragged_topo()
    subs = graph.subscribe_all(N, 1)
    sp = default_peer_score_params(1)
    s_dim = 3
    rounds = 6
    po, pt, pv = publish_schedule(rounds)

    def run(layout):
        net = Net.build(topo, subs, edge_layout=layout)
        cfg = _gossip_cfg(layout)
        st = GossipSubState.init(net, M, cfg, score_params=sp, seed=0)
        states = ebatch.batch_states(st, s_dim)
        ens = ebatch.lift_step(make_gossipsub_step(cfg, net, score_params=sp))
        for i in range(rounds):
            states = ens(states, ebatch.tile(po[i], s_dim),
                         ebatch.tile(pt[i], s_dim), ebatch.tile(pv[i], s_dim))
        return canon(net, states, batched=True)

    assert_trees_equal(run("dense"), run("csr"), "ensemble S=3")


def test_checkpoint_v6_roundtrip_csr(tmp_path):
    """A CSR-run state tree checkpoints and restores with NO version
    bump (the layout lives in the Net, never the state), and the
    resumed run continues bit-identical to the uninterrupted one."""
    topo = ragged_topo()
    subs = graph.subscribe_all(N, 1)
    sp = default_peer_score_params(1)
    po, pt, pv = publish_schedule(8)
    net = Net.build(topo, subs, edge_layout="csr")
    cfg = _gossip_cfg("csr")
    step = make_gossipsub_step(cfg, net, score_params=sp)

    st = GossipSubState.init(net, M, cfg, score_params=sp, seed=0)
    for i in range(4):
        st = step(st, po[i], pt[i], pv[i])
    path = str(tmp_path / "csr_mid.ckpt")
    checkpoint.save(path, st)
    template = GossipSubState.init(net, M, cfg, score_params=sp, seed=0)
    restored = checkpoint.restore(path, template)
    assert_trees_equal(st, restored, "checkpoint restore")

    resumed = restored
    for i in range(4, 8):
        resumed = step(resumed, po[i], pt[i], pv[i])
    uninterrupted = GossipSubState.init(net, M, cfg, score_params=sp, seed=0)
    for i in range(8):
        uninterrupted = step(uninterrupted, po[i], pt[i], pv[i])
    assert_trees_equal(uninterrupted, resumed, "resume == uninterrupted")


# ---------------------------------------------------------------------------
# narrowing contract


def test_narrow_counters_value_exact():
    topo = ragged_topo()
    subs = graph.subscribe_all(N, 1)
    sp = default_peer_score_params(1)
    po, pt, pv = publish_schedule(8)

    def run(narrow):
        net = Net.build(topo, subs)
        cfg = GossipSubConfig.build(
            GossipSubParams(), PeerScoreThresholds(), score_enabled=True,
            narrow_counters=narrow)
        st = GossipSubState.init(net, M, cfg, score_params=sp, seed=0)
        step = make_gossipsub_step(cfg, net, score_params=sp)
        for i in range(8):
            st = step(st, po[i], pt[i], pv[i])
        return st

    wide, narrow = run(False), run(True)
    assert narrow.peerhave.dtype == jnp.int16
    assert narrow.iasked.dtype == jnp.int16
    np.testing.assert_array_equal(
        np.asarray(wide.peerhave),
        np.asarray(narrow.peerhave).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(wide.iasked), np.asarray(narrow.iasked).astype(np.int32))
    # every OTHER leaf bit-identical — the narrowing never leaks
    np.testing.assert_array_equal(np.asarray(wide.scores),
                                  np.asarray(narrow.scores))
    np.testing.assert_array_equal(np.asarray(wide.core.dlv.have),
                                  np.asarray(narrow.core.dlv.have))


def test_narrow_counters_rejects_oversized_cap():
    with pytest.raises(ValueError, match="max_ihave_length"):
        GossipSubConfig.build(
            dataclasses.replace(GossipSubParams(), max_ihave_length=2 ** 15),
            narrow_counters=True)
    # peerhave's bound is the heartbeat clear cadence, not the IHAVE
    # message cap — a cadence outside int16 must be refused too
    with pytest.raises(ValueError, match="heartbeat_every"):
        GossipSubConfig.build(
            GossipSubParams(), narrow_counters=True,
            heartbeat_every=2 ** 15)


# ---------------------------------------------------------------------------
# static selection + guards + artifacts


def test_layout_mismatch_rejected():
    topo = ragged_topo()
    subs = graph.subscribe_all(N, 1)
    net = Net.build(topo, subs, edge_layout="csr")
    cfg = GossipSubConfig.build(GossipSubParams(), edge_layout="dense")
    with pytest.raises(ValueError, match="edge_layout"):
        make_gossipsub_step(cfg, net)
    with pytest.raises(ValueError, match="edge_layout"):
        Net.build(topo, subs, edge_layout="coo")
    with pytest.raises(ValueError, match="edge_layout"):
        GossipSubConfig.build(GossipSubParams(), edge_layout="coo")


def test_dense_build_has_no_csr_leaves():
    """The dense path's Net tree is unchanged — the elision-when-off
    face of the layout (the HLO census gates pin the program side)."""
    topo = graph.ring_lattice(N, d=4)
    subs = graph.subscribe_all(N, 1)
    net = Net.build(topo, subs)
    assert net.edge_layout == "dense"
    assert net.csr_col is None and net.csr_eperm is None
    assert net.csr_e2nk is None and net.csr_e_of_nk is None
    assert net.csr_row is None
    assert net.n_edges is None


def test_guards_csr_negative():
    """Seeded negative: the csr guard row must FAIL loudly when the
    committed base rows disagree (schema drift = layout leaked into
    the state tree)."""
    from go_libp2p_pubsub_tpu.analysis import guards

    base = guards.load_baseline()
    assert base is not None, "STATE_SCHEMA.json missing"
    rows = [dict(r) for r in base["engines"]["gossipsub"]["leaves"]]
    h = guards.build_csr_harness()
    out_tree = guards.strict_trace(h)
    # positive: match against the committed rows (check_schema_csr
    # applies the round-18 csr_variant_rows transformation itself)
    guards.check_schema_csr(h, out_tree, rows)
    # negative: corrupt one committed dtype
    rows[0] = {**rows[0], "dtype": "int64"}
    with pytest.raises(guards.GuardViolation,
                       match="leaked beyond the resident tier"):
        guards.check_schema_csr(h, out_tree, rows)


def test_simlint_covers_csr_kernels():
    """Seeded negatives: the word-dtype / traced-branch rules police
    ops/csr.py like every other ops module (the repo's own csr.py must
    lint clean — the make-analyze positive covers that)."""
    import textwrap

    from go_libp2p_pubsub_tpu.analysis import simlint

    def lint(src):
        return {v.rule
                for v in simlint.lint_source(textwrap.dedent(src),
                                             "ops/csr.py")}

    assert "word-dtype" in lint("""
        import jax.numpy as jnp
        def segment_or_bad(words_e):
            return words_e & 1
    """)
    assert "traced-branch" in lint("""
        import jax.numpy as jnp
        def unpack_bad(x_e, e_of_nk):
            if jnp.any(e_of_nk < 0):
                return x_e
            return x_e + jnp.uint32(1)
    """)
    assert lint("""
        import jax.numpy as jnp
        def segment_or_ok(words_e):
            return words_e & jnp.uint32(1)
    """) == set()


def test_fingerprint_and_artifact_edge_layout():
    from go_libp2p_pubsub_tpu.perf.artifacts import BenchRecord
    from go_libp2p_pubsub_tpu.perf.sweep import workload_fingerprint

    fp = workload_fingerprint("default", 1000, 64, 1, 1)
    assert fp["engine"]["edge_layout"] == "dense"
    fp_csr = workload_fingerprint("default", 1000, 64, 1, 1,
                                  edge_layout="csr")
    assert fp_csr["engine"]["edge_layout"] == "csr"
    rec = BenchRecord(metric="m", value=1.0, unit="u", vs_baseline=0.1,
                      fingerprint=fp_csr)
    assert rec.edge_layout == "csr"
    legacy = BenchRecord(metric="m", value=1.0, unit="u", vs_baseline=0.1)
    assert legacy.edge_layout == "dense"


# ---------------------------------------------------------------------------
# N-scaling projection


def test_project_at_scale():
    from go_libp2p_pubsub_tpu.perf.projection import (
        ROUND5_SHARD_RATES_R16,
        project,
        project_at_scale,
        shard_ms_at,
    )

    # anchor points reproduce the committed table exactly
    for n, rate in ROUND5_SHARD_RATES_R16.items():
        assert shard_ms_at(n) == pytest.approx(1000.0 / rate)
    # monotone between/beyond anchors
    assert shard_ms_at(125_000) > shard_ms_at(100_000)
    assert shard_ms_at(400_000) > shard_ms_at(200_000)
    # the 100k projection through the scale API == the round-5 path
    base = project(1000.0 / ROUND5_SHARD_RATES_R16[12_500], 16)
    scaled = project_at_scale(100_000)
    assert scaled.shard_n == 12_500
    assert scaled.projection.rounds_per_sec == base.rounds_per_sec
    # memory term: a plainly-too-big bytes/peer fails the HBM gate
    tight = project_at_scale(1_000_000, bytes_per_peer=1e6)
    assert tight.fits_hbm is False
    roomy = project_at_scale(1_000_000, bytes_per_peer=2300.0)
    assert roomy.fits_hbm is True and roomy.hbm_headroom > 1.0


def test_mem_audit_reproduces():
    """The committed MEM_AUDIT.json is pure shape arithmetic and must
    reproduce byte-identical with defaults (the make mem-audit gate)."""
    import json
    import os
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "scripts"))
    import memstat

    with open(memstat.AUDIT_PATH) as f:
        committed = json.load(f)
    assert memstat.build_audit() == committed
