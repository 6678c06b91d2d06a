"""What the chip's compiler says, asked without the chip: the main
path's phase step is lowered at the bench's real size against a
DESCRIBED ``v5e:2x2`` device (the TPU compiler is installed; nothing is
attached and nothing runs). A compile that passes here is not a chip
run — it guards every later PR against a program the chip would refuse,
at no chip time.

EVERY test that loads the TPU library lives in this one file — these
compiles, and the PJRT bridge tests below that open the same library —
and only inside fixtures/tests (never at import, in a ``skipif`` or in
``parametrize``): every xdist worker imports every test file, the library
belongs to one process at a time, and two files would starve each other
of its lock under workers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from go_libp2p_pubsub_tpu.native import pjrt

BENCH_N = 100_000
BENCH_M = 64
V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever says "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described device is written to the
    # persistent cache but cannot be read back without a chip: the next
    # run would warn and compile again, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def bench_prng():
    old = str(jax.config.jax_default_prng_impl)
    jax.config.update("jax_default_prng_impl", "unsafe_rbg")
    yield
    jax.config.update("jax_default_prng_impl", old)


def _on(sharding, tree):
    """The tree's shapes, placed on the described device."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def test_default_phase_step_compiles_at_bench_size(one_chip, bench_prng):
    """The program bench.py's scanned window is made of: pure XLA,
    accepted by the v5e compiler, inside one chip's HBM."""
    from go_libp2p_pubsub_tpu.perf.sweep import PUBS_PER_ROUND, bench_cell

    r = 8
    cell = bench_cell(BENCH_N, BENCH_M, config="default", heartbeat_every=r,
                      rounds_per_phase=r, devices=jax.devices()[:1])
    pubs = (jnp.zeros((r, PUBS_PER_ROUND), jnp.int32),
            jnp.zeros((r, PUBS_PER_ROUND), jnp.int32),
            jnp.ones((r, PUBS_PER_ROUND), bool))
    compiled = cell.step.lower(
        _on(one_chip, cell.state), *_on(one_chip, pubs), do_heartbeat=True
    ).compile()
    ma = compiled.memory_analysis()
    resident = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.generated_code_size_in_bytes)
    assert resident < V5E_HBM_BYTES, ma
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("words", [5, 13])
def test_tiered_gather_compiles_without_a_relayout_loop(one_chip, words):
    """The planned edge gather at size, composed and consumed as the data
    round does it (planes of a ``[N, K]`` mask and ``[N, W]`` words in, an
    OR over K out), at the sub-rounds' width and the control head's. XLA
    merges ``[K, Np] -> [K*Np]`` as a bitcast only
    because the plan's peer axis is a whole number of lanes; the N-major
    order (and a K-major one over N = 100,000 = 781.25 x 128) went through
    a 1-D ``u32[...]{0:T(1024)}`` buffer, one word an iteration, four
    ``while`` ops in this program and a quarter of the round on the chip
    (PERF.md §6, PR 34). No tier-1 net is large enough to show them.

    The 13 words are two sublane tiles, and the compact table of 2.51 M
    rows read that wide lies beyond the cliff: they cross as TWO gather
    fusions of 8 and 5 words, each out of a table of its own (one fusion
    over both would hold the whole 160 MB again; PERF.md §6, PR 36)."""
    import os
    import sys

    from go_libp2p_pubsub_tpu import graph
    from go_libp2p_pubsub_tpu.ops import edges

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "scripts"))
    import window_whiles

    topo = graph.random_connect(BENCH_N, 10, seed=1)
    tiers = edges.plan_tiers(
        edges.build_edge_perm(topo.nbr, topo.rev, topo.nbr_ok), topo.nbr_ok)
    assert tiers is not None
    assert tiers.head.shape[1] == edges.lane_padded(BENCH_N) == 100_096

    def gathered(mask, payload):
        x = mask[:, :, None] & payload[:, None, :]
        with jax.named_scope("gs.data_round"), \
                jax.named_scope("gs.edge_gather"):
            got = edges.edge_permute_tiered(x, tiers)
        return jax.lax.reduce(got & mask[:, :, None], jnp.uint32(0),
                              jax.lax.bitwise_or, (1,))

    text = jax.jit(gathered).lower(
        jax.ShapeDtypeStruct(topo.nbr.shape, jnp.uint32, sharding=one_chip),
        jax.ShapeDtypeStruct((BENCH_N, words), jnp.uint32, sharding=one_chip),
    ).compile().as_text()
    assert " gather(" in text and " scatter(" in text
    assert " while(" not in text
    rows = tiers.table_rows(topo.nbr.shape[1])
    big = [(g["words"], g["count"], g["fusions"])
           for g in window_whiles.edge_gathers(text) if g["rows"] == rows]
    assert big == {5: [(5, 1, 1)], 13: [(5, 1, 1), (8, 1, 1)]}[words]


def test_sybil_window_gathers_read_their_tables_from_fast_memory(
        one_chip, bench_prng):
    """``sybil-50k.stepped``'s window, built as ``scripts/window_whiles.py``
    builds it and compiled for the described chip: each of the ten edge
    gathers of 1,201,152 rows (one a sub-round, one a slice of the control
    head) has its 1,751,680-row table in the fast memory space ``S(1)``.
    With the K-wide attribution planes concatenated into ``[N, 2K, W]`` /
    ``[N, 3K, W]`` stacks XLA's memory-space assignment left five of the
    ten tables in HBM, at 2.86 x the time on the chip (PERF.md §6, PR 40):
    the cell every gather PR watches, because its planes ride on the
    gather's neighbours."""
    import collections
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                    "scripts"))
    import window_whiles

    _, built, lowered = window_whiles.lower_window("sybil-50k.stepped",
                                                   one_chip)
    gathers = window_whiles.edge_gathers(lowered.compile().as_text())
    big = [g for g in gathers if g["rows"] == 1_201_152]
    assert all(g["table_rows"] == 1_751_680 for g in big), big
    by_stage = collections.Counter()
    for g in big:
        by_stage[g["stage"]] += g["count"]
    assert by_stage == {"data_round": 8, "control_head": 2}, gathers
    assert all(g["table_fast"] for g in big), big
    assert window_whiles.edge_tables_hbm(gathers, built.n_peers) == 0


# ---------------------------------------------------------------------------
# the PJRT C-API bridge (native/pjrt_bridge.cc) against the TPU library:
# load a real PJRT plugin, compile StableHLO exported from jax, execute
# against host buffers — zero Python in the device loop (survey §2
# BUILD-NEW "cgo→PJRT bridge"; the C ABI is Go-consumable, driven here
# through ctypes). The plugin is an explicit PJRT_PLUGIN_PATH, else the
# installed libtpu — the same library the compiles above load, which is
# why these tests live in this file. The execute tests skip — not fail —
# when no device is available, an environment property: the bridge opens
# its OWN client, which needs a chip no other process holds.


@pytest.fixture(scope="module")
def bridge():
    """Build native/libpjrt_bridge.so (a git-ignored product of the
    tracked sources) on demand — inside a fixture, so only the worker
    that is given this file runs make, not every worker at import."""
    if not pjrt.available() and not pjrt.build():
        pytest.skip("pjrt bridge library not buildable")


def test_load_bad_path_errors(bridge):
    with pytest.raises(pjrt.PjrtError):
        pjrt.PjrtPlugin.load("/nonexistent/plugin.so")


@pytest.fixture(scope="module")
def client(bridge):
    path = pjrt.default_plugin_path()
    if path is None:
        pytest.skip("no PJRT plugin on this machine")
    plugin = pjrt.PjrtPlugin.load(path)
    try:
        c = plugin.create_client()
    except pjrt.PjrtError as e:
        pytest.skip(f"PJRT client unavailable: {e}")
    yield c
    c.close()


def test_plugin_api_version(bridge):
    path = pjrt.default_plugin_path()
    if path is None:
        pytest.skip("no PJRT plugin on this machine")
    plugin = pjrt.PjrtPlugin.load(path)
    major, minor = plugin.api_version
    assert major == 0 and minor > 0


def test_client_platform_and_devices(client):
    assert client.platform_name != ""
    assert client.device_count() >= 1


def test_buffer_host_roundtrip(client):
    for arr in (
        np.arange(24, dtype=np.float32).reshape(4, 6),
        np.array([1, -2, 3, -4], dtype=np.int32),
        np.arange(30, dtype=np.float32).reshape(2, 3, 5),
    ):
        buf = client.buffer_from_numpy(arr)
        out = buf.to_numpy()
        assert out.dtype == arr.dtype and out.shape == arr.shape
        np.testing.assert_array_equal(out, arr)


def test_compile_and_execute(client):
    import jax
    import jax.numpy as jnp

    def f(x, y):
        return x @ y, jnp.sum(x) + 1.0

    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    y = np.full((4, 2), 2.0, np.float32)
    exported = jax.export.export(jax.jit(f))(
        jax.ShapeDtypeStruct(x.shape, x.dtype),
        jax.ShapeDtypeStruct(y.shape, y.dtype),
    )
    exe = client.compile(exported.mlir_module_serialized)
    assert exe.num_outputs == 2
    outs = exe.run([x, y])
    np.testing.assert_allclose(outs[0], x @ y)
    np.testing.assert_allclose(outs[1], x.sum() + 1.0)


def test_execute_router_selection_kernel(client):
    """Execute a real framework kernel through the bridge: the random-k
    peer selection primitive the heartbeat is built on (ops/select.py)."""
    import jax

    from go_libp2p_pubsub_tpu.ops.select import select_random_mask

    def kern(key, elig):
        return select_random_mask(key, elig, 3)

    key = np.zeros(2, dtype=np.uint32)
    elig = np.ones((8, 16), bool)
    exported = jax.export.export(jax.jit(kern))(
        jax.ShapeDtypeStruct((2,), np.uint32),
        jax.ShapeDtypeStruct(elig.shape, bool),
    )
    exe = client.compile(exported.mlir_module_serialized)
    (sel,) = exe.run([key, elig])
    assert sel.shape == elig.shape
    assert (sel.sum(axis=1) == 3).all()


def test_compile_garbage_errors(client):
    with pytest.raises(pjrt.PjrtError):
        client.compile(b"not an mlir module")


@pytest.mark.parametrize("scored", [False, True])
@pytest.mark.slow
def test_execute_full_gossipsub_step(client, scored):
    """The flagship program end-to-end through the native bridge: export
    the full jitted GossipSub round step (state pytree flattened to
    buffers, PRNG key passed as raw key-data) and run one round with zero
    Python in the loop — the embedding a Go host would use. The scored
    variant is the production v1.1 machine (live score plane +
    thresholds), pinning the ABI the Go embedder depends on."""
    import jax
    import jax.numpy as jnp

    from go_libp2p_pubsub_tpu import graph
    from go_libp2p_pubsub_tpu.config import (
        GossipSubParams,
        PeerScoreParams,
        PeerScoreThresholds,
        TopicScoreParams,
    )
    from go_libp2p_pubsub_tpu.models.gossipsub import (
        GossipSubConfig,
        GossipSubState,
        make_gossipsub_step,
    )
    from go_libp2p_pubsub_tpu.state import Net

    n, m = 64, 32
    topo = graph.ring_lattice(n, d=3)
    net = Net.build(topo, graph.subscribe_all(n, 1))
    if scored:
        sp = PeerScoreParams(
            topics={0: TopicScoreParams(
                mesh_message_deliveries_weight=-0.5,
                mesh_message_deliveries_threshold=2.0,
                mesh_message_deliveries_activation=4.0,
                mesh_message_deliveries_window=2.0,
            )},
            skip_app_specific=True,
            behaviour_penalty_weight=-1.0,
            behaviour_penalty_threshold=1.0,
            behaviour_penalty_decay=0.9,
        )
        cfg = GossipSubConfig.build(
            GossipSubParams(), PeerScoreThresholds(), score_enabled=True
        )
        st = GossipSubState.init(net, m, cfg, score_params=sp, seed=0)
        step = make_gossipsub_step(cfg, net, score_params=sp)
    else:
        cfg = GossipSubConfig.build(GossipSubParams(), PeerScoreThresholds())
        st = GossipSubState.init(net, m, cfg, seed=0)
        step = make_gossipsub_step(cfg, net)

    leaves, treedef = jax.tree_util.tree_flatten(st)
    key_idx = [
        i for i, l in enumerate(leaves)
        if jnp.issubdtype(l.dtype, jax.dtypes.prng_key)
    ]
    assert len(key_idx) == 1
    ki = key_idx[0]

    def step_raw(*flat):
        flat = list(flat)
        flat[ki] = jax.random.wrap_key_data(flat[ki])
        po, pt, pv = flat[-3:]
        s = jax.tree_util.tree_unflatten(treedef, flat[:-3])
        out = step(s, po, pt, pv)
        out_leaves = jax.tree_util.tree_flatten(out)[0]
        out_leaves[ki] = jax.random.key_data(out_leaves[ki])
        return tuple(out_leaves)

    np_in = []
    for i, l in enumerate(leaves):
        if i == ki:
            l = jax.random.key_data(l)
        np_in.append(np.asarray(l))
    po = np.array([5, -1, -1, -1], np.int32)
    pt = np.array([0, -1, -1, -1], np.int32)
    pv = np.array([True, False, False, False])
    np_in += [po, pt, pv]

    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in np_in]
    exported = jax.export.export(jax.jit(step_raw))(*shapes)
    # compile_exported records module_kept_var_idx: XLA prunes unused
    # parameters (e.g. state fields this config never reads), and passing
    # the full list would mismatch the executable's arity
    exe = client.compile_exported(exported)
    outs = exe.run(np_in)
    assert len(outs) == len(leaves)

    # the same step in-process must agree exactly
    ref = step(st, jnp.asarray(po), jnp.asarray(pt), jnp.asarray(pv))
    ref_leaves = jax.tree_util.tree_flatten(ref)[0]
    ref_leaves[ki] = jax.random.key_data(ref_leaves[ki])
    for a, b in zip(outs, ref_leaves):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pure_c_host_executes_module(bridge, tmp_path):
    """The Go-embedding proof, minus Go (not in this image): a pure-C
    program (native/example_host.c) linked against the bridge library
    compiles and executes an exported StableHLO module with no Python in
    the process at all."""
    import pathlib
    import subprocess

    import jax

    from go_libp2p_pubsub_tpu.native.pjrt import (
        default_compile_options,
        default_plugin_path,
    )

    repo = pathlib.Path(__file__).resolve().parent.parent
    host = repo / "native" / "example_host"
    if not host.exists():
        rc = subprocess.run(["make", "-C", str(repo / "native"), "example_host"],
                            capture_output=True, text=True)
        if rc.returncode != 0:
            pytest.skip(f"example_host not buildable: {rc.stderr[-200:]}")
    plugin = default_plugin_path()
    if plugin is None:
        pytest.skip("no PJRT plugin on this machine")

    def f(x):
        return x * 2.0 + 1.0

    exported = jax.export.export(jax.jit(f))(
        jax.ShapeDtypeStruct((8,), np.float32)
    )
    mod = tmp_path / "m.mlirpb"
    mod.write_bytes(exported.mlir_module_serialized)
    opts = tmp_path / "opts.pb"
    opts.write_bytes(default_compile_options())

    args = [str(host), plugin, str(mod), str(opts)]
    rc = subprocess.run(args, capture_output=True, text=True, timeout=240)
    if rc.returncode != 0 and "client:" in rc.stderr:
        pytest.skip(f"PJRT client unavailable to C host: {rc.stderr[-150:]}")
    if rc.returncode != 0 and "lockfile" in rc.stderr:
        # this worker loaded the TPU library (the fixtures above) and
        # keeps it until it exits; a child cannot load it meanwhile
        pytest.skip("the TPU library is held by this test process")
    assert rc.returncode == 0, rc.stderr[-400:]
    # f([1..8]) = [3 5 7 9 11 13 15 17]
    assert rc.stdout.strip().startswith("out0: 3 5 7 9 11 13 15 17"), rc.stdout
