"""The explicit halo exchange of a banded net whose peer axis is split over
a 1-D ``peers`` mesh (``ops/edges._peer_ring``): each ring offset's
boundary rows cross in ONE ``ppermute`` to the neighbouring shard inside a
``shard_map``, and the result is the rolls' bit for bit. Every other
layout (one device, a 2-D mesh, a shard narrower than the widest offset)
keeps the rolls and lowers with no ``shard_map``. On the suite's 8 host
devices; counts, no device metric."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from go_libp2p_pubsub_tpu import driver, graph
from go_libp2p_pubsub_tpu.config import (
    GossipSubParams,
    PeerScoreParams,
    PeerScoreThresholds,
    TopicScoreParams,
)
from go_libp2p_pubsub_tpu.models.gossipsub import GossipSubConfig, GossipSubState
from go_libp2p_pubsub_tpu.models.gossipsub_phase import make_gossipsub_phase_step
from go_libp2p_pubsub_tpu.ops import edges
from go_libp2p_pubsub_tpu.parallel import (
    collective_profile,
    make_mesh,
    make_mesh_2d,
    make_multihost_mesh,
    shard_state,
)
from go_libp2p_pubsub_tpu.perf import stages
from go_libp2p_pubsub_tpu.state import Net

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs the 8-virtual-device CPU harness")

N = 64
#: ring offsets as ``detect_banded`` gives them (mod N): both signs, the
#: widest (8) and the wrap of every one across the ring's ends
OFF = (1, 3, 8, N - 1, N - 2, N - 8)
REV = (3, 4, 5, 0, 1, 2)
#: the sdy op a ``shard_map`` lowers to
MANUAL = "sdy.manual_computation"


def _planes():
    rng = np.random.default_rng(43)
    words = lambda *shape: rng.integers(0, 2**32, shape, dtype=np.uint32)
    return {"edge_nkw": words(N, len(OFF), 3), "edge_nk": words(N, len(OFF)),
            "peer_n": words(N), "peer_nw": words(N, 2)}


PLANES = tuple(_planes())


def _want(kind: str, x: np.ndarray) -> np.ndarray:
    """The banded gather written out: out[j,k] = x[(j+off[k]) % N, rev[k]]
    of an edge plane, v[(j+off[k]) % N] of a peer plane."""
    j = np.arange(N)[:, None]
    rows = (j + np.asarray(OFF)[None, :]) % N
    if kind.startswith("edge"):
        return x[rows, np.asarray(REV)[None, :]]
    return x[rows]


def _gather(kind: str):
    if kind.startswith("edge"):
        return jax.jit(lambda x: edges.edge_permute_banded(x, OFF, REV))
    return jax.jit(lambda v: edges.peer_gather_banded(v, OFF))


def _placed(x, mesh, spec=PartitionSpec("peers")):
    return jax.device_put(jnp.asarray(x), NamedSharding(mesh, spec))


@pytest.mark.parametrize("kind", PLANES)
@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_explicit_halo_equals_the_rolls_bit_for_bit(n_dev, kind):
    x = _planes()[kind]
    fn = _gather(kind)
    placed = _placed(x, make_mesh(n_dev))
    assert MANUAL in fn.lower(placed).as_text()
    got = fn(placed)
    assert np.array_equal(np.asarray(got), _want(kind, x))
    # and the one-device rolls give the same
    assert np.array_equal(np.asarray(fn(jnp.asarray(x))), _want(kind, x))
    assert got.sharding.spec[0] == "peers"


@pytest.mark.parametrize("kind", ["edge_nkw", "peer_nw"])
@pytest.mark.parametrize("n_dev", [2, 4, 8])
def test_one_permute_per_ring_offset_and_no_all_gather(n_dev, kind):
    fn = _gather(kind)
    placed = _placed(_planes()[kind], make_mesh(n_dev))
    prof = collective_profile(fn.lower(placed).compile().as_text())
    assert prof["collective-permute"] == len(OFF), prof
    assert sum(prof.values()) == len(OFF), prof


def _one_device(x):
    return jnp.asarray(x)


def _two_d(x):
    return _placed(x, make_mesh_2d(2, 2), PartitionSpec("peers"))


def _multihost(x):
    mesh = make_multihost_mesh(n_hosts=2, devices=jax.devices()[:4])
    return _placed(x, mesh, PartitionSpec(("dcn", "ici")))


def _narrow(x):
    # shards of 4 rows on 8 devices, against the widest offset's 8
    return _placed(x[:32], make_mesh(8))


FALLBACKS = {"one_device": _one_device, "sims_x_peers": _two_d,
             "dcn_x_ici": _multihost, "narrow_shard": _narrow}


@pytest.mark.parametrize("kind", ["edge_nkw", "peer_n"])
@pytest.mark.parametrize("layout", FALLBACKS)
def test_other_layouts_keep_the_rolls(layout, kind):
    x = _planes()[kind]
    placed = FALLBACKS[layout](x)
    n = placed.shape[0]
    off = tuple(o % n for o in OFF)
    if kind.startswith("edge"):
        fn = jax.jit(lambda a: edges.edge_permute_banded(a, off, REV))
    else:
        fn = jax.jit(lambda a: edges.peer_gather_banded(a, off))
    assert MANUAL not in fn.lower(placed).as_text()
    j = np.arange(n)[:, None]
    rows = (j + np.asarray(off)[None, :]) % n
    host = np.asarray(placed)
    want = (host[rows, np.asarray(REV)[None, :]] if kind.startswith("edge")
            else host[rows])
    assert np.array_equal(np.asarray(fn(placed)), want)


R = 4


def _lattice_window():
    """A scored phase window on a ring lattice (K = 16, ring offsets up to
    8), as ``tests/test_stages.py`` builds its toy."""
    net = Net.build(graph.ring_lattice(N, d=8), graph.subscribe_all(N, 1))
    tp = TopicScoreParams(mesh_message_deliveries_weight=0.0,
                          mesh_failure_penalty_weight=0.0,
                          invalid_message_deliveries_weight=0.0)
    sp = PeerScoreParams(topics={0: tp}, skip_app_specific=True)
    cfg = GossipSubConfig.build(
        dataclasses.replace(GossipSubParams(), flood_publish=False),
        PeerScoreThresholds(), score_enabled=True, heartbeat_every=R)
    cfg = dataclasses.replace(cfg, count_events=False, fanout_slots=0)
    step = make_gossipsub_phase_step(cfg, net, R, score_params=sp)
    scan = driver.make_scan(step, heartbeat_every=R, rounds_per_phase=R,
                            static_heartbeat=True, donate=False)
    return scan, GossipSubState.init(net, 64, cfg, score_params=sp, seed=3)


@pytest.mark.parametrize("n_dev", [1, 4])
def test_halo_exchanges_per_dispatch(n_dev):
    """``r + 1`` gather sets a step call take the exchange where the peer
    axis is split (the pin's ``r`` data sets and the control head's,
    ``tests/test_collectives.py``), none on one device; the window still
    compiles once."""
    scan, st = _lattice_window()
    if n_dev > 1:
        st = shard_state(st, make_mesh(n_dev), N)
    rng = np.random.default_rng(0)
    xs = (jnp.asarray(rng.integers(0, N, (2 * R, 4)).astype(np.int32)),
          jnp.zeros((2 * R, 4), jnp.int32), jnp.ones((2 * R, 4), bool))
    st = scan(scan(st, *xs), *xs)
    jax.block_until_ready(st)
    assert scan._cache_size() == 1
    (entry,) = [w for w in stages.traced_windows() if w.jitted is scan]
    assert entry.sharded == (n_dev > 1)
    assert entry.halo_exchanges_per_dispatch == (R + 1 if n_dev > 1 else 0)
