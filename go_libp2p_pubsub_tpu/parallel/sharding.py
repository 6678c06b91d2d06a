"""Device-mesh sharding of the peer axis (survey §2 checklist: the
TPU-native distributed backend).

The framework's parallelism is data-parallel-over-peers: every state array
whose leading dimension is N is sharded along a 1-D 'peers' mesh axis;
small global structures (the message table, event counters, RNG key) are
replicated. Cross-peer traffic — the neighbor gathers x[nbr] in the
delivery engine and control-plane handlers — lowers to XLA collectives
over ICI (single host) / DCN (multi host) under GSPMD; the topology
builders can be composed with a peer-id relabeling so that most mesh
edges stay shard-local, keeping those collectives small.

This replaces the reference's libp2p stream layer + per-peer goroutines
(comm.go) — the "NCCL analogue" named in the survey — with compiler-
inserted collectives, per the scaling-book recipe: pick a mesh, annotate
shardings, let XLA do the rest.
"""

from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """1-D device mesh over the peer axis."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.asarray(devices), ("peers",))


def make_multihost_mesh(
    n_hosts: int | None = None, devices=None, axis_names=("dcn", "ici")
) -> Mesh:
    """2-D (hosts x chips-per-host) mesh for multi-host runs: the peer axis
    is sharded over BOTH axes (dcn-major), so neighbor gathers between
    peer-shards on one host ride ICI while only the band edges that cross a
    host boundary pay DCN — the banded topology builders put consecutive
    peer ids on the same host, keeping DCN traffic to the halo.

    Single-process multi-host simulation (the driver's virtual-device
    setup) and real multi-host (jax.distributed + one process per host)
    build the same mesh; under GSPMD the collective choice per edge is
    XLA's, exactly the scaling-book recipe."""
    if devices is None:
        devices = jax.devices()
    if n_hosts is None:
        n_hosts = max(1, len(set(d.process_index for d in devices)))
    n_dev = len(devices)
    assert n_dev % n_hosts == 0, "devices must split evenly across hosts"
    # host-major order so each 'ici' row stays within one process — the
    # global device list is not guaranteed to be grouped by host
    devices = sorted(devices, key=lambda d: (d.process_index, d.id))
    arr = np.asarray(devices).reshape(n_hosts, n_dev // n_hosts)
    return Mesh(arr, axis_names)


def make_mesh_2d(n_sims_devices: int, n_peer_devices: int | None = None,
                 devices=None, axis_names=("sims", "peers")) -> Mesh:
    """2-D (sims × peers) device mesh for ensemble windows
    (docs/DESIGN.md §14): the leading sim axis of a batched state tree
    shards over ``sims`` rows and the peer axis over ``peers`` columns
    (ensemble.shard_ensemble_state(axis="sims+peers")). Each sims-row
    is an independent replica of the 1-D peer layout, so the halo
    collective-permute count per phase is UNCHANGED vs the 1-D mesh —
    permutes just run row-parallel (the collective audit asserts
    this). sims-major order keeps each row's peer shards on
    consecutive devices (ICI-adjacent on a real slice)."""
    if devices is None:
        devices = jax.devices()
    ns = int(n_sims_devices)
    if ns < 1 or len(devices) % ns:
        raise ValueError(
            f"n_sims_devices={ns} must divide the device count "
            f"{len(devices)}")
    npd = int(n_peer_devices) if n_peer_devices else len(devices) // ns
    if ns * npd > len(devices):
        raise ValueError(
            f"mesh {ns}x{npd} needs {ns * npd} devices, have "
            f"{len(devices)}")
    arr = np.asarray(devices[: ns * npd]).reshape(ns, npd)
    return Mesh(arr, tuple(axis_names))


def peer_spec(mesh: Mesh) -> P:
    """PartitionSpec sharding the leading (peer) axis over every mesh axis."""
    return P(tuple(mesh.axis_names)) if len(mesh.axis_names) > 1 else P(mesh.axis_names[0])


def state_shardings(state, mesh: Mesh, n_peers: int,
                    n_edges: int | None = None):
    """Pytree of NamedShardings: leaves with leading dim == n_peers are
    sharded along the peer axes (all mesh axes); everything else is
    replicated.

    ``n_edges`` (round 18) extends the rule to the CSR-RESIDENT flat
    planes: leaves with leading dim == E shard over the SAME peer axes.
    Because the flat edge space is row-owner-ordered (ops/csr.py) and —
    on ``edge_shards=`` builds — padded to row-owner-ALIGNED equal
    blocks (pad_csr_blocks), each peer shard owns whole rows of the
    edge axis: the [E] partition follows the [N] partition, so a
    shard's cross-peer traffic stays the same boundary halo the dense
    involution pays. Pass ``net.n_edges`` (None on dense builds)."""
    peer = NamedSharding(mesh, peer_spec(mesh))
    repl = NamedSharding(mesh, P())

    def choose(leaf):
        if not hasattr(leaf, "shape") or leaf.ndim < 1:
            return repl
        if leaf.shape[0] == n_peers:
            return peer
        if n_edges is not None and leaf.shape[0] == n_edges:
            return peer
        return repl

    return jax.tree_util.tree_map(choose, state)


def shard_state(state, mesh: Mesh, n_peers: int,
                n_edges: int | None = None):
    """Place a state pytree onto the mesh with peer-axis sharding
    (``n_edges`` shards the CSR-resident flat planes too)."""
    return jax.device_put(
        state, state_shardings(state, mesh, n_peers, n_edges=n_edges))


_COLLECTIVES = ("collective-permute", "all-gather", "all-reduce",
                "all-to-all", "reduce-scatter")
_COLLECTIVE_RE = re.compile(
    r" = (.*?) (" + "|".join(_COLLECTIVES) + r")(?:-start)?\(")
_ARRAY_RE = re.compile(r"[a-z0-9]+\[([0-9,]*)\]")


def collective_ops(hlo_text: str) -> list:
    """``(op, shape)`` of every collective in compiled (partitioned) HLO,
    ``shape`` being the first array of its result type. The async start
    forms count as their op: XLA:CPU prints them with an array type, the
    TPU compiler with a TUPLE type ``(operand, result, ...)`` whose
    layouts carry parentheses of their own, so the type is matched
    non-greedily up to the op name rather than as one token."""
    out = []
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m is None:
            continue
        dims = _ARRAY_RE.search(m.group(1)).group(1)
        out.append((m.group(2),
                    tuple(int(d) for d in dims.split(",") if d)))
    return out


def collective_profile(hlo_text: str) -> dict:
    """Count collective ops in compiled (partitioned) HLO
    (:func:`collective_ops`). Used by the scaling report
    (scripts/scaling_cpu_mesh.py), the CI regression guard
    (tests/test_collectives.py) and chip_smoke.py to pin the GSPMD
    lowering of the cross-peer neighbor gathers (halo transfers, never
    peer-sized all-gathers)."""
    prof = dict.fromkeys(_COLLECTIVES, 0)
    for op, _shape in collective_ops(hlo_text):
        prof[op] += 1
    return prof
