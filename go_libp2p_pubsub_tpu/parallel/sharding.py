"""Device-mesh sharding of the peer axis (survey §2 checklist: the
TPU-native distributed backend).

The framework's parallelism is data-parallel-over-peers: every state array
whose leading dimension is N is sharded along a 1-D 'peers' mesh axis;
small global structures (the message table, event counters, RNG key) are
replicated.

Cross-peer traffic, the edge gathers of the delivery engine and of the
control-plane handlers, crosses a 1-D 'peers' mesh in the program's own
exchanges: on a banded ring one ``ppermute`` a ring offset
(``ops/edges._banded``), on every other static dense graph one exchange a
gather set of the rows each shard's slots read from the others, S - 1
permutes by shard offset (``ops/edges.edge_permute_sharded``, the
per-shard plan). GSPMD chooses the collectives for the rest: the
neighbour views ``v[nbr]``, the reductions into replicated structures,
and every layout the explicit exchanges do not take (a 2-D or multi-host
mesh, a traced dynamic graph, the flat CSR layout).

This replaces the reference's libp2p stream layer + per-peer goroutines
(comm.go), the "NCCL analogue" named in the survey, with collectives over
ICI (single host) / DCN (multi host): explicit ones where the program
knows the graph, compiler-inserted ones elsewhere.
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
    replicated, a leaf with no elements too (XLA hands a zero-size
    output back replicated whatever its input was, so a window over a
    peer-split ``[N, 0]`` plane would compile a second time for the state
    it returned).

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
        if not hasattr(leaf, "shape") or leaf.ndim < 1 or leaf.size == 0:
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
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*?) ("
    + "|".join(_COLLECTIVES) + r")(-start|-done)?\(")
_ARRAY_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_PARTITIONS_RE = re.compile(r"num_partitions=(\d+)")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_LIST_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]*)\}")
#: bytes of an element, by the dtype HLO prints
DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
               "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8, "s4": 0.5, "u4": 0.5}


def _dims(text: str) -> tuple:
    return tuple(int(d) for d in text.split(",") if d)


_COMPUTATION_RE = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_CALLEE_RE = re.compile(
    r"\b(?:body|condition|calls|to_apply|true_computation|"
    r"false_computation)=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')


def _computations(hlo_text: str) -> tuple:
    """``({computation: its instruction lines}, entry)`` of compiled HLO."""
    lines, entry, comp = {}, None, None
    for line in hlo_text.splitlines():
        head = _COMPUTATION_RE.match(line)
        if head is not None and " = " not in line:
            comp = head.group(1)
            lines[comp] = []
            if line.startswith("ENTRY"):
                entry = comp
        elif comp is not None and " = " in line:
            lines[comp].append(line)
    return lines, entry


def _trips(while_line: str, lines: dict) -> int:
    """A ``while``'s trip count: XLA's ``known_trip_count`` where it gives
    one (XLA:CPU), else the bound of its condition's ``i < N`` against a
    constant (a scan's loop counts from 0; the TPU compiler's text), else
    1."""
    known = _TRIP_RE.search(while_line)
    if known:
        return int(known.group(1))
    cond = re.search(r"condition=%?([\w.\-]+)", while_line)
    body = lines.get(cond.group(1), []) if cond else []
    consts = dict(re.findall(r"%?([\w.\-]+) = \S+ constant\((\d+)\)",
                             "\n".join(body)))
    for line in body:
        cmp = re.search(r"ROOT .* compare\(%?([\w.\-]+), %?([\w.\-]+)\), "
                        r"direction=LT", line)
        if cmp and cmp.group(2) in consts:
            return int(consts[cmp.group(2)])
    return 1


def computation_runs(hlo_text: str) -> dict:
    """``{computation: times one run of the module runs it}`` of compiled
    HLO: the entry once, a ``while`` body and condition as often as the
    loop turns (:func:`_trips`), every other callee as often as its
    callers (each branch of a conditional as if taken)."""
    lines, entry = _computations(hlo_text)
    calls = {comp: [] for comp in lines}
    for comp, body in lines.items():
        for line in body:
            times = _trips(line, lines) if " while(" in line else 1
            for one, many in _CALLEE_RE.findall(line):
                for callee in ([one] if one else many.split(",")):
                    calls[comp].append((callee.strip().lstrip("%"), times))
    runs: dict = {}

    def visit(comp, times):
        runs[comp] = runs.get(comp, 0) + times
        for callee, n in calls.get(comp, ()):
            visit(callee, times * n)

    if entry is not None:
        visit(entry, 1)
    return runs


def collective_table(hlo_text: str) -> list:
    """``(instruction, op, results, group, bytes sent, runs)`` of every
    collective of a compiled (partitioned) module, an async pair once (by
    its ``-start``). ``results`` are the ``(dtype, shape)`` of its result
    arrays: the op's type, all of a variadic op's TUPLE, the second array
    of an async start's tuple (``(operand, result, ...)``); ``group`` the
    (source, target) pairs of a permute, else the size of its device
    group; ``runs`` how often one run of the module runs it
    (:func:`computation_runs`). Bytes sent are what ONE device puts on the
    links for it, the mean over the module's partitions: a permute sends
    its result to its target (pairs / partitions of the devices send), an
    all-gather (g - 1) / g of its result, a reduce-scatter g - 1 results,
    an all-to-all (g - 1) / g, an all-reduce twice (g - 1) / g (a ring's
    reduce-scatter and all-gather)."""
    m = _PARTITIONS_RE.search(hlo_text)
    n_part = int(m.group(1)) if m else None
    lines, _entry = _computations(hlo_text)
    runs = computation_runs(hlo_text)
    out = []
    for comp, body in lines.items():
        for line in body:
            m = _COLLECTIVE_RE.search(line)
            if m is None or m.group(4) == "-done":
                continue
            name, typ, op = m.group(1), m.group(2), m.group(3)
            arrays = [(dtype, _dims(dims))
                      for dtype, dims in _ARRAY_RE.findall(typ)]
            if typ.startswith("(") and m.group(4) == "-start":
                arrays = arrays[1:2]
            nbytes = sum(DTYPE_BYTES.get(dtype, 4) * int(np.prod(dims))
                         for dtype, dims in arrays)
            if op == "collective-permute":
                pairs = _PAIRS_RE.search(line)
                group = pairs.group(1).count("{") if pairs else 1
                sent = nbytes * group / (n_part or group)
            else:
                iota = _IOTA_GROUPS_RE.search(line)
                listed = _LIST_GROUPS_RE.search(line)
                group = (int(iota.group(2)) if iota else
                         len(_dims(listed.group(1))) if listed else
                         n_part or 1)
                share = (group - 1) / group
                sent = nbytes * {"all-reduce": 2 * share,
                                 "reduce-scatter": group - 1}.get(op, share)
            out.append((name, op, tuple(arrays), group, sent,
                        runs.get(comp, 1)))
    return out


def collective_ops(hlo_text: str) -> list:
    """``(op, shape)`` of every collective in compiled (partitioned) HLO,
    ``shape`` being the first array of its type: the operand of an async
    start (:func:`collective_table` has the results). The async start forms count
    as their op: XLA:CPU prints them with an array type, the TPU compiler
    with a TUPLE type ``(operand, result, ...)`` whose layouts carry
    parentheses of their own, so the type is matched non-greedily up to
    the op name rather than as one token."""
    out = []
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m is None or m.group(4) == "-done":
            continue
        out.append((m.group(3), _dims(_ARRAY_RE.search(m.group(2)).group(2))))
    return out


def collective_names(hlo_text: str) -> dict:
    """``{instruction: op}`` of every collective instruction of compiled
    HLO, the ``-start`` and ``-done`` of an async pair both: the names
    a device trace gives the collectives' op events."""
    out = {}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m is not None:
            out[m.group(1)] = m.group(3)
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
