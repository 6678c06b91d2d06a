"""The quickest proof that the system still starts on the chip.

``python3 chip_smoke.py`` needs ONE TPU chip and drives the main path
once through the entry points a user calls, at the bench's real size:

  engine    the 100k-peer GossipSub v1.1 phase engine under the scanned
            window ``bench.py`` measures (``perf.sweep``), checked against
            the ring lattice's causality bound, the mesh / score planes
            and the invariant oracle folded into one window;
  floodsub  the integer-only, randomness-free FloodSub data plane on the
            chip AND on this host's CPU backend, compared bit for bit;
  api       the README quick start (``api.Network``) per round and at
            ``rounds_per_phase=8`` with a JSON trace sink attached;
  served    ``serve.Supervisor`` over the bench net: segments, a rolling
            checkpoint, resume from the store, digest equal to an
            uninterrupted run.

``python3 chip_smoke.py --chips 4`` needs FOUR chips and runs only the
peer-sharded engine cell and the one-device run it is compared with.

One process, no child that imports jax: a chip belongs to one process.
Every phase prints one JSON line; a phase that raises or fails a check
ends the run with a non-zero exit code and no result line. The LAST line
of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU (``perf.sweep.select_platform`` raises) or without the rest
of the repo beside this file (the import fails) the script exits non-zero
and prints no result. The per-segment seconds it
prints are smoke output, not a benchmark.

The command line offers no size and no platform option; the phases are
functions of their sizes so tests/test_chip_smoke.py can run the same
control flow on the CPU at toy size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

BENCH_M = 64         # bench.py's BENCH_M default
BENCH_R = 8          # bench.py's BENCH_PHASE_R default (heartbeat_every too)
LATTICE_D = 8        # perf.sweep.bench_cell: ring_lattice(d=8) -> K = 16
BENCH_PRNG = "unsafe_rbg"   # bench.py's BENCH_PRNG default
PARITY_PRNG = "threefry2x32"  # sharding-invariant draws (tests/test_parallel.py)


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def peak_bytes(device):
    """``memory_stats()["peak_bytes_in_use"]`` where the backend reports
    it (the TPU does; the CPU backend returns no stats)."""
    stats = device.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def versions() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def tree_equal(a, b) -> list:
    """Key paths of the leaves that differ between two state trees
    (PRNG keys compared by their key data)."""
    import jax
    import numpy as np

    bad = []
    fa, _ = jax.tree_util.tree_flatten_with_path(a)
    fb = jax.tree_util.tree_leaves(b)
    require(len(fa) == len(fb), "state trees differ in structure")
    for (path, x), y in zip(fa, fb):
        if jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key):
            x, y = jax.random.key_data(x), jax.random.key_data(y)
        if not np.array_equal(np.asarray(x), np.asarray(y)):
            bad.append(jax.tree_util.keystr(path))
    return bad


# ---------------------------------------------------------------------------
# engine: the path bench.py drives, as a library


def check_engine_state(st, n_peers: int, rounds_run: int) -> dict:
    """The engine phase's answers, read back from the device.

    This cell is a throughput workload, not a coverage one: on the
    degree-16 ring lattice a message moves at most 8 peer ids per round
    and its slot is recycled after M/4 = 16 rounds, so full delivery is
    NOT the protocol's answer here. What must hold is causality (no
    holder farther from the origin than the rounds since publish allow —
    a wrong gather or a wrapped index breaks it), progress (a message
    two rounds old has left its origin), and well-formed mesh / scores.
    """
    import numpy as np

    from go_libp2p_pubsub_tpu.ops import bitset

    core = st.core
    tick = int(core.tick)
    require(tick == rounds_run,
            f"core.tick {tick} != rounds executed {rounds_run}")
    m = core.msgs.capacity
    have = np.asarray(bitset.unpack(core.dlv.have, m))          # [N, M]
    origin = np.asarray(core.msgs.origin)
    birth = np.asarray(core.msgs.birth)
    live = np.flatnonzero(birth >= 0)
    require(live.size > 0, "no live message after the run")
    worst_slack = None
    for slot in live:
        holders = np.flatnonzero(have[:, slot])
        age = tick - int(birth[slot])     # delivery rounds, publish round counted
        require(age >= 1, f"slot {slot}: birth {birth[slot]} >= tick {tick}")
        require(have[origin[slot], slot],
                f"slot {slot}: origin {origin[slot]} does not hold its message")
        d = np.abs(holders - int(origin[slot]))
        dist = int(np.minimum(d, n_peers - d).max())
        require(dist <= LATTICE_D * age,
                f"slot {slot}: holder {dist} ring positions from origin "
                f"after {age} rounds (bound {LATTICE_D * age})")
        if age >= 2:
            require(holders.size > 1,
                    f"slot {slot}: {age} rounds old, held by its origin only")
        slack = LATTICE_D * age - dist
        worst_slack = slack if worst_slack is None else min(worst_slack, slack)
    deg = np.asarray(st.mesh).sum(axis=(1, 2))
    k = st.mesh.shape[-1]
    require(deg.min() >= 1 and deg.max() <= k,
            f"mesh degree outside [1, {k}]: min {deg.min()} max {deg.max()}")
    require(bool(np.isfinite(np.asarray(st.scores)).all()),
            "non-finite score")
    vals, counts = np.unique(deg, return_counts=True)
    return {
        "tick": tick,
        "live_messages": int(live.size),
        "min_causality_slack": int(worst_slack),
        "mesh_degree_histogram": {int(v): int(c) for v, c in zip(vals, counts)},
    }


def run_invariants(n_peers: int, rounds: int, check_every: int, devices) -> dict:
    """One window of the bench net with the invariant oracle folded into
    the scan (``oracle.ScanInvariants``): zero violations, one compile."""
    import jax
    import jax.numpy as jnp

    from go_libp2p_pubsub_tpu.driver import make_window
    from go_libp2p_pubsub_tpu.oracle import InvariantConfig, ScanInvariants
    from go_libp2p_pubsub_tpu.perf.sweep import bench_cell, bench_schedule

    r = BENCH_R
    d = rounds // r
    # live event counters: the counter properties read them
    cell = bench_cell(n_peers, BENCH_M, config="default", heartbeat_every=r,
                      rounds_per_phase=r, count_events=True, devices=devices)
    spec = ScanInvariants(
        "phase", cell.net, cell.cfg,
        InvariantConfig(check_every=check_every, delivery_window=2 * r),
        batched=False, rounds_per_step=r)
    due = spec.precompute(d)
    window = make_window(cell.step, heartbeat=[True], check=spec.check,
                         check_every=check_every)
    xs = tuple(jnp.asarray(a).reshape((d, r) + a.shape[1:])
               for a in bench_schedule(n_peers, cell.n_topics, cell.honest,
                                       d * r))
    t0 = time.perf_counter()
    st, ys = window(cell.state, xs, due)
    jax.block_until_ready((st, ys))
    seconds = time.perf_counter() - t0
    rep = spec.report(ys["ok"])
    require(rep.n_checks == d // check_every and rep.checked > 0,
            f"{rep.n_checks} invariant checks recorded, expected "
            f"{d // check_every}")
    require(rep.all_ok,
            f"{rep.violated}/{rep.checked} invariant evaluations failed: "
            f"{rep.violations(8)}")
    require(window._cache_size() == 1,
            f"the checked window compiled {window._cache_size()} times")
    return {"n_peers": n_peers, "rounds": d * r, "checks": rep.n_checks,
            "properties": len(rep.names), "evaluations": rep.checked,
            "violations": rep.violated,
            "compile_and_run_seconds": round(seconds, 3)}


def phase_engine(n_peers: int, seg_rounds: int, n_segments: int,
                 inv_n: int, inv_rounds: int, devices) -> None:
    import jax
    import jax.numpy as jnp

    from go_libp2p_pubsub_tpu.perf.sweep import (
        bench_cell,
        bench_schedule,
        make_bench_scan,
    )

    jax.config.update("jax_default_prng_impl", BENCH_PRNG)
    r = BENCH_R
    cell = bench_cell(n_peers, BENCH_M, config="default", heartbeat_every=r,
                      rounds_per_phase=r, devices=devices)
    po, pt, pv = (jnp.asarray(a) for a in bench_schedule(
        n_peers, cell.n_topics, cell.honest, seg_rounds))
    scan, unroll = make_bench_scan(cell.step, r, r)

    def readback(st):
        # the scalar readback perf.sweep used as its completion barrier
        return int(st.core.tick), float(jnp.sum(st.scores))

    t0 = time.perf_counter()
    st = scan(cell.state, po, pt, pv)           # compile + warm-up
    jax.block_until_ready(st)
    readback(st)                                # ... and the readback's reduce
    compile_s = time.perf_counter() - t0
    rounds_run = seg_rounds

    seg_s = []
    for _ in range(n_segments):
        t0 = time.perf_counter()
        st = scan(st, po, pt, pv)
        jax.block_until_ready(st)
        seg_s.append(time.perf_counter() - t0)
        rounds_run += seg_rounds

    # is jax.block_until_ready a true completion barrier on this runtime?
    # One segment ended by it (then the readback, which must find nothing
    # left to wait for), one ended by the readback alone.
    t0 = time.perf_counter()
    st = scan(st, po, pt, pv)
    jax.block_until_ready(st)
    block_s = time.perf_counter() - t0
    readback(st)
    after_block_s = time.perf_counter() - t0 - block_s
    t0 = time.perf_counter()
    st = scan(st, po, pt, pv)
    readback(st)
    readback_s = time.perf_counter() - t0
    rounds_run += 2 * seg_rounds
    is_barrier = block_s >= 0.9 * readback_s and after_block_s <= 0.1 * block_s

    require(scan._cache_size() == 1,
            f"the scan compiled {scan._cache_size()} times")
    checks = check_engine_state(st, n_peers, rounds_run)
    emit("engine", n_peers=n_peers, msg_slots=BENCH_M, rounds_per_phase=r,
         seg_rounds=seg_rounds, unroll=unroll, prng=BENCH_PRNG,
         devices=len(devices), compile_and_warmup_seconds=round(compile_s, 3),
         segment_seconds=[round(s, 4) for s in seg_s],
         barrier={"block_until_ready_seconds": round(block_s, 4),
                  "readback_after_block_seconds": round(after_block_s, 4),
                  "readback_only_seconds": round(readback_s, 4),
                  "block_until_ready_is_barrier": bool(is_barrier)},
         scan_compiles=scan._cache_size(),
         peak_bytes_in_use=peak_bytes(devices[0]), **checks)
    del st, cell, scan

    inv = run_invariants(inv_n, inv_rounds, 4, devices)
    emit("engine.invariants", peak_bytes_in_use=peak_bytes(devices[0]), **inv)


# ---------------------------------------------------------------------------
# data plane: chip against host, bit for bit


def run_floodsub(n_peers: int, rounds: int, device):
    """The seeded FloodSub cell on one device: ``(have, first_round,
    events, delivery_ratio)`` as numpy."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from go_libp2p_pubsub_tpu import graph
    from go_libp2p_pubsub_tpu.ensemble import stats as estats
    from go_libp2p_pubsub_tpu.models.floodsub import floodsub_step
    from go_libp2p_pubsub_tpu.state import Net, SimState

    pubs = 2
    require(pubs * (rounds // 2) <= BENCH_M, "schedule would recycle a slot")
    rng = np.random.default_rng(7)
    po = rng.integers(0, n_peers, size=(rounds, pubs)).astype(np.int32)
    po[rounds // 2:] = -1                      # publishes in the first half
    pt = np.zeros((rounds, pubs), np.int32)
    pv = np.ones((rounds, pubs), bool)
    with jax.default_device(device):
        net = Net.build(graph.random_connect(n_peers, d=4, seed=1),
                        graph.subscribe_all(n_peers, 1))
        st = SimState.init(n_peers, BENCH_M, seed=5, k=net.max_degree)
        for i in range(rounds):
            st = floodsub_step(net, st, jnp.asarray(po[i]),
                               jnp.asarray(pt[i]), jnp.asarray(pv[i]))
        jax.block_until_ready(st)
        require(st.dlv.have.devices() == {device},
                f"floodsub ran on {st.dlv.have.devices()}, not {device}")
        ratio = estats.sim_delivery_ratios(
            st.dlv.first_round[None], st.msgs.birth[None],
            st.msgs.topic[None], st.msgs.origin[None], net.subscribed)
        return (np.asarray(st.dlv.have), np.asarray(st.dlv.first_round),
                np.asarray(st.events), float(np.asarray(ratio)[0]))


def phase_floodsub(n_peers: int, rounds: int, device, reference) -> None:
    import numpy as np

    got = run_floodsub(n_peers, rounds, device)
    want = run_floodsub(n_peers, rounds, reference)
    for name, a, b in zip(("have", "first_round", "events"), got, want):
        require(np.array_equal(a, b),
                f"floodsub {name} differs between {device} and {reference}")
    require(got[3] == 1.0 and want[3] == 1.0,
            f"floodsub delivery ratio {got[3]} / {want[3]}, expected 1.0")
    emit("floodsub", n_peers=n_peers, rounds=rounds, device=str(device),
         reference=str(reference), identical=["have", "first_round", "events"],
         delivery_ratio=got[3], events_sum=int(got[2].sum()))


# ---------------------------------------------------------------------------
# user API: the README quick start


def phase_api(n_nodes: int, tmp: str) -> None:
    from go_libp2p_pubsub_tpu import api
    from go_libp2p_pubsub_tpu.pb import pubsub_trace_pb2 as tpb
    from go_libp2p_pubsub_tpu.trace import sinks

    for r in (1, BENCH_R):
        path = os.path.join(tmp, f"trace_r{r}.ndjson")
        net = api.Network(rounds_per_phase=r,
                          trace_sinks=[sinks.JSONTracer(path)])
        nodes = net.add_nodes(n_nodes)
        net.dense_connect(d=6, seed=3)
        subs = [nd.join("news").subscribe() for nd in nodes]
        net.start()
        for i in range(3):
            nodes[i].topics["news"].publish(b"m%d" % i)
        net.run(8)
        net.stop()
        got = [sum(1 for _ in s) for s in subs]
        require(all(g == 3 for g in got),
                f"rounds_per_phase={r}: deliveries per subscriber {got}")
        kinds = [ev.type for ev in sinks.read_json_trace(path)]
        n_pub = kinds.count(tpb.TraceEvent.PUBLISH_MESSAGE)
        n_dlv = kinds.count(tpb.TraceEvent.DELIVER_MESSAGE)
        require(n_pub == 3, f"rounds_per_phase={r}: {n_pub} publish events")
        # the device->host drain ran: every (peer, message) delivery traced
        require(n_dlv >= 3 * (n_nodes - 1),
                f"rounds_per_phase={r}: {n_dlv} deliver events")
        emit("api", rounds_per_phase=r, nodes=n_nodes, delivered=sum(got),
             trace_events=len(kinds), publish_events=n_pub,
             deliver_events=n_dlv)


# ---------------------------------------------------------------------------
# served path: supervisor, rolling checkpoint, resume


def served_host_ms(seconds: float) -> dict:
    """The served loop's host spans recorded since the recorder was cleared
    (``perf/spans.py``): per span its count, its median and its total in
    ms, and what the ``serve.segment`` spans leave of ``seconds``."""
    import statistics

    from go_libp2p_pubsub_tpu.perf import spans

    by = {name[len("serve."):]: [1e3 * x for x in took]
          for name, took in spans.seconds_by_name("serve.").items()}
    out = {k: {"n": len(v), "median": round(statistics.median(v), 3),
               "total": round(sum(v), 3)} for k, v in sorted(by.items())}
    out["outside_segments"] = {"total": round(
        1e3 * seconds - sum(by.get("segment", [])), 3)}
    return out


def phase_served(n_peers: int, segment_len: int, n_segments: int,
                 tmp: str, devices) -> None:
    """``serve.Supervisor`` over the bench net, wired as
    ``serve/_child.py`` wires its N=48 cell: run the first half, then a
    FRESH supervisor on the same root resumes from the store and
    finishes; the digest must equal an uninterrupted run's."""
    import jax
    import jax.numpy as jnp

    from go_libp2p_pubsub_tpu.perf import spans
    from go_libp2p_pubsub_tpu.perf.sweep import bench_cell, bench_schedule
    from go_libp2p_pubsub_tpu.serve import (
        ServiceConfig,
        Supervisor,
        state_digest,
    )

    jax.config.update("jax_default_prng_impl", BENCH_PRNG)
    r = BENCH_R
    require(n_segments >= 4 and n_segments % 2 == 0,
            "the served phase halves its segments around the resume")
    total = segment_len * n_segments
    cell = bench_cell(n_peers, BENCH_M, config="default", heartbeat_every=r,
                      rounds_per_phase=r, devices=devices)
    po, pt, pv = (a.reshape((total, r) + a.shape[1:]) for a in bench_schedule(
        n_peers, cell.n_topics, cell.honest, total * r))

    def make_args(i):
        return jnp.asarray(po[i]), jnp.asarray(pt[i]), jnp.asarray(pv[i])

    def supervisor(root, n_dispatches):
        svc = ServiceConfig(
            n_dispatches=n_dispatches, segment_len=segment_len,
            rounds_per_dispatch=r,
            checkpoint_every_segments=n_segments // 2, report_name=None)
        return Supervisor(cell.step, make_args, cell.fresh, root, svc,
                          heartbeat_fn=lambda i: True)

    def run(root, n_dispatches, fresh):
        t0 = time.perf_counter()
        rep = supervisor(root, n_dispatches).run(fresh=fresh)
        require(rep.recoveries == 0 and rep.retries == 0
                and not rep.degradations,
                f"recoveries {rep.recoveries} retries {rep.retries} "
                f"degradations {rep.degradations}")
        require(set(rep.window_compiles.values()) == {1},
                f"window compiles {rep.window_compiles}")
        return rep, round(time.perf_counter() - t0, 3)

    spans.clear()
    control, control_s = run(os.path.join(tmp, "control"), total, True)
    host_ms = served_host_ms(control.seconds)
    want = state_digest(control.states)
    del control

    root = os.path.join(tmp, "served")
    first, first_s = run(root, total // 2, True)
    require(len(first.checkpoints) >= 1, "no rolling checkpoint written")
    require(first.segments == n_segments // 2,
            f"{first.segments} segments before the resume")
    del first
    resumed, resumed_s = run(root, total, False)
    require(resumed.resumed_from == total // 2,
            f"resumed from dispatch {resumed.resumed_from}, expected "
            f"{total // 2}")
    got = state_digest(resumed.states)
    require(got == want, f"resumed digest {got} != uninterrupted {want}")
    emit("served", n_peers=n_peers, rounds=total * r,
         segment_rounds=segment_len * r, segments=n_segments,
         resumed_from_dispatch=resumed.resumed_from,
         checkpoints=[e["ordinal"] for e in resumed.checkpoints],
         window_compiles=resumed.window_compiles, digest=got,
         seconds={"uninterrupted": control_s, "first_half": first_s,
                  "resumed_half": resumed_s},
         host_ms_uninterrupted=host_ms,
         peak_bytes_in_use=peak_bytes(devices[0]))


# ---------------------------------------------------------------------------
# --chips 4: the peer-sharded engine cell against one device


def sharded_rows(st, n_peers: int, devices) -> int:
    """Require every leaf with leading dim N to hold one N/len(devices)-row
    shard on each of ``devices``; returns how many such leaves there are.
    Zero-size leaves (the [N, 0] fanout planes of a fanout-free build)
    hold no rows to place and are skipped."""
    import jax

    n_dev = len(devices)
    count = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(st)[0]:
        if leaf.ndim < 1 or leaf.shape[0] != n_peers or leaf.size == 0:
            continue
        count += 1
        shards = leaf.addressable_shards
        where = jax.tree_util.keystr(path)
        require(len(shards) == n_dev
                and {s.device for s in shards} == set(devices),
                f"{where}: shards on {[s.device for s in shards]}")
        require(all(s.data.shape[0] == n_peers // n_dev for s in shards),
                f"{where}: shard rows {[s.data.shape[0] for s in shards]}")
    require(count > 0, "no peer-axis leaf in the state")
    return count


def phase_sharded(n_peers: int, seg_rounds: int, devices) -> None:
    import jax
    import jax.numpy as jnp

    from go_libp2p_pubsub_tpu.ops import edges
    from go_libp2p_pubsub_tpu.parallel import collective_ops, collective_profile
    from go_libp2p_pubsub_tpu.perf.sweep import (
        bench_cell,
        bench_schedule,
        make_bench_scan,
    )

    r = BENCH_R
    n_dev = len(devices)

    def build(devs):
        return bench_cell(n_peers, BENCH_M, config="default",
                          heartbeat_every=r, rounds_per_phase=r, devices=devs)

    # the halo budget of the compiled phase step, counted under the
    # bench's PRNG (threefry's sharded lowering adds rng permutes the
    # bench never pays — __graft_entry__.dryrun_multichip)
    jax.config.update("jax_default_prng_impl", BENCH_PRNG)
    cell = build(devices)
    shape = (r, 4)
    tally: list = []
    with edges.tally_halo_gathers(tally):
        lowered = cell.step.lower(
            cell.state, jnp.zeros(shape, jnp.int32),
            jnp.zeros(shape, jnp.int32), jnp.ones(shape, bool),
            do_heartbeat=True)
    t0 = time.perf_counter()
    hlo = lowered.compile().as_text()
    step_compile_s = time.perf_counter() - t0
    prof = collective_profile(hlo)
    # Cross-peer traffic must be the lattice's halo and nothing else. The
    # XLA:CPU partitioner lowers each of the 16 band directions of a
    # gather set to exactly one collective-permute and no all-gather
    # (tests/test_collectives.py pins that count); the TPU compiler
    # splits the same rolls into more permutes and serves some halos by
    # all-gathering a row or two from every shard. Either way no
    # collective may carry more rows than the widest band reaches.
    moved = [(op, shape) for op, shape in collective_ops(hlo)
             if op != "all-reduce"]
    wide = [(op, shape) for op, shape in moved
            if not shape or shape[0] > n_dev * LATTICE_D]
    require(not wide, f"peer-sized collectives in the phase step: {wide[:8]}")
    require(len(moved) >= 2 * LATTICE_D * len(tally),
            f"{len(moved)} halo transfers for {len(tally)} gather sets of "
            f"{2 * LATTICE_D} band directions: {prof}")
    del cell, lowered, hlo

    # every state leaf equal to the one-device run's, under threefry
    # (unsafe_rbg draws differ with the sharding)
    jax.config.update("jax_default_prng_impl", PARITY_PRNG)
    finals, seconds, leaves = [], [], None
    for devs in (devices, devices[:1]):
        cell = build(devs)
        if len(devs) > 1:
            leaves = sharded_rows(cell.state, n_peers, devs)
        po, pt, pv = (jnp.asarray(a) for a in bench_schedule(
            n_peers, cell.n_topics, cell.honest, seg_rounds))
        scan, _ = make_bench_scan(cell.step, r, r)
        t0 = time.perf_counter()
        st = scan(cell.state, po, pt, pv)
        jax.block_until_ready(st)
        seconds.append(round(time.perf_counter() - t0, 3))
        if len(devs) > 1:
            # donation and GSPMD propagation must not collapse the
            # returned state onto one device
            require(sharded_rows(st, n_peers, devs) == leaves,
                    "peer-axis leaf count changed across the scan")
        require(int(st.core.tick) == seg_rounds, "tick != rounds executed")
        finals.append(st)
        del cell, scan
    differing = tree_equal(*finals)
    require(not differing,
            f"sharded and one-device states differ at {differing}")
    emit("sharded", n_peers=n_peers, devices=n_dev, rounds=seg_rounds,
         rows_per_shard=n_peers // n_dev, peer_axis_leaves=leaves,
         permute_sets_per_phase=len(tally), collectives=prof,
         widest_collective_rows=max(shape[0] for _, shape in moved),
         phase_step_compile_seconds=round(step_compile_s, 3),
         compile_and_run_seconds={"sharded": seconds[0],
                                  "one_device": seconds[1]},
         equal_to_one_device=True, parity_prng=PARITY_PRNG,
         peak_bytes_in_use=[peak_bytes(d) for d in devices])


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the peer-sharded cell and the "
                    "one-device run it is compared with")
    args = ap.parse_args(argv)

    import jax

    from go_libp2p_pubsub_tpu.compile_cache import enable_persistent_cache
    from go_libp2p_pubsub_tpu.perf.sweep import select_platform

    device = select_platform(None)      # a TPU, or RuntimeError: no carry-on
    devs = jax.devices()
    if len(devs) < args.chips:
        raise RuntimeError(f"--chips {args.chips} but jax found "
                           f"{len(devs)} device(s)")
    enable_persistent_cache()
    t0 = time.perf_counter()
    emit("start", **device, chips=args.chips,
         cache_dir=jax.config.jax_compilation_cache_dir, **versions())

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        if args.chips == 4:
            phase_sharded(100_000, 160, devs[:4])
        else:
            one = devs[:1]
            phase_engine(100_000, 1600, 3, inv_n=100_000, inv_rounds=160,
                         devices=one)
            phase_floodsub(2_048, 64, devs[0], jax.devices("cpu")[0])
            phase_api(20, tmp)
            phase_served(100_000, 20, 6, tmp, one)

    emit("done", seconds=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["device_kind"],
        "count": device["n_devices"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
