"""The north-star cell peer-sharded over four chips,
``random-100k.stepped-4chip``, at toy size on 4 of the suite's 8 host
devices: its builder refuses a program without the per-shard plan, its
window compiles once, the reference judges it (and catches the faults), the
sharded window's final state is the one-device window's under
``threefry2x32``, each gather set crosses in one exchange, and the three
readers of the exchange read a two-plane trace. One build of the sharded
window serves the file. No number here is a device metric."""

import copy
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import faults, halo, traffic
from benchmark.harness import manifest as mf
from benchmark.harness import stages as harness_stages
from go_libp2p_pubsub_tpu.ops import edges
from go_libp2p_pubsub_tpu.parallel import collective_profile
from go_libp2p_pubsub_tpu.perf import stages as program

MANIFEST = mf.load_manifest()
CELL = mf.find_cell(MANIFEST, "random-100k.stepped-4chip")
CONFIG = mf.load_config(MANIFEST, CELL["config"])
BUILDER = mf.load_plugin("builders", CONFIG["builder"])
DRIVER = mf.load_plugin("drivers", "segment_loop")
REFERENCE = mf.load_plugin("references", CONFIG["reference"])
MIX = mf.load_traffic(CELL["traffic"])
UNROLL = int(MIX["unroll_phases"])
N = 256
SHARDS = 4
SEED = 4500000113
READERS = ("part_us_exchange", "exchange_ici_pct", "remote_rows_per_round")


def build(devices, unroll=UNROLL, **config):
    """The cell's builder and ONE window for the file's runs."""
    built = BUILDER.build(dict(CONFIG, **config), SEED, devices, n_peers=N)
    window = built.make_window(unroll)
    built.make_window = lambda unroll: window
    return built, window


def judge(built, segments, fault=None):
    """``benchmark/run.py``'s path for one run: the driver, then the
    reference on the answers of the timed window."""
    jax.config.update("jax_default_prng_impl", built.config["prng_impl"])
    b = copy.copy(built)
    if fault:
        faults.plant(b, fault)
    run = DRIVER.run(b, MIX, SEED, 1e9, False, time.perf_counter(),
                     max_segments=segments)
    numbers = REFERENCE.check(run.pop("answers"), b.graph, b.subs, b.config,
                              run["tail"], run["rounds_run"], run["summaries"])
    return run, all(x["value"] <= x["limit"] for x in numbers)


@pytest.fixture(scope="module")
def sharded():
    """The cell's builder on 4 devices under the configuration's PRNG,
    and the program's entry for its window once it has run."""
    old = str(jax.config.jax_default_prng_impl)
    try:
        built, window = build(jax.devices()[:SHARDS])
        run, correct = judge(built, 7)
        (entry,) = [w for w in program.traced_windows() if w.jitted is window]
        yield built, window, run, correct, entry
    finally:
        jax.config.update("jax_default_prng_impl", old)


def test_the_configuration_is_random_100k_on_four_chips():
    """The pair ``random-100k.stepped`` / ``-4chip`` differs in the chip
    count alone: every key but the names, the chips and the notes is
    ``random-100k``'s, and the traffic is the same mix."""
    base = mf.load_config(MANIFEST, "random-100k")
    own = ("name", "source", "chips", "reduced", "assumed", "builder")
    assert ({k: v for k, v in CONFIG.items() if k not in own}
            == {k: v for k, v in base.items() if k not in own})
    assert (CONFIG["builder"], base["builder"]) == (
        "gossipsub_shard_plan", "gossipsub_phase")
    assert CONFIG["assumed"][:len(base["assumed"])] == base["assumed"]
    assert CONFIG["reduced"] == ["chips"] and CONFIG["chips"] == 4
    one = mf.find_cell(MANIFEST, "random-100k.stepped")
    assert (CELL["traffic"], CELL["chips"]) == (one["traffic"], 4)


@pytest.mark.parametrize("missing", ["plan_shards", "exchange"])
def test_the_builder_refuses_a_program_without_the_plan(monkeypatch,
                                                        missing):
    """A program with no per-shard plan of its tiered gather cannot run
    the cell: the builder says so before building anything, so such a
    commit fails the cell at once instead of compiling the partitioner's
    form of the gather."""
    if missing == "plan_shards":
        monkeypatch.delattr(edges, "plan_shards")
    else:
        monkeypatch.setattr(program, "PARTS", program.PARTS[:-1])
    with pytest.raises(RuntimeError, match="cannot run it"):
        BUILDER.build(CONFIG, SEED, jax.devices()[:SHARDS], n_peers=N)


def test_the_sharded_window_compiles_once_and_is_correct(sharded):
    built, window, run, correct, entry = sharded
    assert run["segments"] == 7 and run["window_compiles"] == 0
    assert window._cache_size() == 1           # the warm-up's, and no other
    assert correct
    # the gather sets took the per-shard plan: each an exchange, each
    # receiving (S - 1) pads of rows a shard
    plan = built.net.shard_plans(SHARDS)
    assert isinstance(plan, edges.ShardTiers)
    assert entry.remote_rows_per_dispatch == (
        entry.halo_exchanges_per_dispatch * (SHARDS - 1) * plan.pad) > 0


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_faults_on_four_devices_are_not_correct(sharded, fault):
    _, correct = judge(sharded[0], 3, fault=fault)
    assert not correct


@pytest.fixture(scope="module")
def threefry():
    """The cell's builder under ``threefry2x32`` on 4 devices and on one,
    each window run for two phases: the final states' leaves, and the
    one-device window's entry."""
    old = str(jax.config.jax_default_prng_impl)
    try:
        finals, entry = [], None
        for devices in (jax.devices()[:SHARDS], jax.devices()[:1]):
            built, window = build(devices, 1, prng_impl="threefry2x32")
            po, pt, pv = traffic.segment_schedule(MIX, SEED, 0, 16, N, 1)
            st = window(built.fresh(), jnp.asarray(po), jnp.asarray(pt),
                        jnp.asarray(pv))
            finals.append([np.asarray(
                jax.random.key_data(x) if jax.dtypes.issubdtype(
                    x.dtype, jax.dtypes.prng_key) else x)
                for x in jax.tree_util.tree_leaves(st)])
            (entry,) = [w for w in program.traced_windows()
                        if w.jitted is window]
        yield finals, entry
    finally:
        jax.config.update("jax_default_prng_impl", old)


def test_threefry_sharded_window_equals_one_device_bit_for_bit(threefry):
    """Under ``threefry2x32`` the draws do not depend on the sharding, so
    the sharded window's final state is the one-device window's."""
    finals, _ = threefry
    assert len(finals[0]) == len(finals[1])
    for a, b in zip(*finals):
        assert np.array_equal(a, b)


def test_one_exchange_a_gather_set_and_no_all_gather(sharded):
    """The partitioned module holds S - 1 permutes for each gather set a
    step call runs (the ``r`` sub-rounds' and the control head's) and
    GSPMD's few scalar reductions: no table crosses whole."""
    entry = sharded[4]
    assert entry.sharded and entry.dispatches == MIX["segment_phases"]
    args, kwargs = jax.tree_util.tree_unflatten(*entry.signature)
    text = entry.jitted.lower(*args, **kwargs).compile().as_text()
    prof = collective_profile(text)
    sets = CONFIG["rounds_per_phase"] + 1
    assert entry.halo_exchanges_per_dispatch == sets
    assert prof["collective-permute"] == (SHARDS - 1) * sets * UNROLL
    assert prof["all-gather"] == prof["all-to-all"] == 0
    assert all(results == (("pred", ()),)
               for _, op, results, *_ in entry.collective_rows()
               if op != "collective-permute")
    # the permutes stand in the exchange's part
    parts = entry.parts()
    assert {parts.get(name) for name, op in entry.collectives().items()
            if op == "collective-permute"} == {"exchange"}


def two_plane_run(entry, rounds=8):
    """A two-plane trace in ``harness/trace.extract``'s form: the window's
    module on each plane with three of the exchange's permutes (2 and 4 us
    of self time on the two planes), three ops of its part that are not
    collectives (1 and 3 us) and one op of another kind."""
    coll = sorted(n for n, op in (entry.collectives() or {}).items()
                  if op == "collective-permute")[:3]
    part = sorted(n for n, p in (entry.parts() or {}).items()
                  if p == "exchange" and n not in coll)[:3]
    other = next(n for n in entry.stages()
                 if n not in coll and n not in (entry.parts() or {}))
    devices = {}
    for d, (us, part_us) in enumerate(((2_000, 1_000), (4_000, 3_000))):
        ops = [[name, 100_000 + 10_000 * i, us] for i, name in enumerate(coll)]
        ops += [[name, 200_000 + 10_000 * i, part_us]
                for i, name in enumerate(part)]
        ops.append([other, 90_000, 5_000])
        devices[f"/device:TPU:{d}"] = {
            "ops": ops,
            "modules": [[entry.module_name + "(1)", 50_000, 500_000]]}
    return ({"device_trace": {"devices": devices, "spans": []},
             "rounds": rounds, "rounds_per_phase": 8,
             "device_kind": "TPU v5 lite"}, len(coll), len(part))


def read_all(run, windows, monkeypatch):
    monkeypatch.setattr(harness_stages, "traced_windows", lambda: windows)
    return {n: mf.load_plugin("readers", n).read(dict(run)) for n in READERS}


def test_exchange_readers_on_a_two_plane_trace(sharded, monkeypatch):
    entry = sharded[4]
    run, n_coll, n_part = two_plane_run(entry)
    assert n_coll == n_part == 3
    got = read_all(run, [entry], monkeypatch)
    # the collectives are the exchange's too: the mean over the planes of
    # both kinds of op, over 8 rounds
    coll_us = n_coll * (2.0 + 4.0) / 2 / 8
    assert got["part_us_exchange"] == pytest.approx(
        coll_us + n_part * (1.0 + 3.0) / 2 / 8)
    per_round = halo.bytes_per_dispatch(entry, 2) / 8
    assert got["exchange_ici_pct"] == pytest.approx(
        100 * per_round / (200e9 * coll_us * 1e-6))
    assert got["remote_rows_per_round"] == entry.remote_rows_per_dispatch / 8
    # no peak for the device, no share
    assert read_all(dict(run, device_kind="cpu"), [entry],
                    monkeypatch)["exchange_ici_pct"] is None


def test_exchange_readers_read_nothing_on_one_chip(threefry, monkeypatch):
    entry = threefry[1]
    assert not entry.sharded and entry.remote_rows_per_dispatch == 0
    run, _, _ = two_plane_run(entry)
    assert read_all(run, [entry], monkeypatch) == dict.fromkeys(READERS)

    class Old:          # a commit whose program gives no collective map
        module_name, sharded = entry.module_name, True
        stages = entry.stages
    assert read_all(run, [Old()], monkeypatch) == dict.fromkeys(READERS)
