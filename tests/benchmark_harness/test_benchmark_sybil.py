"""``sybil-50k`` at toy size on the CPU: the sybil draw of
``harness/sybils.py``, every new number of the sybil-aware reference
failing under a fault planted here, the controls (a squatter that
forwards, the deficit switched off), the builder's refusal of a program
that counts a timer otherwise, and the two part readers. No number here
is a device metric."""

import copy
import time

import jax
import numpy as np
import pytest

from benchmark.harness import graphs, sybils
from benchmark.harness import manifest as mf

MANIFEST = mf.load_manifest()
CELL = mf.find_cell(MANIFEST, "sybil-50k.stepped")
CONFIG = mf.load_config(MANIFEST, "sybil-50k")
MIX = mf.load_traffic(CELL["traffic"])
builder = mf.load_plugin("builders", "gossipsub_sybil")
driver = mf.load_plugin("drivers", MIX["driver"])
reference = mf.load_plugin("references", "gossipsub_sybil")
N_TOY = 256
#: old enough for the squatters' share to be judged (the file's
#: ``sybil_mesh_share.after_rounds``), with the warm-up segment
LONG = CONFIG["sybil_mesh_share"]["after_rounds"] // 8


def drive(segments, seed=5, control=None):
    """One toy run through the cell's own builder and driver: the built
    configuration and the run, its answers still in it."""
    built = builder.build(CONFIG, seed, jax.devices()[:1], n_peers=N_TOY,
                          control=control)
    run = driver.run(built, MIX, seed, 1e9, False, time.perf_counter(),
                     max_segments=segments)
    return built, run


def judge(built, run, answers=None):
    numbers = reference.check(
        run["answers"] if answers is None else answers, built.graph,
        built.subs, built.config, run["tail"], run["rounds_run"],
        run["summaries"])
    return {x["name"]: x for x in numbers}


def failed(numbers):
    return {k for k, x in numbers.items() if x["value"] > x["limit"]}


@pytest.fixture(scope="module")
def sound():
    """A sound run long enough for the defence to have done its work."""
    old = str(jax.config.jax_default_prng_impl)
    built, run = drive(LONG)
    jax.config.update("jax_default_prng_impl", old)
    return built, run


# ---------------------------------------------------------------------------
# the draw


def test_the_draw_is_exact_and_a_function_of_its_seed():
    spec = CONFIG["sybils"]
    n = CONFIG["n_peers"]
    mask = sybils.draw(spec, n, n)
    assert mask.dtype == bool and mask.sum() == spec["count"] == 10_000
    assert spec["count"] == round(spec["fraction"] * n)
    assert np.array_equal(mask, sybils.draw(spec, n, n))
    other = sybils.draw(dict(spec, seed=spec["seed"] + 1), n, n)
    assert other.sum() == 10_000 and not np.array_equal(mask, other)
    # at another size the count follows the fraction
    assert sybils.draw(spec, 256, n).sum() == 51
    assert sybils.draw(spec, 2000, n).sum() == 400
    with pytest.raises(ValueError, match="behaviour"):
        sybils.draw(dict(spec, behaviour="lie"), n, n)


def test_the_honest_subgraph_is_connected_at_the_files_size():
    """On the numpy graph alone: the messages have to cross the graph the
    squatters leave, and the file states the graph it was drawn on."""
    n = CONFIG["n_peers"]
    g = graphs.build_graph(CONFIG["graph"], n)
    assert g["nbr"].shape == (n, CONFIG["graph"]["K"])
    assert int(g["nbr_ok"].sum()) == CONFIG["graph"]["edge_slots"]
    mask = sybils.draw(CONFIG["sybils"], n, n)
    reached = sybils.honest_component(g, mask)
    assert np.array_equal(reached, ~mask)
    # every honest peer sees the fraction among its neighbours on average
    seen = (mask[np.clip(g["nbr"], 0, None)] & g["nbr_ok"])[~mask]
    assert seen.sum() / g["nbr_ok"][~mask].sum() == pytest.approx(0.2, abs=0.005)
    # a cut the component does see: an honest peer all of whose
    # neighbours are sybils is left out
    walled = mask.copy()
    lone = int(np.flatnonzero(~mask)[7])
    walled[g["nbr"][lone][g["nbr_ok"][lone]]] = True
    assert not sybils.honest_component(g, walled)[lone]


# ---------------------------------------------------------------------------
# the reference: sound runs, and each new number under a planted fault


def test_a_sound_long_run_is_correct_and_the_defence_has_worked(sound):
    built, run = sound
    numbers = judge(built, run)
    assert not failed(numbers), {k: numbers[k] for k in failed(numbers)}
    share = numbers["sybil_mesh_share"]
    assert share["limit"] == CONFIG["sybil_mesh_share"]["limit"]
    assert share["value"] < share["limit"]
    assert 0.1 < numbers["publishes_sybil_share"]["value"] < 0.3
    assert {"undelivered", "delivery_rounds_max"}.isdisjoint(numbers)
    ans = run["answers"]
    # the paths the cell exists for have all run: P3 active and counted,
    # squatters scored below 0 and out of the honest meshes
    assert ans["mmd_active"].sum() > 100 and ans["mmd"].max() > 4
    assert ans["mfp"].max() > 0 and (ans["scores"] < 0).sum() > 100
    assert ans["gater_validate"].min() > 0 and ans["gater_deliver"].max() > 0


def alter(ans, built, run, name):
    """Plant fault ``name`` in a copy of the answers."""
    a = copy.deepcopy(ans)
    sybil = a["sybil"]
    honest = np.flatnonzero(~sybil)
    t_end = run["rounds_run"]
    mesh = a["mesh"][:, 0]
    birth, origin = a["msg_birth"], a["msg_origin"]
    if name == "negative_mesh_edge":
        n, k = np.argwhere(mesh)[0]
        a["scores"][n, k] = -1.0
    elif name == "mmd_dropped":
        a["mmd"] = np.zeros_like(a["mmd"])
    elif name == "p3b_dropped":
        a["mfp"] = np.zeros_like(a["mfp"])
    elif name == "activation_early":
        n, k = np.argwhere(mesh & (a["mesh_time"][:, 0] <= 80))[0]
        a["mmd_active"][n, 0, k] = True
    elif name == "queues_overflowed":
        over = CONFIG["limits"]["gater_throttled"] + 1
        a["gater_last_throttle"][honest[:over]] = t_end - 3
    elif name == "validate_dropped":
        a["gater_validate"] = np.zeros_like(a["gater_validate"])
    elif name == "invalid_counted":
        a["imd"][honest[0], 0, 0] = 1.0
    elif name in ("honest_forgets", "sybil_publish_spread"):
        from_sybil = name == "sybil_publish_spread"
        old = np.flatnonzero((birth >= 32) & (t_end - birth >= 16)
                             & (sybil[np.clip(origin, 0, None)] == from_sybil))
        s = int(old[0])
        w, b = divmod(s, 32)
        peer = honest[honest != origin[s]][5]
        if from_sybil:          # an honest peer holds a squatter's publish
            a["have"][peer, w] |= np.uint32(1 << b)
            a["first_round"][peer, s] = birth[s] + 2
        else:                   # an honest peer never got an honest one
            a["have"][peer, w] &= ~np.uint32(1 << b)
            a["first_round"][peer, s] = -1
            a["fe_words"][peer, :, w] &= ~np.uint32(1 << b)
    elif name == "came_from_a_sybil":
        # re-point one first arrival at a sybil neighbour's edge
        nbr, ok = built.graph["nbr"], built.graph["nbr_ok"]
        sybil_nbr = sybil[np.clip(nbr, 0, None)] & ok
        s = int(np.flatnonzero(
            (birth >= 0) & ~sybil[np.clip(origin, 0, None)])[0])
        w, b = divmod(s, 32)
        got = (a["first_round"][:, s] > birth[s]) & sybil_nbr.any(axis=1)
        peer = int(np.flatnonzero(got)[0])
        a["fe_words"][peer, :, w] &= ~np.uint32(1 << b)
        a["fe_words"][peer, int(np.argmax(sybil_nbr[peer])), w] |= np.uint32(1 << b)
    else:
        raise KeyError(name)
    return a


@pytest.mark.parametrize("fault,number", [
    ("negative_mesh_edge", "mesh_negative"),
    ("mmd_dropped", "mmd_short"),
    ("p3b_dropped", "score_gap"),
    ("activation_early", "activation_early"),
    ("queues_overflowed", "gater_throttled"),
    ("validate_dropped", "validate_short"),
    ("invalid_counted", "imd_nonzero"),
    ("honest_forgets", "honest_undelivered"),
    ("sybil_publish_spread", "sybil_origin_spread"),
    ("came_from_a_sybil", "sybil_sourced"),
])
def test_each_new_number_fails_under_its_planted_fault(sound, fault, number):
    built, run = sound
    numbers = judge(built, run, alter(run["answers"], built, run, fault))
    assert number in failed(numbers), (fault, failed(numbers))


def test_a_sybil_that_forwards_is_caught():
    """The program built with three of the drawn sybils left out of its
    adversary vector: they forward like anybody, and the reference, which
    knows who is a sybil, sees their copies."""
    built, run = drive(6, control={"program_sybils_forward": 3})
    numbers = judge(built, run)
    assert {"sybil_sourced", "sybil_origin_spread"} & failed(numbers)
    assert numbers["sybil_sourced"]["value"] > 0
    assert failed(numbers) <= {"sybil_sourced", "sybil_origin_spread"}


def test_with_the_deficit_off_the_squatters_stay_in_the_meshes():
    """The control ``program_score`` w3 = 0 at the length the share is
    judged at: P1 keeps every squatter's score above 0, no heartbeat
    prunes one, and ``sybil_mesh_share`` stays where the draw put it."""
    built, run = drive(LONG, control={
        "program_score": {"mesh_message_deliveries_weight": 0.0}})
    numbers = judge(built, run)
    assert "sybil_mesh_share" in failed(numbers)
    assert numbers["sybil_mesh_share"]["value"] > 0.12
    # the reference recomputes the scores with the FILE's weight, which
    # the program did not use
    assert failed(numbers) <= {"sybil_mesh_share", "score_gap"}


def test_a_young_run_prints_the_share_and_cannot_fail_it():
    built, run = drive(3)
    numbers = judge(built, run)
    assert not failed(numbers)
    assert numbers["sybil_mesh_share"]["limit"] == 1.0
    assert 0.1 < numbers["sybil_mesh_share"]["value"] < 0.3


# ---------------------------------------------------------------------------
# the builder


@pytest.mark.parametrize("timer,rounds", [
    ("p3_activation", 10), ("p3_window", 1), ("gater_quiet", 60),
    ("prune_backoff", 480), ("backoff_clear", 120),
])
def test_the_builder_refuses_a_program_that_counts_a_timer_otherwise(
        timer, rounds):
    """The file's rounds are the program's: stated in heartbeats (what the
    program built before its repair), or on a clock the program does not
    use, the builder runs nothing."""
    timers = dict(CONFIG["timers"])
    timers[timer] = dict(timers[timer], rounds=rounds)
    with pytest.raises(RuntimeError, match="cannot run it") as err:
        builder.build(dict(CONFIG, timers=timers), 1, jax.devices()[:1],
                      n_peers=64)
    assert timer in str(err.value)


def test_the_file_states_every_timer_in_seconds_and_in_rounds():
    from go_libp2p_pubsub_tpu.config import ticks_for

    he = CONFIG["heartbeat_every"]
    t = CONFIG["timers"]
    assert all(set(v) >= {"seconds", "rounds"} for v in t.values())
    # the three this configuration settled, counted in rounds
    assert t["p3_activation"]["rounds"] == ticks_for(10.0, 1.0) * he == 80
    assert t["p3_window"]["rounds"] == ticks_for(2.0, 1.0) * he - 1 == 15
    assert t["gater_quiet"]["rounds"] == ticks_for(60.0, 1.0) * he == 480
    sc = CONFIG["score"]
    assert sc["mesh_message_deliveries_activation_s"] == t["p3_activation"]["seconds"]
    assert sc["mesh_message_deliveries_window_s"] == t["p3_window"]["seconds"]
    assert CONFIG["gater"]["quiet_s"] == t["gater_quiet"]["seconds"]


# ---------------------------------------------------------------------------
# the parts


def test_the_window_has_both_parts_and_the_readers_read_them(sound, monkeypatch):
    from go_libp2p_pubsub_tpu.perf import stages as program

    built, run = sound
    ours = [w for w in program.traced_windows()
            if w.stages() is not None and "attrib" in set(w.parts().values())]
    window = ours[-1]
    part_of, stage_of = window.parts(), window.stages()
    assert set(part_of.values()) == {"attrib", "gater"}
    # each part lies inside the stages its callers are
    stages_of = lambda p: {stage_of[i] for i, q in part_of.items() if q == p}
    assert stages_of("attrib") >= {"data_round", "score"}
    assert stages_of("gater") >= {"data_round", "control_head", "heartbeat"}
    monkeypatch.setattr(program, "traced_windows", lambda: [window])
    attrib = sorted(i for i, q in part_of.items() if q == "attrib")[:10]
    gater = sorted(i for i, q in part_of.items() if q == "gater")[:5]
    fake = dict(run, device_trace={"devices": {"/device:TPU:0": {
        "ops": [[n, 100 + 10 * i, 10] for i, n in enumerate(attrib + gater)],
        "modules": [[window.module_name + "(7)", 100, 1000]]}}, "spans": []})
    read = lambda name: mf.load_plugin("readers", name).read(fake)
    assert read("part_us_attrib") == pytest.approx(1e6 * 100e-9 / run["rounds"])
    assert read("part_us_gater") == pytest.approx(1e6 * 50e-9 / run["rounds"])
    assert read("part_us_fanout") == 0.0
    for name in ("part_us_attrib", "part_us_gater"):
        assert mf.load_plugin("readers", name).read({"rounds": 4}) is None
