"""``churn-100k`` at toy size on the CPU: the manifest's rules for the new
entries, the churn draw of ``harness/churn.py``, a sound toy run through
the cell's own builder and driver, every new number of the churn-aware
reference failing under a fault planted here, the four controls, the
builder's refusals and the part reader. No number here is a device
metric."""

import copy
import time

import jax
import numpy as np
import pytest

from benchmark.harness import churn
from benchmark.harness import manifest as mf

MANIFEST = mf.load_manifest()
CELL = mf.find_cell(MANIFEST, "churn-100k.stepped")
CONFIG = mf.load_config(MANIFEST, "churn-100k")
MIX = mf.load_traffic(CELL["traffic"])
builder = mf.load_plugin("builders", "gossipsub_churn")
driver = mf.load_plugin("drivers", MIX["driver"])
reference = mf.load_plugin("references", "gossipsub_churn")
N_TOY = 512
#: peers have left (from round 32 on), stayed away 8 to 24 heartbeats and
#: come back; the last returns are 3 and 4 phases old
SEGMENTS = 40
R = CONFIG["rounds_per_phase"]


def drive(segments=SEGMENTS, seed=5, control=None, config=CONFIG):
    """One toy run through the cell's own builder and driver."""
    built = builder.build(config, seed, jax.devices()[:1], n_peers=N_TOY,
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
    old = str(jax.config.jax_default_prng_impl)
    built, run = drive()
    jax.config.update("jax_default_prng_impl", old)
    return built, run


# ---------------------------------------------------------------------------
# the manifest and the file


def test_the_manifest_holds_the_new_entries_and_breaks_no_rule():
    assert mf.check_manifest(MANIFEST) == []
    # found by NAME: a later PR appends its own configuration and cell
    (entry,) = [c for c in MANIFEST["configs"] if c["name"] == "churn-100k"]
    assert entry["reduced"] == [] and CELL["config"] == entry["name"]
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert CELL["name"] == "churn-100k.stepped"
    assert CELL["chips"] == 1 and CELL["traffic"] == "stepped"
    assert CONFIG["reduced"] == [] and CONFIG["architecture"] is None
    for key in ("assumed", "guarantees", "churn", "timers", "catchup"):
        assert CONFIG[key], key
    # the network is random-100k's, key for key; the cells differ in
    # liveness alone
    base = mf.load_config(MANIFEST, "random-100k")
    for key in ("n_peers", "n_topics", "graph", "msg_slots", "mesh_params",
                "score", "rounds_per_phase", "heartbeat_every", "prng_impl",
                "score_dtype", "score_enabled", "mesh_build_rounds", "limits"):
        assert CONFIG[key] == base[key], key


# ---------------------------------------------------------------------------
# the draw


def test_the_draw_is_a_function_of_its_seed_however_it_is_asked():
    spec = CONFIG["churn"]
    whole = churn.liveness(11, 60, 2000, spec, R)
    assert whole.dtype == bool and whole.shape == (60, 2000)
    proc = churn.Process(11, 2000, spec, R)
    parts = np.concatenate([proc.rows(1), proc.rows(7), proc.rows(52)])
    assert np.array_equal(whole, parts)
    assert not np.array_equal(whole, churn.liveness(12, 60, 2000, spec, R))
    # nobody leaves before start_round, and whoever leaves stays away a
    # whole number of heartbeats inside the file's range, then returns
    assert whole[:spec["start_round"] // R].all()
    lo, hi = spec["down_heartbeats"]
    for peer in np.flatnonzero(~whole.all(axis=0))[:50]:
        rows = np.flatnonzero(~whole[:, peer])
        runs = np.split(rows, np.flatnonzero(np.diff(rows) > 1) + 1)
        for run in runs[:-1]:
            assert lo <= run.size <= hi
        assert runs[-1].size <= hi
    with pytest.raises(ValueError, match="down_heartbeats"):
        churn.check(dict(spec, down_heartbeats=[0, 3]))


def test_the_draw_settles_where_the_file_says_at_the_files_size():
    spec = CONFIG["churn"]
    n = CONFIG["n_peers"]
    t0 = time.perf_counter()
    hist = churn.liveness(3, 120, n, spec, R)
    assert (time.perf_counter() - t0) / 120 < 5e-3           # a row, seconds
    down = 1.0 - hist.mean(axis=1)
    # 0.25 % of the up peers a heartbeat, away 16 on average: 3.85 % down
    assert down[60:].mean() == pytest.approx(0.0385, abs=0.002)
    moved = churn.stats(hist)
    assert moved["peers_left"] == pytest.approx(0.0025 * 0.97 * n * 116, rel=0.03)
    assert 0 < moved["peers_returned"] < moved["peers_left"]
    assert moved["down_share_end"] == pytest.approx(down[-1])
    since = churn.up_since(hist)
    assert (since[~hist[-1]] == 120).all() and (since[hist.all(axis=0)] == 0).all()
    back = np.flatnonzero(hist[-1] & ~hist.all(axis=0))[:100]
    assert all(hist[since[p]:, p].all() and not hist[since[p] - 1, p]
               for p in back)


# ---------------------------------------------------------------------------
# the reference: a sound run, and each new number under a planted fault


def test_a_sound_toy_run_is_correct_and_the_lifecycle_has_run(sound):
    built, run = sound
    numbers = judge(built, run)
    assert not failed(numbers), {k: numbers[k] for k in failed(numbers)}
    assert numbers["peers_left"]["value"] > 20
    assert numbers["peers_returned"]["value"] > 10
    assert numbers["down_share_end"]["value"] == numbers["down_share_state"]["value"] > 0
    assert numbers["catchup_pairs_judged"]["value"] > 100
    assert numbers["delivery_pairs_judged"]["value"] > 5000
    assert numbers["catchup_missed_share"]["value"] < 0.05
    assert run["window_compiles"] == 0
    ans = run["answers"]
    assert not ans["up"].all() and ans["rows_sent"] == run["rounds_run"] // R


def _history(run):
    return churn.liveness(run["answers"]["churn_seed"],
                          run["rounds_run"] // R, N_TOY, CONFIG["churn"], R)


def alter(ans, built, run, name):
    """Plant fault ``name`` in a copy of the answers."""
    a = copy.deepcopy(ans)
    hist = _history(run)
    up = hist[-1]
    since = churn.up_since(hist) * R
    nbr, ok = built.graph["nbr"], built.graph["nbr_ok"]
    nbr0 = np.clip(nbr, 0, None)
    birth, origin = a["msg_birth"], a["msg_origin"]
    down = np.flatnonzero(~up)
    dead_edge = ok & up[:, None] & ~up[nbr0]    # an up holder, a down far end
    bit = lambda s: (s // 32, np.uint32(1 << (s % 32)))
    if name == "bit_on_a_down_peer":
        a["have"][down[0], 0] |= np.uint32(1)
    elif name == "mesh_edge_to_a_down_peer":
        n, k = np.argwhere(dead_edge)[0]
        a["mesh"][n, 0, k] = True
    elif name == "receipt_before_the_return":
        peer = int(np.argmax(np.where(up, since, -1)))      # the last back
        s = int(np.flatnonzero((birth >= 0) & (birth < since[peer] - 1)
                               & (a["first_round"][peer] < 0))[0])
        w, b = bit(s)
        a["have"][peer, w] |= b
        a["first_round"][peer, s] = since[peer] - 1
    elif name == "copy_over_a_dead_edge":
        # re-point one first arrival at an edge whose far end was down
        for s in np.flatnonzero(birth >= 0):
            fr = a["first_round"][:, s]
            got = np.flatnonzero((fr > birth[s]) & (np.arange(N_TOY) != origin[s]))
            was_down = ok[got] & ~hist[(fr[got] // R)[:, None], nbr0[got]]
            hit = np.flatnonzero(was_down.any(axis=1))
            if hit.size:
                peer = int(got[hit[0]])
                w, b = bit(int(s))
                a["fe_words"][peer, :, w] &= ~b
                a["fe_words"][peer, int(np.argmax(was_down[hit[0]])), w] |= b
                break
        else:
            raise AssertionError("no arrival beside a down neighbour")
    elif name == "down_origins_message_held":
        s = int(np.flatnonzero(
            (birth >= 0) & ~hist[np.clip(birth, 0, None) // R,
                                 np.clip(origin, 0, None)])[0])
        w, b = bit(s)
        a["have"][origin[s], w] |= b
        a["first_round"][origin[s], s] = birth[s]
    elif name == "catch_up_dropped":
        phases = hist.shape[0]
        for back in (3, 4):
            pr = phases - back
            who = np.flatnonzero(hist[pr:].all(axis=0) & ~hist[pr - 1])
            old = np.flatnonzero((birth >= 0) & (birth < pr * R))
            for s in old:
                w, b = bit(int(s))
                a["have"][who, w] &= ~b
                a["fe_words"][who, :, w] &= ~b
            a["first_round"][np.ix_(who, old)] = -1
    elif name == "stat_on_a_dead_edge":
        n, k = np.argwhere(dead_edge & (a["bp"] <= 0))[0]
        a["fmd"][n, 0, k] = 1.0
    elif name == "backoff_from_before_the_crash":
        peer = int(np.flatnonzero(up & (since > 0))[0])
        a["backoff_present"][peer, 0, 0] = True
        a["backoff_expire"][peer, 0, 0] = since[peer] - R + 60
    elif name == "state_up_flipped":
        a["up"][down[0]] = True
    else:
        raise KeyError(name)
    return a


@pytest.mark.parametrize("fault,number", [
    ("bit_on_a_down_peer", "down_holders"),
    ("mesh_edge_to_a_down_peer", "down_in_mesh"),
    ("receipt_before_the_return", "stale_receipt"),
    ("copy_over_a_dead_edge", "causality"),
    ("down_origins_message_held", "down_origin_holders"),
    ("catch_up_dropped", "catchup_missed_share"),
    ("stat_on_a_dead_edge", "dead_edge_stats"),
    ("backoff_from_before_the_crash", "stale_backoff"),
    ("state_up_flipped", "up_mismatch"),
])
def test_each_new_number_fails_under_its_planted_fault(sound, fault, number):
    built, run = sound
    numbers = judge(built, run, alter(run["answers"], built, run, fault))
    assert number in failed(numbers), (fault, failed(numbers))


def _mesh_of(ans, built, run, n_out, n_in):
    """The answers with peer ``p``'s mesh made of ``n_out`` neighbours it
    dialled and ``n_in`` that dialled it, all up."""
    a = copy.deepcopy(ans)
    up = _history(run)[-1]
    ok, outb = built.graph["nbr_ok"], built.graph["outbound"]
    live = ok & up[:, None] & up[np.clip(built.graph["nbr"], 0, None)]
    fits = ((live & outb).sum(axis=1) >= n_out) & ((live & ~outb).sum(axis=1)
                                                   >= n_in)
    p = int(np.flatnonzero(fits)[0])
    a["mesh"][p, 0] = False
    a["mesh"][p, 0, np.flatnonzero(live[p] & outb[p])[:n_out]] = True
    a["mesh"][p, 0, np.flatnonzero(live[p] & ~outb[p])[:n_in]] = True
    return a


@pytest.mark.parametrize("members,breach", [
    # (D_hi, D_out) -> (members the peer dialled, members that dialled it)
    (lambda hi, out: (1, hi), False),       # D_hi + 1: the outbound top-up
    (lambda hi, out: (out, hi), False),     # D_hi + D_out: the most it leaves
    (lambda hi, out: (out + 1, hi - out), True),   # D_hi + 1 with D_out
                                            # outbound in it and one more
    (lambda hi, out: (0, hi + 1), True),    # over D_hi by a peer that dialled IN
], ids=["topped_up_by_one", "topped_up_by_D_out", "outbound_over_D_out",
        "inbound_over_D_hi"])
def test_mesh_degree_out_says_what_the_heartbeat_does(sound, members, breach):
    """Upstream tops up the outbound quota AFTER it pruned to D, on any mesh
    of D_lo or more (gossipsub.go:1451-1476): such a mesh over D_hi is the
    protocol's own (seed 3800000214 read 1 for it on the chip, PR 38);
    another over D_hi is a breach, counted once."""
    built, run = sound
    mp = CONFIG["mesh_params"]
    assert judge(built, run)["mesh_degree_out"]["value"] == 0
    planted = _mesh_of(run["answers"], built, run,
                       *members(mp["D_hi"], mp["D_out"]))
    assert judge(built, run, planted)["mesh_degree_out"]["value"] == int(breach)


@pytest.mark.parametrize("control,caught_by", [
    ({"program_static_peers": True}, {"up_mismatch", "down_holders"}),
    ({"program_mesh_params": {"D_lazy": 0, "gossip_factor": 0.0}},
     {"catchup_missed_share", "ihave_mismatch"}),
    ({"program_mesh_params": {"D": 3, "D_lo": 2, "D_hi": 4, "D_score": 1,
                              "D_out": 1}}, {"mesh_degree_out"}),
    ({"publish_gate_off": True}, {"down_origin_holders"}),
])
def test_each_control_comes_out_not_correct_by_its_number(control, caught_by):
    """The program built without the liveness plane under the same rows,
    with gossip switched off, with a mesh of D = 3, and with a down
    origin's publish put back where a program without the gate left it."""
    config = CONFIG
    if "publish_gate_off" in control:
        # enough down origins in 40 live rounds at 512 peers
        config = dict(CONFIG, churn=dict(CONFIG["churn"], leave_prob=0.02))
    built, run = drive(40, control=control, config=config)
    numbers = judge(built, run)
    assert caught_by <= failed(numbers), failed(numbers)


# ---------------------------------------------------------------------------
# the builder


def test_the_builder_refuses_a_program_without_the_gate_or_with_other_timers(
        monkeypatch):
    from go_libp2p_pubsub_tpu.perf import stages as program

    timers = dict(CONFIG["timers"])
    timers["prune_backoff"] = dict(timers["prune_backoff"], rounds=480)
    with pytest.raises(RuntimeError, match="cannot run it") as err:
        builder.build(dict(CONFIG, timers=timers), 1, jax.devices()[:1],
                      n_peers=64)
    assert "prune_backoff" in str(err.value)
    monkeypatch.setattr(program, "PARTS", ("fanout", "attrib", "gater"))
    with pytest.raises(RuntimeError, match="no churn part"):
        builder.build(CONFIG, 1, jax.devices()[:1], n_peers=64)


def test_the_window_draws_its_rows_inside_the_call_and_counts_them(sound):
    built, run = sound
    window = built.window
    assert window.process.phase == run["rounds_run"] // R == SEGMENTS + 1
    assert np.array_equal(window.last_row, _history(run)[-1])
    assert np.array_equal(run["answers"]["up"], window.last_row)
    # the carried state, which ``hbm_floor_pct`` is counted from, holds
    # the liveness plane
    assert ((N_TOY,), 1) in run["state_shapes"]


# ---------------------------------------------------------------------------
# the part


def test_the_dynamic_window_has_the_churn_part_and_the_reader_reads_it(
        sound, monkeypatch):
    from go_libp2p_pubsub_tpu.perf import stages as program

    built, run = sound
    ours = [w for w in program.traced_windows() if w.jitted is built.window.scan]
    window = ours[-1]
    part_of, stage_of = window.parts(), window.stages()
    assert set(part_of.values()) == {"churn"}
    # the part lies inside the stage its callers are: the head (the
    # transitions, the views, the publish gate). Since PR 38 the liveness
    # code crosses once a phase as a 2-bit code through the planned edge
    # gather, booked to edge_gather today; a build that carries the bits
    # inside the head's own wire exchange books no op of the part there,
    # so only the head is held
    assert {stage_of[i] for i in part_of} >= {"control_head"}
    monkeypatch.setattr(program, "traced_windows", lambda: [window])
    ops = sorted(part_of)[:10]
    fake = dict(run, device_trace={"devices": {"/device:TPU:0": {
        "ops": [[n, 100 + 10 * i, 10] for i, n in enumerate(ops)],
        "modules": [[window.module_name + "(7)", 100, 1000]]}}, "spans": []})
    read = mf.load_plugin("readers", "part_us_churn").read
    assert read(fake) == pytest.approx(1e6 * 100e-9 / run["rounds"])
    assert mf.load_plugin("readers", "part_us_attrib").read(fake) == 0.0
    assert read({"rounds": 4}) is None
