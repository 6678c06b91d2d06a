"""``eth2-100k`` at toy size on the CPU: the graph and subscriptions of
``harness/subnets.py``, the topic-aware reference's new numbers each
failing under a fault planted here, the ``fanout_slots`` 0 control, and
the part reader. No number here is a device metric."""

import time

import jax
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.harness import faults, parts, subnets
from benchmark.harness import manifest as mf
from go_libp2p_pubsub_tpu.perf import stages as program

MANIFEST = mf.load_manifest()
CELL = "eth2-100k.stepped"
CONFIG = mf.load_config(MANIFEST, "eth2-100k")
reference = mf.load_plugin("references", "gossipsub_subnets")


def toy(seed, segments, n=256, **overrides):
    cell = mf.find_cell(MANIFEST, CELL)
    out = bench_run.measure(
        MANIFEST, cell, seed, 1e9, False, jax.devices()[:1],
        time.perf_counter(),
        overrides=dict(overrides, n_peers=n, max_segments=segments))
    return out["result"]


def failed_numbers(result):
    return {x["name"] for x in result["compared"] if x["value"] > x["limit"]}


def value(result, name):
    (x,) = [x for x in result["compared"] if x["name"] == name]
    return x["value"]


# ---------------------------------------------------------------------------
# graph and subscriptions


@pytest.mark.parametrize("n", [40, 256, 2000])
def test_subnet_graph_is_a_symmetric_involution(n):
    g, s = subnets.build(CONFIG, n)
    nbr, rev, ok = g["nbr"], g["rev"], g["nbr_ok"]
    rows = np.arange(n)[:, None].repeat(nbr.shape[1], 1)
    back = nbr[np.clip(nbr, 0, None), rev]
    assert np.array_equal(back[ok], rows[ok])
    assert np.array_equal(g["outbound"][ok],
                          ~g["outbound"][np.clip(nbr, 0, None), rev][ok])
    assert (nbr[ok] != rows[ok]).all()
    assert ok.sum(axis=1).max() == nbr.shape[1]         # K: no empty column
    assert ok.sum(axis=1).min() >= min(10, n - 1)
    again, _ = subnets.build(CONFIG, n)
    assert np.array_equal(again["nbr"], nbr)
    # two distinct topics a peer, ascending, and the three views agree
    mt = s["my_topics"]
    assert mt.shape == (n, 2) and (mt[:, 0] < mt[:, 1]).all()
    assert (s["subscribed"].sum(axis=1) == 2).all()
    assert np.array_equal(np.nonzero(s["slot_of"] >= 0),
                          np.nonzero(s["subscribed"]))
    assert (s["slot_of"][np.arange(n)[:, None], mt] == [0, 1]).all()


def test_subnet_dials_clamp_to_the_members_there_are():
    # n = 40 peers over 64 topics: topics of 0, 1, 2, 3 members
    g, s = subnets.build(CONFIG, 40)
    sub = s["subscribed"]
    members = sub.sum(axis=0)
    assert {0, 1, 2, 3} <= set(members.tolist())
    co = (sub[np.clip(g["nbr"], 0, None)] & g["nbr_ok"][:, :, None]).sum(axis=1)
    # each peer dialed min(d_subnet, members - 1) co-subscribers itself
    for t in range(64):
        mine = np.flatnonzero(sub[:, t])
        assert (co[mine, t] >= min(5, len(mine) - 1)).all()
    # a big topic: five DISTINCT others each, all of them members
    rng = np.random.default_rng(0)
    picks = subnets.draw_others(rng, 9, 5)
    assert picks.shape == (9, 5)
    assert all(len(set(row)) == 5 and i not in row
               for i, row in enumerate(picks.tolist()))
    assert subnets.draw_others(rng, 1, 5).shape == (1, 0)
    assert subnets.draw_others(rng, 3, 5).tolist() == [[1, 2], [0, 2], [0, 1]]


def test_the_program_draws_the_same_graph_from_the_same_parameters():
    from go_libp2p_pubsub_tpu import graph

    g, s = subnets.build(CONFIG, 700)
    subs = graph.Subscriptions(s["subscribed"], s["my_topics"], s["slot_of"])
    topo = graph.subnet_connect(subs, d_any=10, d_subnet=5, seed=1)
    assert np.array_equal(topo.nbr, g["nbr"])
    assert np.array_equal(topo.rev, g["rev"])
    assert np.array_equal(topo.outbound, g["outbound"])


def test_co_subscribers_and_the_routable_share():
    g, s = subnets.build(CONFIG, 4000)
    nbr = np.clip(g["nbr"], 0, None)
    co = np.stack([
        (s["subscribed"][nbr, s["my_topics"][:, j][:, None]] & g["nbr_ok"]).sum(1)
        for j in range(2)], axis=1)
    assert co.min() >= 5 and 8.0 < co.mean() < 13.0
    reach = reference.routable(g, s)
    assert reach[s["subscribed"]].all()
    # a uniform publish comes from outside its topic 31 times in 32, and
    # some 6 in 10 of all have somewhere to go
    assert 0.5 < reach.mean() < 0.75
    by_hand = s["subscribed"][5] | s["subscribed"][g["nbr"][5][g["nbr_ok"][5]]].any(0)
    assert np.array_equal(reach[5], by_hand)


# ---------------------------------------------------------------------------
# the reference's own pieces


def test_fanout_table_keeps_the_most_recent_topics():
    subs = {"subscribed": np.zeros((4, 8), bool)}
    subs["subscribed"][0, 1] = True
    tail = {"start": 10,
            "origin": np.array([[0, 0, 1, 1], [0, 1, 1, 1], [1, 2, 2, 2]]),
            "topic": np.array([[1, 2, 3, 4], [2, 5, 3, 3], [6, 0, 1, 2]])}
    got = reference.fanout_table(tail, subs, 2)
    # peer 0: topic 1 is its own (no slot); topic 2 published again in 11
    # peer 1: round 10 {3, 4}; round 11: 5 takes 3's slot, 3 takes 4's,
    #   3 again; round 12: 6 takes 5's -> {3: 11, 6: 12}
    # peer 2: three new topics in one round: the first is evicted unsent
    assert got["held"] == {0: {2: 11}, 1: {3: 11, 6: 12}, 2: {1: 12, 2: 12}}
    assert got["evicted"] == {(12, 2, 0)}


def test_mutual_mesh_reads_the_far_end_in_its_own_slot():
    # 0 - 1 - 2; topic 5 sits in slot 1 of peer 0 and in slot 0 of peer 1
    graph = {"nbr": np.array([[1, -1], [0, 2], [1, -1]]),
             "rev": np.array([[0, 0], [0, 0], [1, 0]]),
             "nbr_ok": np.array([[1, 0], [1, 1], [1, 0]], bool)}
    subs = {"my_topics": np.array([[2, 5], [5, 7], [5, 7]]),
            "slot_of": np.full((3, 8), -1)}
    subs["slot_of"][0, [2, 5]] = subs["slot_of"][1, [5, 7]] = [0, 1]
    subs["slot_of"][2, [5, 7]] = [0, 1]
    mesh = np.zeros((3, 2, 2), bool)
    mesh[0, 1, 0] = mesh[1, 0, 0] = True          # 0 <-> 1 on topic 5
    mesh[1, 1, 1] = True                          # 1 -> 2 on topic 7 only
    got = reference.mutual_mesh({"mesh": mesh}, graph, subs)
    want = np.zeros((3, 2, 2), bool)
    want[0, 1, 0] = want[1, 0, 0] = True
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# sound runs, and what must fail


def test_a_sound_run_judges_delivery_per_topic():
    result = toy(61, 14)
    assert result["correct"], result["compared"]
    names = [x["name"] for x in result["compared"]]
    assert {"off_topic_holders", "mesh_off_topic", "fanout_off_topic",
            "fanout_short", "fanout_slot_mismatch", "fanout_push_gap",
            "topic_undelivered", "topic_delivery_rounds_max",
            "publishes_routable_share", "publishes_fanout_share"} <= set(names)
    assert not {"undelivered", "delivery_rounds_max"} & set(names)
    assert 0 < value(result, "topic_delivery_rounds_max") < 12
    assert 0.3 < value(result, "publishes_routable_share") < 0.8
    assert value(result, "publishes_fanout_share") > 0.9


def _after(alter):
    """A fault of ``harness/faults.py``'s form: the window runs, then
    ``alter(state)`` puts the final state wrong."""
    def fault(window, state, po, pt, pv):
        return alter(window(state, po, pt, pv))
    return fault


def _stranger_in_a_fanout_slot(st):
    """A neighbour that does not subscribe the topic, in the first live
    fanout slot."""
    import jax.numpy as jnp

    g, s = subnets.build(CONFIG, st.fanout_topic.shape[0])
    ft = np.asarray(st.fanout_topic)
    peer, slot = map(int, np.argwhere(ft >= 0)[0])
    outside = ~s["subscribed"][g["nbr"][peer], ft[peer, slot]] & g["nbr_ok"][peer]
    k = int(np.flatnonzero(outside)[0])
    return st.replace(fanout_peers=jnp.asarray(st.fanout_peers)
                      .at[peer, slot, k].set(True))


def _mesh_edge_to_a_non_subscriber(st):
    import jax.numpy as jnp

    g, s = subnets.build(CONFIG, st.mesh.shape[0])
    outside = ~s["subscribed"][g["nbr"][7], s["my_topics"][7, 0]] & g["nbr_ok"][7]
    k = int(np.flatnonzero(outside)[0])
    return st.replace(mesh=jnp.asarray(st.mesh).at[7, 0, k].set(True))


def _refused_graft_with_an_old_time_in_mesh(st):
    """What the phase's head leaves when it refuses a GRAFT from a
    neighbour that was in the mesh before: the PRUNE answer in the
    outbox, and the time in mesh of the earlier membership untouched."""
    import jax.numpy as jnp

    g, _ = subnets.build(CONFIG, st.mesh.shape[0])
    free = (g["nbr_ok"][9] & ~np.asarray(st.mesh)[9, 0]
            & ~np.asarray(st.prune_out)[9, 0])
    k = int(np.flatnonzero(free)[0])
    score = st.score.replace(
        mesh_time=jnp.asarray(st.score.mesh_time).at[9, 0, k].set(4),
        graft_tick=jnp.asarray(st.score.graft_tick).at[9, 0, k].set(10))
    return st.replace(score=score,
                      prune_out=jnp.asarray(st.prune_out).at[9, 0, k].set(True))


def test_a_refused_graft_is_no_mesh_member_of_the_score_refresh(monkeypatch):
    """On the chip 3 seeds of 8 read ``score_gap`` 0.029 at round 144 (the
    lazy backoff clear of round 135 lets old mesh peers GRAFT again, and
    full meshes refuse them) while the mesh | PRUNE-outbox membership of
    ``references/gossipsub.py`` was in use: a gap of the reference's."""
    monkeypatch.setitem(faults.FAULTS, "planted",
                        _after(_refused_graft_with_an_old_time_in_mesh))
    result = toy(65, 8, fault="planted")
    assert result["correct"], result["compared"]
    assert value(result, "score_gap") == 0.0


@pytest.mark.parametrize("alter,caught_by", [
    (_stranger_in_a_fanout_slot, "fanout_off_topic"),
    (_mesh_edge_to_a_non_subscriber, "mesh_off_topic"),
], ids=["stranger_in_fanout_slot", "mesh_edge_off_topic"])
def test_a_planted_fault_is_not_correct(alter, caught_by, monkeypatch):
    monkeypatch.setitem(faults.FAULTS, "planted", _after(alter))
    result = toy(62, 8, fault="planted")
    assert not result["correct"]
    assert caught_by in failed_numbers(result)


def test_a_fanout_that_carries_nothing_is_not_correct(monkeypatch):
    """The fanout slots fill and are kept, and nothing rides them: what
    ``references/gossipsub.py``'s numbers would pass."""
    import jax.numpy as jnp

    from go_libp2p_pubsub_tpu.models import gossipsub_phase

    def nothing(fp_pack, k, fanout_topic, msg_topic):
        return jnp.zeros((fp_pack.shape[0], k, -(-msg_topic.shape[0] // 32)),
                         jnp.uint32)

    monkeypatch.setattr(gossipsub_phase, "fanout_carry_words_packed", nothing)
    result = toy(63, 14)
    assert not result["correct"]
    assert {"fanout_push_gap", "topic_undelivered"} <= failed_numbers(result)
    assert value(result, "fanout_push_gap") == 1.0
    # every other number of the base reference passes: it cannot see it
    assert failed_numbers(result) <= {
        "fanout_push_gap", "topic_undelivered", "topic_delivery_rounds_max"}


def test_control_no_fanout_slots_fails():
    sound = toy(64, 14, n=512)
    assert sound["correct"], sound["compared"]
    control = toy(64, 14, n=512, control={"fanout_slots": 0})
    assert not control["correct"]
    assert {"topic_undelivered", "fanout_slot_mismatch"} <= failed_numbers(control)
    # every routable publish from outside its topic stays at its origin:
    # as many subscribers short as such publishes times their topic's size
    assert value(control, "topic_undelivered") > 100


def test_a_builder_refuses_a_program_with_another_fanout_ttl():
    builder = mf.load_plugin("builders", "gossipsub_subnets")
    with pytest.raises(RuntimeError, match="cannot run it"):
        builder.build(dict(CONFIG, fanout_ttl_rounds=86), 1,
                      jax.devices()[:1], n_peers=64)


# ---------------------------------------------------------------------------
# the part reader


#: the live window's module, by the program's own name for it
W = "jit_" + program.window_name()


class Window:
    def __init__(self, module_name, part_of):
        self.module_name, self.part_of = module_name, part_of

    def parts(self):
        return self.part_of


TRACE = {"devices": {"/device:TPU:0": {
    "ops": [["while.1", 100, 60], ["fusion.1", 100, 20], ["fusion.2", 130, 30],
            ["fusion.1", 200, 10]],
    "modules": [[W + "(11)", 100, 75], ["jit_summary(2)", 200, 10]],
}}, "spans": []}


def test_part_seconds_by_hand():
    run = {"device_trace": TRACE, "rounds": 4}
    win = Window(W, {"fusion.2": "fanout"})
    assert parts.part_seconds(run, [win]) == pytest.approx({"fanout": 30e-9})
    assert parts.part_us_per_round(run, "fanout") == pytest.approx(1e6 * 30e-9 / 4)
    # a window that traced no fanout: the part reads 0, not nothing
    run = {"device_trace": TRACE, "rounds": 4}
    assert parts.part_seconds(run, [Window(W, {})]) == {}
    assert parts.part_us_per_round(run, "fanout") == 0.0
    # two device planes (a window sharded over chips): the mean over them
    (dev,) = TRACE["devices"].values()
    slower = dict(dev, ops=[[n, t, 2 * d] for n, t, d in dev["ops"]])
    run = {"device_trace": {"devices": {"a": dev, "b": slower}, "spans": []},
           "rounds": 4}
    assert parts.part_seconds(run, [win]) == pytest.approx(
        {"fanout": (30e-9 + 60e-9) / 2})


@pytest.mark.parametrize("windows,why", [
    ([], "no window traced"),
    ([object.__new__(type("Old", (), {"module_name": W}))],
     "a commit before the parts"),
    ([Window(W, None)], "sharded: the program gives no map"),
    ([Window(W, {}), Window(W, {})],
     "two windows of one name"),
    ([Window("jit_run", {})], "no module of that name ran"),
])
def test_no_part_seconds_where_there_is_no_one_map(windows, why):
    run = {"device_trace": TRACE, "rounds": 4}
    assert parts.part_seconds(run, windows) is None, why
    assert parts.part_us_per_round(run, "fanout") is None
    reader = mf.load_plugin("readers", "part_us_fanout")
    assert reader.read({"device_trace": None, "rounds": 4}) is None
    assert reader.read({"rounds": 4}) is None


def test_the_part_reader_on_a_toy_window(monkeypatch):
    before = set(map(id, program.traced_windows()))
    cell = mf.find_cell(MANIFEST, CELL)
    out = bench_run.measure(
        MANIFEST, cell, 5, 1e9, False, jax.devices()[:1], time.perf_counter(),
        overrides=dict(n_peers=256, max_segments=2))
    (window,) = [w for w in program.traced_windows() if id(w) not in before]
    monkeypatch.setattr(program, "traced_windows", lambda: [window])
    inside = sorted(window.parts())[:20]
    assert len(inside) == 20
    others = sorted(set(window.stages()) - set(window.parts()))[:30]
    run = dict(out["run"], device_trace={"devices": {"/device:TPU:0": {
        "ops": [[n, 100 + 10 * i, 10] for i, n in enumerate(inside + others)],
        "modules": [[window.module_name + "(7)", 100, 1000]]}}, "spans": []})
    reader = mf.load_plugin("readers", "part_us_fanout")
    assert reader.read(run) == pytest.approx(1e6 * 200e-9 / run["rounds"])
    # the stages still hold every op, the part's among them
    from benchmark.harness import stages
    assert sum(stages.stage_seconds(run).values()) == pytest.approx(500e-9)
    # edge rows: one gather for the control head and one a round
    rows = mf.load_plugin("readers", "edge_rows_per_round").read(run)
    assert rows > 0
