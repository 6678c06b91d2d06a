"""Device time by stage (``benchmark/harness/stages.py``) and the ten
readers on it: pinned on a run recorded on the chip (three one-phase
segments of ``random-100k.stepped`` on a TPU v5 lite with the stage map of
its compiled window, my chip run, PR 29: what
``benchmark/tools/record_stages.py`` wrote), on intervals small enough to
work out by hand, and on a toy run that counts the lowerings."""

import os
import time

import jax
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.harness import manifest as mf
from benchmark.harness import stages, trace
from go_libp2p_pubsub_tpu.perf import stages as program

MANIFEST = mf.load_manifest()
RECORDED = os.path.join(mf.BENCH_DIR, "data",
                        "trace_v5e_random-100k_stepped3.json")
STAGE_READERS = ["stage_us_" + s for s in (*program.STAGES, program.UNSCOPED)]
READERS = STAGE_READERS + ["kernels_per_round"]
#: the LIVE window's module, by the program's own name for it (a bump of
#: ``perf.stages.VERSION`` moves it); the recorded run keeps the name its
#: file holds
W = "jit_" + program.window_name()


#: the recorded run's reduction (my chip run, PR 29)
PINNED = {
    "ops": 9464,
    "seconds": {
        "edge_gather": 2.147731936, "unscoped": 0.186918505,
        "deliver": 0.056004131, "heartbeat": 0.046019363,
        "data_round": 0.027558272, "control_head": 0.010876189,
        "phase_tail": 0.005055565, "score": 0.001223116,
        "pub_plan": 0.001196607},
    "largest": "edge_gather",
}


class Window:
    """What the readers use of an entry of the program's registry."""

    def __init__(self, module_name, stage_of):
        self.module_name, self.stage_of, self.lowerings = module_name, stage_of, 0

    def stages(self):
        self.lowerings += 1
        return self.stage_of


def recorded_run():
    rec = mf.load_json(RECORDED)
    run = {"device_trace": {"devices": rec["devices"], "spans": rec["spans"]},
           "rounds": rec["rounds"]}
    return rec, run, Window(rec["module_name"], rec["stage_map"])


def test_every_new_metric_is_in_the_manifest_with_a_reader():
    # by NAME, wherever they stand: later PRs append their own metrics
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert len(by_name) == len(MANIFEST["per_layer"])
    assert set(READERS) <= set(by_name)
    for name in READERS:
        m = by_name[name]
        assert (m["source"], m["layer"], m["moves"], m["better"]) == (
            "device_trace", "engine", "rounds_per_s", "lower")
        assert m["unit"] == ("count" if name == "kernels_per_round" else "us")
        assert "workloads" not in m
        assert callable(mf.load_plugin("readers", name).read)


def test_stage_seconds_by_hand():
    tr = {"devices": {"/device:TPU:0": {
        # the window: a while spanning two fusions, then a copy; the
        # summary program has a fusion.1 of its own; one more window
        "ops": [["while.1", 100, 60], ["fusion.1", 100, 20],
                ["fusion.2", 130, 30], ["copy.9", 160, 10],
                ["fusion.1", 200, 10],
                ["fusion.1", 300, 25], ["late.3", 330, 5]],
        "modules": [[W + "(11)", 100, 75],
                    ["jit_summary(2)", 200, 10],
                    [W + "(11)", 300, 40]],
    }}, "spans": []}
    win = Window(W, {
        "while.1": "unscoped", "fusion.1": "edge_gather",
        "fusion.2": "deliver", "copy.9": "unscoped"})
    red = stages.reduce(tr, win)
    # the while's own time is what its children leave (60 - 20 - 30); the
    # summary's fusion.1 is left out; `late.3` is no instruction of the
    # compiled text: unscoped, and counted
    assert red["seconds"] == pytest.approx({
        "edge_gather": 45e-9, "deliver": 30e-9, "unscoped": 25e-9})
    assert red["ops"] == 6 and red["unmapped"] == ["late.3"]
    (dev,) = tr["devices"].values()
    assert [e[1] for e in stages.ops_inside(dev, "jit_summary")] == [200]
    assert stages.ops_inside(dev, "jit_other") == []
    assert stages.module_base(W + "(685704595)") == W

    run = {"device_trace": tr, "rounds": 4}
    assert stages.stage_seconds(run, [win]) == red["seconds"]
    assert stages.stage_us_per_round(run, "edge_gather") == pytest.approx(
        1e6 * 45e-9 / 4)
    assert stages.stage_us_per_round(run, "score") == 0.0
    assert win.lowerings == 2                  # reduce, then once per run


@pytest.mark.parametrize("windows,why", [
    ([], "no window traced"),
    ([Window("jit_run", {})], "a commit before the scopes"),
    ([Window(W, None)], "sharded: the program gives no map"),
    ([Window(W, {}), Window(W, {})],
     "two windows of one name"),
])
def test_no_stage_seconds_where_there_is_no_one_map(windows, why):
    tr = {"devices": {"/device:TPU:0": {
        "ops": [["fusion.1", 100, 20]],
        "modules": [[W + "(1)", 100, 60]]}}, "spans": []}
    run = {"device_trace": tr, "rounds": 8}
    assert stages.stage_seconds(run, windows) is None, why
    assert stages.stage_us_per_round(run, "deliver") is None


def test_two_device_planes_reduce_to_their_mean():
    """A window sharded over chips: each plane's ops inside the window's
    modules by stage, then the MEAN over the planes (``trace.reduce``'s
    rule for ``busy_s``); the same plane twice reads what it reads once;
    with no map, nothing."""
    a = {"ops": [["while.1", 100, 60], ["fusion.1", 100, 20],
                 ["fusion.2", 130, 30], ["fusion.1", 200, 10]],
         "modules": [[W + "(11)", 100, 75], ["jit_summary(2)", 200, 10]]}
    # the second chip: the same program, a slower gather, one op more (a
    # collective the text does not hold), its clock 1,000 ns on
    b = {"ops": [["while.1", 1100, 80], ["fusion.1", 1100, 40],
                 ["fusion.2", 1150, 20], ["all-reduce.7", 1170, 6]],
         "modules": [[W + "(11)", 1100, 90]]}
    stage_of = {"while.1": "unscoped", "fusion.1": "edge_gather",
                "fusion.2": "deliver"}
    one = stages.reduce({"devices": {"/device:TPU:0": a}, "spans": []},
                        Window(W, stage_of))
    assert one["seconds"] == pytest.approx({
        "edge_gather": 20e-9, "deliver": 30e-9, "unscoped": 10e-9})
    assert one["ops"] == 3 and one["unmapped"] == []
    two = {"devices": {"/device:TPU:0": a, "/device:TPU:1": b}, "spans": []}
    red = stages.reduce(two, Window(W, stage_of))
    assert red["seconds"] == pytest.approx({
        "edge_gather": (20e-9 + 40e-9) / 2, "deliver": (30e-9 + 20e-9) / 2,
        "unscoped": (10e-9 + (80 - 40 - 20 - 6 + 6) * 1e-9) / 2})
    assert red["ops"] == (3 + 4) / 2 and red["unmapped"] == ["all-reduce.7"]
    twice = stages.reduce({"devices": {"x": a, "y": a}, "spans": []},
                          Window(W, stage_of))
    assert twice["seconds"] == one["seconds"] and twice["ops"] == one["ops"]
    # the readers on it, and the program's answer for a sharded window today
    run = {"device_trace": two, "rounds": 4}
    assert stages.stage_seconds(run, [Window(W, stage_of)]) == red["seconds"]
    assert stages.stage_us_per_round(run, "edge_gather") == pytest.approx(
        1e6 * 30e-9 / 4)
    run = {"device_trace": two, "rounds": 4}
    assert stages.stage_seconds(run, [Window(W, None)]) is None


def test_recorded_chip_run_reduces_to_pinned_numbers():
    rec, run, win = recorded_run()
    assert rec["device_kind"] == "TPU v5 lite" and rec["rounds"] == 24
    assert rec["workload"] == "random-100k.stepped" and rec["segments"] == 3
    # the name the recorded file holds (PR 29's program: VERSION 1)
    assert win.module_name == rec["module_name"] == "jit_gs_window_v1"
    (dev,) = rec["devices"].values()
    inside = stages.ops_inside(dev, win.module_name)
    red = stages.reduce(run["device_trace"], win)
    assert red["ops"] == len(inside) == PINNED["ops"]
    assert red["unmapped"] == []
    # every op inside the window's modules is an instruction of the text
    assert {e[0] for e in inside} <= set(rec["stage_map"])
    # the harness's own programs ran too, and are left out
    assert len(dev["ops"]) > len(inside)
    assert {stages.module_base(m[0]) for m in dev["modules"]} == {
        rec["module_name"], "jit_summary"}
    # the stages sum to the busy time inside the window's modules (to
    # 5 ns of 2.48 s: a few events overlap their neighbour by a ns)
    arr = np.asarray([[e[1], e[1] + e[2]] for e in inside], np.float64)
    bs, be = trace.merge(arr[:, 0], arr[:, 1])
    assert sum(red["seconds"].values()) == pytest.approx(
        float(np.sum(be - bs)) * 1e-9, rel=1e-8)
    assert set(red["seconds"]) <= {*program.STAGES, program.UNSCOPED}
    for stage, sec in PINNED["seconds"].items():
        assert red["seconds"][stage] == pytest.approx(sec, rel=1e-9), stage
    assert max(red["seconds"], key=red["seconds"].get) == PINNED["largest"]


@pytest.fixture
def recorded(monkeypatch):
    _, run, win = recorded_run()
    monkeypatch.setattr(program, "traced_windows", lambda: [win])
    return run, win


@pytest.mark.parametrize("name", READERS)
def test_reader_on_the_recorded_run_and_without_a_trace(name, recorded):
    run, win = recorded
    reader = mf.load_plugin("readers", name)
    value = reader.read(run)
    assert isinstance(value, float) and value >= 0.0
    if name == "kernels_per_round":
        assert value == PINNED["ops"] / 24
    else:
        stage = name[len("stage_us_"):]
        assert value == pytest.approx(
            1e6 * PINNED["seconds"].get(stage, 0.0) / 24, rel=1e-9)
    assert reader.read({"device_trace": None, "rounds": 24}) is None
    assert reader.read({"rounds": 24}) is None


def test_ten_readers_one_reduction_and_the_stages_sum(recorded):
    run, win = recorded
    values = {n: mf.load_plugin("readers", n).read(run) for n in READERS}
    assert win.lowerings == 1
    busy_us = 1e6 * trace.reduce(run["device_trace"])["busy_s"] / 24
    total = sum(values[n] for n in STAGE_READERS)
    # the rest of the busy time is the harness's summary program
    assert 0.99 * busy_us < total <= busy_us


def test_an_untraced_run_lowers_nothing_and_a_traced_one_once(monkeypatch):
    """``--trace 0`` pays for no stage map: the readers are the only way to
    ``TracedWindow.stages``, and they run in a traced run alone, where ten
    of them lower the window once."""
    lowered = []
    real = program.TracedWindow.stages

    def counting(self):
        if self._stages is None:
            lowered.append(self)
        return real(self)

    monkeypatch.setattr(program.TracedWindow, "stages", counting)
    before = set(map(id, program.traced_windows()))
    cell = mf.find_cell(MANIFEST, "lattice-100k.steady")
    out = bench_run.measure(
        MANIFEST, cell, 5, 1e9, False, jax.devices()[:1], time.perf_counter(),
        overrides=dict(n_peers=256, max_segments=2))
    assert out["result"]["correct"] and lowered == []
    assert not set(out["result"]["metrics"]) & set(READERS)
    (window,) = [w for w in program.traced_windows() if id(w) not in before]
    assert window.module_name == W

    # the same run with a device trace (made up: the CPU has no device
    # plane), through the readers as `--trace 1` calls them
    names = sorted(real(window))[:50]
    lowered.clear()
    run = dict(out["run"], device_trace={"devices": {"/device:TPU:0": {
        "ops": [[n, 100 + 10 * i, 10] for i, n in enumerate(names)],
        "modules": [[W + "(7)", 100, 1000]]}}, "spans": []})
    monkeypatch.setattr(window, "_stages", None)
    # a test worker has traced other windows of the same name before this
    # one; the command's process makes one
    monkeypatch.setattr(program, "traced_windows", lambda: [window])
    values = {n: mf.load_plugin("readers", n).read(run) for n in READERS}
    assert len(lowered) == 1
    assert values["kernels_per_round"] == 50 / run["rounds"]
    assert sum(values[n] for n in STAGE_READERS) == pytest.approx(
        1e6 * 500e-9 / run["rounds"])
