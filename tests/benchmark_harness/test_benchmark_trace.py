"""The reduction from a trace to busy / idle / gap attribution / top ops,
pinned on a trace recorded on the chip (three one-phase segments of
``lattice-100k.stepped`` on a TPU v5 lite, my chip run, PR 28: the
extract ``benchmark/tools/record_trace.py`` wrote) and on intervals small
enough to work out by hand."""

import os

import numpy as np
import pytest

from benchmark.harness import manifest as mf
from benchmark.harness import trace

RECORDED = os.path.join(mf.BENCH_DIR, "data",
                        "trace_v5e_lattice-100k_stepped3.json")


def test_recorded_chip_trace_reduces_to_pinned_numbers():
    tr = mf.load_json(RECORDED)
    assert tr["device_kind"] == "TPU v5 lite" and tr["rounds"] == 24
    assert list(tr["devices"]) == ["/device:TPU:0"]
    assert len(tr["devices"]["/device:TPU:0"]["ops"]) == 5959
    assert [s[0] for s in tr["spans"]] == [
        "xs_assembly", "dispatch", "summary_readback"] * 3
    red = trace.reduce(tr)
    # window: first harness span's start to the last one's end
    assert red["window_s"] == pytest.approx(0.079037248, rel=1e-9)
    # busy: union of the op intervals
    assert red["busy_s"] == pytest.approx(0.066847291, rel=1e-9)
    idle = dict(red["idle_gaps"])
    assert list(idle)[0] == "summary_readback"
    assert idle["summary_readback"] == pytest.approx(0.006369608, rel=1e-6)
    assert idle["xs_assembly"] == pytest.approx(0.005111963, rel=1e-6)
    assert idle["dispatch"] == pytest.approx(0.000269856, rel=1e-6)
    assert idle["between_segments"] == pytest.approx(0.00039686, rel=1e-5)
    assert idle["inside_program"] == pytest.approx(4.167e-05, rel=1e-3)
    # the parts of the idle time add up to window less busy
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-9)
    assert len(red["device_ops"]) == 10
    assert red["device_ops"][0][0] == "concatenate.205"
    assert red["device_ops"][0][1] == pytest.approx(0.020787481, rel=1e-9)
    assert red["device_ops"][1][0] == "slice_bitcast_fusion"
    # the readers on the same trace
    run = {"trace": red, "rounds": tr["rounds"], "rounds_per_phase": 8,
           "device_kind": tr["device_kind"],
           "state_shapes": [((100_000, 64), 4)]}
    per_round = mf.load_plugin("readers", "device_us_per_round").read(run)
    assert per_round == pytest.approx(1e6 * 0.066847291 / 24)
    idle_pct = mf.load_plugin("readers", "device_idle_pct").read(run)
    assert idle_pct == pytest.approx(100 * (1 - 0.066847291 / 0.079037248))


def test_reduction_by_hand():
    tr = {
        "devices": {"/device:TPU:0": {
            # a while op spanning two fusions, then a lone copy
            "ops": [["while.1", 100, 60], ["fusion.1", 100, 20],
                    ["fusion.2", 130, 30], ["copy.1", 200, 10]],
            "modules": [["jit_run(1)", 100, 60], ["jit_summary(2)", 200, 10]],
        }},
        "spans": [["xs_assembly", 50, 30], ["dispatch", 80, 15],
                  ["summary_readback", 95, 125], ["xs_assembly", 230, 20]],
    }
    red = trace.reduce(tr)
    assert red["window_s"] == pytest.approx(200e-9)
    assert red["busy_s"] == pytest.approx(70e-9)          # [100,160] + [200,210]
    idle = dict(red["idle_gaps"])
    assert idle["xs_assembly"] == pytest.approx(50e-9)
    assert idle["dispatch"] == pytest.approx(15e-9)
    # in summary_readback the device idles over [95,100], [160,200], [210,220]
    assert idle["summary_readback"] == pytest.approx(55e-9)
    assert idle["between_segments"] == pytest.approx(10e-9)   # [220,230]
    assert idle["inside_program"] == pytest.approx(0.0)
    ops = dict(red["device_ops"])
    # the while's own time is what its children leave: 60 - 20 - 30
    assert ops == pytest.approx({"fusion.2": 30e-9, "fusion.1": 20e-9,
                                 "while.1": 10e-9, "copy.1": 10e-9})


def test_interval_helpers_and_errors():
    s, e = trace.merge(np.array([5.0, 0.0, 2.0, 20.0]),
                       np.array([8.0, 3.0, 6.0, 21.0]))
    assert list(s) == [0.0, 20.0] and list(e) == [8.0, 21.0]
    assert float(trace.covered(s, e, 1.0, 20.5)) == 7.5
    assert list(trace.covered(s, e, [0.0, 9.0], [30.0, 19.0])) == [9.0, 0.0]
    assert trace.short_name(
        "%fusion.12 = u32[8]{0} fusion(u32[8]{0} %p), kind=kLoop") == "fusion.12"
    with pytest.raises(trace.TraceError):
        trace.reduce({"devices": {}, "spans": []})

    class Ev:
        def __init__(self, name, start, dur):
            self.name, self.start_ns, self.duration_ns = name, start, dur

    class Line:
        def __init__(self, name, events):
            self.name, self.events = name, events

    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    class Profile:
        planes = [
            Plane("/device:TPU:0", [
                Line("XLA Ops", [Ev("%copy.1 = u32[2]{0} copy(%x)", 10, 5)]),
                Line("XLA Modules", [Ev("jit_run(7)", 9, 8)]),
                Line("Steps", [Ev("0", 9, 8)])]),
            Plane("/host:CPU", [Line("python", [
                Ev("bench.dispatch", 8, 2), Ev("other", 0, 99)])]),
        ]

    got = trace.extract(Profile())
    assert got == {"devices": {"/device:TPU:0": {
        "ops": [["copy.1", 10, 5]], "modules": [["jit_run(7)", 9, 8]]}},
        "spans": [["dispatch", 8, 2]]}
    Profile.planes = Profile.planes[1:]
    with pytest.raises(trace.TraceError, match="host:CPU"):
        trace.extract(Profile())
