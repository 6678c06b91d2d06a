"""``benchmark/readers/edge_rows_per_round.py``: the program's count of the
rows its edge gathers address by index, read off the traced window's entry
in ``perf.stages``; nothing where the registry has none."""

import time

import jax
import pytest

from benchmark import run as bench_run
from benchmark.harness import graphs
from benchmark.harness import manifest as mf
from go_libp2p_pubsub_tpu.ops import edges
from go_libp2p_pubsub_tpu.perf import stages as program

MANIFEST = mf.load_manifest()
READER = mf.load_plugin("readers", "edge_rows_per_round")
#: the live window's module, by the program's own name for it
W = "jit_" + program.window_name()
TRACE = {"devices": {"/device:TPU:0": {
    "ops": [["fusion.1", 100, 20]],
    "modules": [[W + "(1)", 100, 60]]}}, "spans": []}


def toy_window(monkeypatch, cell_name):
    """A toy run of ``cell_name`` and the one window it traced, as the
    command's process would hold it."""
    before = set(map(id, program.traced_windows()))
    cell = mf.find_cell(MANIFEST, cell_name)
    out = bench_run.measure(
        MANIFEST, cell, 5, 1e9, False, jax.devices()[:1], time.perf_counter(),
        overrides=dict(n_peers=256, max_segments=2))
    assert out["result"]["correct"]
    (window,) = [w for w in program.traced_windows() if id(w) not in before]
    monkeypatch.setattr(program, "traced_windows", lambda: [window])
    return dict(out["run"], device_trace=TRACE), out["run"], window


def test_edge_rows_of_a_toy_random_window(monkeypatch):
    run, untraced, window = toy_window(monkeypatch, "random-10k-t8.watched")
    r = run["rounds_per_phase"]
    config = mf.load_config(MANIFEST, "random-10k-t8")
    k = graphs.build_graph(config["graph"], 256)["nbr"].shape[1]
    # a toy net is not tiered (the fixed cost decides): every edge gather
    # of the phase addresses N*K rows, and there is one for the control
    # head and one for each delivery round
    assert window.edge_rows_per_dispatch == (r + 1) * 256 * k
    assert READER.read(run) == (r + 1) * 256 * k / r
    assert READER.read(untraced) is None        # no trace: `--trace 0`
    assert READER.read(dict(run, rounds_per_phase=0)) is None


def test_edge_rows_are_zero_on_the_lattice(monkeypatch):
    run, _, window = toy_window(monkeypatch, "lattice-100k.steady")
    assert window.edge_rows_per_dispatch == 0
    assert READER.read(run) == 0.0


class Window:
    def __init__(self, module_name, **fields):
        self.module_name = module_name
        self.__dict__.update(fields)


@pytest.mark.parametrize("windows,want,why", [
    (None, None, "a commit without the registry"),
    ([], None, "no window traced"),
    ([Window(W)], None, "a commit without the counter"),
    ([Window(W, edge_rows_per_dispatch=None)], None,
     "the trace replayed a step traced before it"),
    ([Window("jit_run", edge_rows_per_dispatch=8.0)], None,
     "its module did not run in the trace"),
    ([Window(W, edge_rows_per_dispatch=8.0)] * 2, None,
     "two windows of one name"),
    ([Window(W, edge_rows_per_dispatch=36_900_000.0)],
     4_612_500.0, "nine gathers of 4.1 M rows a phase of 8 rounds"),
])
def test_edge_rows_reader_by_hand(monkeypatch, windows, want, why):
    from benchmark.harness import stages

    monkeypatch.setattr(stages, "traced_windows", lambda: windows)
    run = {"device_trace": TRACE, "rounds_per_phase": 8, "rounds": 24}
    assert READER.read(run) == want, why


@pytest.mark.parametrize("tally,want", [
    ([], None),
    ([("edge", 10)], None),                              # no dispatch marked
    ([("dispatch", True), ("edge", 10), ("peer", 99), ("edge", 5)], 15.0),
    ([("dispatch", True), ("peer", 7)], 0.0),            # rolls, or no edge
    # the second call with a key replays the first one's jaxpr
    ([("dispatch", True), ("edge", 12), ("dispatch", False), ("edge", 6),
      ("dispatch", False)], 8.0),
    ([("dispatch", None)], None),                        # all replayed
])
def test_edge_rows_per_dispatch_by_hand(tally, want):
    assert edges.edge_rows_per_dispatch(tally) == want
