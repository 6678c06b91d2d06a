"""The seven ``setup_*`` per-layer metrics and the five older ones that
waited with them (``edge_rows_per_round``, the four ``part_us_*``): their
entries in ``BENCHMARK.json`` as their PRs gave them (PR 41 moved them in
from ``benchmark/data/per_layer_waiting.json``), the one reduction
``benchmark/harness/setup.py`` on spans written out by hand, and the
readers on a toy run's recorder."""

import json
import sys
import time

import jax
import pytest

from benchmark import run as bench_run
from benchmark.harness import manifest as mf
from benchmark.harness import setup
from go_libp2p_pubsub_tpu.perf import spans, stages

MANIFEST = mf.load_manifest()
#: found by NAME, wherever they stand: later PRs append their own
PER_LAYER = {m["name"]: m for m in MANIFEST["per_layer"]}
TABLE = {
    "setup_net_build_s": ("s", "program_span", "engine"),
    "setup_state_init_s": ("s", "program_span", "engine"),
    "setup_window_compile_s": ("s", "program_span", "window"),
    "setup_small_programs_s": ("s", "program_span", "engine"),
    "setup_programs_compiled": ("count", "program_counter", "window"),
    "setup_cache_misses": ("count", "program_counter", "window"),
    "setup_step_build_s": ("s", "program_span", "engine"),
}
OLDER = {"part_us_attrib": "sybil-50k.stepped",
         "part_us_gater": "sybil-50k.stepped",
         "part_us_churn": "churn-100k.stepped",
         "part_us_fanout": "eth2-100k.stepped",
         "edge_rows_per_round": None}
W = stages.window_name()


def test_the_names_are_the_reduction_s():
    assert tuple(TABLE) == setup.NAMES
    assert set(TABLE) | set(OLDER) <= set(PER_LAYER)


@pytest.mark.parametrize("name", TABLE)
def test_a_setup_entry_has_the_fields_of_the_table(name):
    unit, source, layer = TABLE[name]
    assert PER_LAYER[name] == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": layer, "moves": "setup_s"}
    assert callable(mf.load_plugin("readers", name).read)


@pytest.mark.parametrize("name", OLDER)
def test_an_older_entry_stands_as_its_pr_gave_it(name):
    want = {"name": name, "unit": "us", "better": "lower",
            "source": "device_trace", "layer": "engine",
            "moves": "rounds_per_s", "workloads": [OLDER[name]]}
    if OLDER[name] is None:
        want.update(unit="count", source="program_counter")
        del want["workloads"]
    assert PER_LAYER[name] == want
    assert callable(mf.load_plugin("readers", name).read)


def test_the_manifest_with_them_keeps_every_rule():
    assert mf.check_manifest(MANIFEST) == []
    assert len(PER_LAYER) == len(MANIFEST["per_layer"])
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}
    # all seven in every cell, whatever cells there are; the older ones
    # where their lists say, and in no other cell
    for cell in (w["name"] for w in MANIFEST["workloads"]):
        mine = {m["name"] for m in mf.cell_metrics(MANIFEST, cell, "per_layer")}
        assert set(TABLE) <= mine and "edge_rows_per_round" in mine
        for name, where in OLDER.items():
            assert (name in mine) == (where in (None, cell)), (name, cell)
    assert len(json.dumps(MANIFEST, indent=1)) < 64 * 1024


def S(name, start_s, end_s, **attrs):
    """A recorded span written out by hand (ids do not matter here)."""
    return (0, None, name, int(start_s * 1e9), int(end_s * 1e9), attrs)


#: one process, by hand: a net built in 5 s whose planes compile two
#: programs (0.5 + 0.25 s) and miss once; a state init of 2 s with one
#: program (1 s) loaded from the cache; the window traced 3 s, lowered 1 s,
#: compiled 8 s, missed; after it the summary program (0.125 s, a miss), a
#: second state init, and the window compiled AGAIN (the readers' stage
#: map: 4 s), with a miss of its own (there is none in life: a miss
#: without a compile)
BY_HAND = [
    S("compile.backend", 1.0, 1.5, fun_name="jit(convert)"),
    S("compile.cache_miss", 1.5, 1.5),
    S("compile.backend", 2.0, 2.25, fun_name="jit(iota)"),
    S("setup.net_build.plan", 0.5, 3.0),
    S("setup.net_build.planes", 3.0, 5.0),
    S("setup.net_build", 0.0, 5.0),
    S("setup.step_build", 5.0, 6.0),
    S("compile.cache_hit", 7.0, 7.0),
    S("compile.backend", 6.5, 7.5, fun_name="jit(_threefry)"),
    S("setup.state_init", 6.0, 8.0),
    S("compile.trace", 8.0, 11.0, fun_name=W),
    S("compile.lower", 11.0, 12.0, fun_name=f"jit({W})"),
    S("compile.cache_miss", 19.5, 19.5),
    S("compile.backend", 12.0, 20.0, fun_name=f"jit({W})"),
    S("compile.backend", 21.0, 21.125, fun_name="jit(summary)"),
    S("compile.cache_miss", 21.125, 21.125),
    S("setup.state_init", 30.0, 31.0),
    S("compile.lower", 40.0, 41.0, fun_name=f"jit({W})"),
    S("compile.backend", 41.0, 45.0, fun_name=f"jit({W})"),
    S("compile.cache_miss", 46.0, 46.0),
]


def test_the_reduction_by_hand():
    got = setup.reduce(BY_HAND, W)
    assert got == {
        "setup_net_build_s": 5.0,             # children included
        "setup_state_init_s": 2.0,            # the later one is left out
        "setup_window_compile_s": 12.0,       # 3 + 1 + 8, the first compile
        "setup_small_programs_s": 1.75,       # 0.5 + 0.25 + 1.0
        "setup_programs_compiled": 4,         # three small ones, the window
        "setup_cache_misses": 2,              # convert's and the window's
        "setup_step_build_s": 1.0,
    }
    assert list(got) == list(setup.NAMES)
    # the order of the recording does not matter, the end times do
    assert setup.reduce(BY_HAND[::-1], W) == got


def test_the_reduction_without_a_window_and_without_anything():
    # no window compiled: everything recorded counts
    upto = [s for s in BY_HAND if W not in str(s[5].get("fun_name", ""))]
    got = setup.reduce(upto, W)
    assert got["setup_window_compile_s"] == 0.0
    assert got["setup_state_init_s"] == 3.0
    assert got["setup_small_programs_s"] == 1.875
    assert got["setup_programs_compiled"] == 4
    assert got["setup_cache_misses"] == 4
    # the recorder holds nothing: numbers all the same, 0 of each
    assert setup.reduce([], W) == {
        "setup_net_build_s": 0.0, "setup_state_init_s": 0.0,
        "setup_window_compile_s": 0.0, "setup_small_programs_s": 0.0,
        "setup_programs_compiled": 0, "setup_cache_misses": 0,
        "setup_step_build_s": 0.0}
    # a window of another version of the scopes is another program
    assert setup.reduce(BY_HAND, "gs_window_v0")[
        "setup_window_compile_s"] == 0.0


@pytest.mark.parametrize("name", TABLE)
def test_a_reader_gives_nothing_on_a_commit_without_the_recorder(
        name, monkeypatch):
    import go_libp2p_pubsub_tpu.perf as perf

    monkeypatch.setitem(sys.modules, "go_libp2p_pubsub_tpu.perf.spans", None)
    monkeypatch.delattr(perf, "spans")
    assert mf.load_plugin("readers", name).read({}) is None


def test_a_toy_run_and_the_seven_readers():
    spans.clear()                # a worker's recorder holds earlier windows
    t0 = time.perf_counter()
    cell = mf.find_cell(MANIFEST, "random-10k-t8.watched")
    out = bench_run.measure(
        MANIFEST, cell, 5, 1e9, False, jax.devices()[:1], t0,
        overrides=dict(n_peers=256, max_segments=2))
    assert out["result"]["correct"]
    assert set(out["result"]["metrics"]) == {"rounds_per_s", "seg_p95_ms",
                                             "setup_s"}
    got = {n: mf.load_plugin("readers", n).read(out["run"]) for n in TABLE}
    assert all(isinstance(v, (int, float)) for v in got.values()), got
    assert got == setup.reduce(spans.recorded(), W)
    assert got["setup_programs_compiled"] >= 1
    assert 0 <= got["setup_cache_misses"] <= got["setup_programs_compiled"]
    assert got["setup_net_build_s"] > 0 and got["setup_state_init_s"] > 0
    assert got["setup_window_compile_s"] > 0
    assert (got["setup_net_build_s"] + got["setup_state_init_s"]
            + got["setup_window_compile_s"]) <= out["run"]["setup_s"]
    assert got["setup_step_build_s"] > 0
    # one window, called three times, compiled once
    (ours,) = [s for s in spans.recorded() if s.name == "compile.backend"
               and W in s.attrs["fun_name"]]
    assert ours.parent is None       # the harness opens no span of its own
