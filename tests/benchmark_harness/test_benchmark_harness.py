"""The whole command at toy size on the CPU, through the functions
``benchmark/run.py`` calls: every cell of the manifest on as many host
devices as it asks chips for, with its own driver, statistics and
``correct`` path. No number here is a device metric."""

import math
import statistics
import time

import jax
import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.harness import costs, graphs, peaks, stats, traffic
from benchmark.harness import manifest as mf

MANIFEST = mf.load_manifest()
N_TOY = 256


def toy(cell_name, seed=5, segments=3, **kw):
    """A toy run on as many (host) devices as the cell asks chips for."""
    cell = mf.find_cell(MANIFEST, cell_name)
    return bench_run.measure(
        MANIFEST, cell, seed, 1e9, False, jax.devices()[:cell["chips"]],
        time.perf_counter(),
        overrides=dict(kw, n_peers=N_TOY, max_segments=segments))


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_cell_at_toy_size(cell):
    """What the result object owes the driver, of EVERY cell whatever its
    driver; the segment loop's own shape where the cell's traffic file
    names that driver."""
    entry = mf.find_cell(MANIFEST, cell)
    out = toy(cell)
    run, result = out["run"], out["result"]
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "compared"
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert result["device"]["count"] == entry["chips"]
    owed = {m["name"]: m for m in mf.cell_metrics(MANIFEST, cell, "end_to_end")}
    assert set(result["metrics"]) == set(owed) >= {
        "rounds_per_s", "seg_p95_ms", "setup_s"}
    for name, got in result["metrics"].items():
        assert got["value"] > 0 and got["unit"] == owed[name]["unit"]
    # every number compared stands beside its limit
    assert all(set(x) == {"name", "value", "limit"} for x in result["compared"])
    # the reference's numbers, by what the configuration's file says
    config = mf.load_config(MANIFEST, entry["config"])
    names = {x["name"] for x in result["compared"]}
    assert len(names) == len(result["compared"]) > 0
    if config["reference"].startswith("gossipsub"):
        assert {"tick_gap", "msgs_mismatch", "have_mismatch", "causality",
                "push_gap_share", "mesh_off_graph", "mesh_degree_out",
                "backoff_in_mesh", "ihave_mismatch"} <= names
        assert ({"score_gap", "fmd_short", "mesh_time_mismatch"} <= names) == (
            config["score_enabled"])
    if config["reference"] == "gossipsub":
        assert ({"undelivered", "delivery_rounds_max"} <= names) == (
            config.get("full_delivery_rounds") is not None)

    mix = mf.load_traffic(entry["traffic"])
    if mix["driver"] != "segment_loop":
        return
    assert result["attempted"] == 3
    assert run["segment_rounds"] == 8 * mix["segment_phases"]
    assert run["rounds"] == 3 * run["segment_rounds"]
    # all rounds over all the window's seconds, every gap included
    assert result["metrics"]["rounds_per_s"]["value"] == (
        run["rounds"] / run["window_s"])
    # a segment's time runs from one summary's arrival on the host to the
    # next one's, and the intervals add up to the window
    seg = run["seg_s"]
    assert len(seg) == 3 and sum(seg) == pytest.approx(run["window_s"])
    assert all(sum(s) <= run["window_s"] for s in run["spans"].values())
    # a span per segment sent, and one more wait for what was in flight
    assert [len(run["spans"][k]) for k in (
        "xs_assembly", "dispatch", "summary_readback")] == [3, 3, 4]
    # the p95 of ALL segments: with three, the slowest
    assert result["metrics"]["seg_p95_ms"]["value"] == pytest.approx(
        1e3 * max(seg), rel=1e-6)
    # nothing compiles inside the window, on one device. A window sharded
    # over several compiles twice more after the warm-up segment today (its
    # zero-width fanout planes come back replicated where `shard_state`
    # split them: PERF.md section 7); the PR that brings the first
    # four-chip cell cures that and drops this condition
    if entry["chips"] == 1:
        assert run["window_compiles"] == 0
    # summaries: the tick the user read after each segment
    assert [t for _, t in run["summaries"]] == [
        (i + 2) * run["segment_rounds"] for i in range(3)]


def test_statistics():
    assert stats.percentile(list(range(1, 101)), 95) == 95
    assert stats.percentile([3.0, 1.0, 2.0], 95) == 3.0
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.rate(1600, 0.92) == 1600 / 0.92
    vals = [100, 101, 102, 103, 104, 105]
    q = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == (q[2] - q[0]) / 102.5
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_traffic_is_a_function_of_seed_and_segment():
    mix = mf.load_traffic("steady")
    a = traffic.segment_schedule(mix, 2**31 + 5, 3, 16, 1000, 8)
    b = traffic.segment_schedule(mix, 2**31 + 5, 3, 16, 1000, 8)
    c = traffic.segment_schedule(mix, 2**31 + 6, 3, 16, 1000, 8)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert a[0].shape == c[0].shape == (16, 4) and a[2].all()
    assert 0 <= a[0].min() and a[0].max() < 1000 and a[1].max() < 8
    with pytest.raises(ValueError):
        traffic.check_mix(dict(mix, segment_phases=0))
    with pytest.raises(ValueError):
        traffic.check_mix(dict(mix, ahead_segments=-1))


@pytest.mark.parametrize("ahead", [0, 1, 7])
def test_segments_sent_ahead_are_all_waited_for(ahead, monkeypatch):
    """Whatever the depth, every segment sent is read back in order before
    the window closes, and the final state is the same."""
    real = mf.load_traffic
    monkeypatch.setattr(
        mf, "load_traffic",
        lambda name, root=mf.ROOT: dict(real(name, root), ahead_segments=ahead))
    out = toy("random-10k-t8.watched", segments=5)
    run, result = out["run"], out["result"]
    assert result["correct"], result["compared"]
    assert run["ahead_segments"] == ahead and result["attempted"] == 5
    assert [t for _, t in run["summaries"]] == [
        (i + 2) * run["segment_rounds"] for i in range(5)]
    assert len(run["seg_s"]) == 5 and min(run["seg_s"]) > 0
    assert sum(run["seg_s"]) == pytest.approx(run["window_s"])
    closed = toy("random-10k-t8.watched", segments=5)["run"]
    assert run["receipts"] == closed["receipts"]


@pytest.mark.parametrize("spec", [
    {"kind": "ring_lattice", "d": 8},
    {"kind": "random_connect", "d": 10, "seed": 1},
])
def test_graphs_are_symmetric_involutions(spec):
    n = 500
    g = graphs.build_graph(spec, n)
    nbr, rev, ok = g["nbr"], g["rev"], g["nbr_ok"]
    rows = np.arange(n)[:, None].repeat(nbr.shape[1], 1)
    back = nbr[np.clip(nbr, 0, None), rev]
    assert np.array_equal(back[ok], rows[ok])
    assert np.array_equal(g["outbound"][ok],
                          ~g["outbound"][np.clip(nbr, 0, None), rev][ok])
    assert (nbr[ok] != rows[ok]).all()
    if spec["kind"] == "random_connect":
        again = graphs.build_graph(spec, n)
        assert np.array_equal(again["nbr"], nbr)
        assert ok.sum(axis=1).min() >= spec["d"]
        assert ok.sum(axis=1).max() == nbr.shape[1]     # K: no empty column
        assert g["outbound"].sum() == ok.sum() // 2


def test_costs_and_peaks():
    assert costs.tree_bytes([((100, 2), 4), ((64,), 1), ((), 4)]) == 868
    assert costs.phase_floor_seconds(819_000_000, 819e9) == pytest.approx(1e-3)
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9", "hbm_bytes_per_s")
    reader = mf.load_plugin("readers", "hbm_floor_pct")
    run = {"trace": {"busy_s": 0.46, "window_s": 0.5}, "rounds": 800,
           "rounds_per_phase": 8, "device_kind": "TPU v5 lite",
           "state_shapes": [((100_000, 64), 4)]}
    want = 100 * (25_600_000 / 819e9) / (0.46 / 100)
    assert reader.read(run) == pytest.approx(want)
    assert reader.read(dict(run, trace=None)) is None
    assert math.isclose(
        mf.load_plugin("readers", "device_idle_pct").read(run), 8.0)
