"""BENCHMARK.json against the driver's naming rules, and the proof that
the harness is driven by data: a configuration, a traffic mix, a cell (on
one chip or four), a driver and a per-layer metric are each added as new
files plus entries APPENDED to the manifest's lists, and no test that
stands under ``tests/benchmark_harness/`` fails for it. So no test here
asserts a position in ``configs``, ``workloads`` or ``per_layer``: find
entries by name."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import manifest as mf

MANIFEST = mf.load_manifest()


def _names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
            yield f"{group}:{entry['name']}", entry["name"]
    for w in MANIFEST["workloads"]:
        yield f"traffic:{w['traffic']}", w["traffic"]
    for m in MANIFEST["per_layer"]:
        yield f"layer:{m['name']}", m["layer"]


@pytest.mark.parametrize("label,name", sorted(set(_names())))
def test_every_name_is_a_slug(label, name):
    assert mf.NAME_RE.match(name), label


@pytest.mark.parametrize(
    "metric", MANIFEST["end_to_end"] + MANIFEST["per_layer"],
    ids=lambda m: m["name"])
def test_every_unit_and_source(metric):
    assert mf.UNIT_RE.match(metric["unit"])
    assert metric["source"] in mf.SOURCES
    assert metric["better"] in ("lower", "higher")


def test_manifest_keeps_every_rule():
    assert mf.check_manifest(MANIFEST) == []
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    for c in MANIFEST["configs"]:
        assert len(c["source"]) <= 200
        held = mf.load_json(os.path.join(mf.ROOT, c["file"]))
        assert held["source"] == c["source"] and held["reduced"] == c["reduced"]
        assert held["guarantees"] and held["assumed"]
    assert mf.four_chip_breaches(MANIFEST["workloads"]) == []
    for w in MANIFEST["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        # every cell reports every end-to-end metric, and each per-layer
        # metric's `moves` is one of them
        names = {m["name"] for m in mf.cell_metrics(MANIFEST, w["name"],
                                                    "end_to_end")}
        assert {"rounds_per_s", "seg_p95_ms", "setup_s"} <= names
        for m in mf.cell_metrics(MANIFEST, w["name"], "per_layer"):
            assert m["moves"] in names


@pytest.mark.parametrize("bad", [
    ("per_layer", 0, "layer", "phase engine (device)"),
    ("per_layer", 1, "unit", "ms per segment"),
    ("end_to_end", 0, "name", "rounds/s"),
    ("per_layer", 2, "moves", "no_such_metric"),
    ("workloads", 0, "traffic", "no-such-mix"),
])
def test_a_broken_manifest_is_caught(bad):
    group, i, key, value = bad
    broken = json.loads(json.dumps(MANIFEST))
    broken[group][i][key] = value
    assert mf.check_manifest(broken) != []


@pytest.mark.parametrize("chips,breaches", [
    ([1], 0), ([4], 0),                       # one always may
    ([1, 4], 0), ([4, 4], 1), ([1, 1, 4, 4], 0), ([1, 4, 4], 1),
    ([1, 1, 1, 1, 1, 1, 4], 0),               # today's six and the lattice's
    ([1, 1, 1, 1, 4, 4, 4], 0), ([1, 1, 1, 4, 4, 4, 4], 1),
    ([1, 2], None), ([8], None),              # no such machine
])
def test_four_chip_cells_are_at_most_half_and_one_always_may(chips, breaches):
    """The driver's rule, checked before the driver refuses the file."""
    man = json.loads(json.dumps(MANIFEST))
    base = man["workloads"][0]
    man["workloads"] = [dict(base, name=f"cell{i}", chips=c)
                        for i, c in enumerate(chips)]
    for m in man["end_to_end"] + man["per_layer"]:
        m.pop("workloads", None)
    bad = mf.check_manifest(man)
    if breaches is None:
        assert any("chips" in b for b in bad)
    else:
        assert len(mf.four_chip_breaches(man["workloads"])) == breaches
        assert len(bad) == breaches, bad


def _files(top: str) -> dict:
    """``{path: bytes}`` of every file under ``top``."""
    held = {}
    for d, _, files in os.walk(top):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                held[p] = fh.read()
    return held


def test_new_cell_mix_config_and_metric_are_only_new_files(tmp_path):
    root = str(tmp_path)
    shutil.copytree(mf.BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(os.path.join(root, "benchmark"))

    man = json.loads(json.dumps(MANIFEST))
    cfg = mf.load_config(man, "random-10k-t8")
    cfg.update(name="random-10k-t4", n_topics=4)
    with open(os.path.join(root, "benchmark/configs/random-10k-t4.json"), "w") as f:
        json.dump(cfg, f)
    mix = mf.load_traffic("steady")
    mix.update(name="burst8", pubs_per_round=8)
    with open(os.path.join(root, "benchmark/traffic/burst8.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "benchmark/readers/summary_readback_ms.py"), "w") as f:
        f.write("from benchmark.harness import stats\n\n\ndef read(run):\n"
                "    spans = run['spans'].get('summary_readback')\n"
                "    return 1e3 * stats.median(spans) if spans else None\n")
    man["configs"].append({
        "name": "random-10k-t4", "source": cfg["source"], "reduced": [],
        "file": "benchmark/configs/random-10k-t4.json", "why": "four topics"})
    man["workloads"].append({
        "name": "random-10k-t4.burst8", "config": "random-10k-t4",
        "traffic": "burst8", "chips": 1, "why": "8 publishes a round"})
    man["per_layer"].append({
        "name": "summary_readback_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "entry", "moves": "seg_p95_ms",
        "workloads": ["random-10k-t4.burst8"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)

    got = mf.load_manifest(root)
    assert mf.check_manifest(got, root) == []
    cell = mf.find_cell(got, "random-10k-t4.burst8")
    assert mf.load_config(got, cell["config"], root)["n_topics"] == 4
    assert mf.load_traffic(cell["traffic"], root)["pubs_per_round"] == 8
    mine = [m["name"] for m in mf.cell_metrics(got, cell["name"], "per_layer")]
    assert "summary_readback_ms" in mine and "hbm_floor_pct" in mine
    old = [m["name"] for m in mf.cell_metrics(
        got, MANIFEST["workloads"][0]["name"], "per_layer")]
    assert "summary_readback_ms" not in old
    reader = mf.load_plugin("readers", "summary_readback_ms", root)
    assert reader.read({"spans": {"summary_readback": [0.001, 0.003, 0.002]}}) == 2.0
    # no file that was there changed
    for p, data in before.items():
        with open(p, "rb") as fh:
            assert fh.read() == data, p

#: set in the environment of the copy's own test run, where this test
#: would otherwise copy the copy
INNER = "BENCH_APPEND_PROOF_INNER"
SPANNED_DRIVER = '''"""Driver kind ``segment_loop_spanned``: the segment loop, and the whole
call as one more span."""
import time

from benchmark.harness import manifest as mf


def run(built, mix, seed, seconds, traced, t_start, max_segments=None):
    t0 = time.perf_counter()
    out = mf.load_plugin("drivers", "segment_loop").run(
        built, mix, seed, seconds, traced, t_start, max_segments=max_segments)
    out["spans"]["whole_call"] = [time.perf_counter() - t0]
    return out
'''
WHOLE_CALL_READER = '''"""Host ms of the driver's whole call (``segment_loop_spanned``'s span)."""


def read(run):
    spans = run["spans"].get("whole_call")
    return 1e3 * spans[0] if spans else None
'''


@pytest.mark.skipif(INNER in os.environ, reason="this IS the copy's run")
def test_a_cell_a_driver_a_four_chip_cell_and_a_metric_come_by_append_alone(
        tmp_path):
    """In a copy of ``BENCHMARK.json``, ``benchmark/`` AND the tests that
    stand: a configuration with a one-chip cell, a ``chips: 4`` cell, a
    traffic file that names a driver of its own and a per-layer metric with
    its reader, all as new files and entries at the END of their lists; no
    file that was there changes, and the copy's own tests pass at toy size
    (4 host devices for the four-chip cell). A test that pins a position
    (``[-1]``, ``[-10:]``), one chip, one driver's shape or the window's
    module name fails here, for whoever appends next."""
    root = str(tmp_path)
    ignore = shutil.ignore_patterns("__pycache__")
    shutil.copytree(mf.BENCH_DIR, os.path.join(root, "benchmark"), ignore=ignore)
    shutil.copytree(os.path.join(mf.ROOT, "tests", "benchmark_harness"),
                    os.path.join(root, "tests", "benchmark_harness"),
                    ignore=ignore)
    before = _files(root)

    def write(rel, text):
        with open(os.path.join(root, rel), "x") as f:       # new files only
            f.write(text)

    man = json.loads(json.dumps(MANIFEST))
    cfg = mf.load_config(man, "random-10k-t8")
    cfg.update(name="appended-10k-t2", n_topics=2)
    write("benchmark/configs/appended-10k-t2.json", json.dumps(cfg))
    mix = mf.load_traffic("watched")
    mix.update(name="spanned", driver="segment_loop_spanned")
    write("benchmark/traffic/spanned.json", json.dumps(mix))
    write("benchmark/drivers/segment_loop_spanned.py", SPANNED_DRIVER)
    write("benchmark/readers/whole_call_ms.py", WHOLE_CALL_READER)
    man["configs"].append({
        "name": "appended-10k-t2", "source": cfg["source"], "reduced": [],
        "file": "benchmark/configs/appended-10k-t2.json", "why": "two topics"})
    man["workloads"].append({
        "name": "appended-10k-t2.spanned", "config": "appended-10k-t2",
        "traffic": "spanned", "chips": 1, "why": "a driver of its own"})
    man["workloads"].append({
        "name": "lattice-100k.steady-x4", "config": "lattice-100k",
        "traffic": "steady", "chips": 4,
        "why": "the peer axis sharded 4 x 25,000: the halo's collectives"})
    man["per_layer"].append({
        "name": "whole_call_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "entry", "moves": "seg_p95_ms",
        "workloads": ["appended-10k-t2.spanned"]})
    write("BENCHMARK.json", json.dumps(man, indent=1))

    for group in ("configs", "workloads", "per_layer"):      # appended
        assert man[group][:len(MANIFEST[group])] == MANIFEST[group]
    now = _files(root)          # four new files and the manifest
    assert len(now) == len(before) + 5 and now.items() >= before.items()
    got = mf.load_manifest(root)
    assert mf.check_manifest(got, root) == []

    # the copy's own tests, on the copy's files (its root comes first on
    # the path; the program is the tree's)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=mf.ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env[INNER] = "1"
    harness = os.path.join("tests", "benchmark_harness")
    cmd = [sys.executable, "-m", "pytest", "-q", "-rA", "--tb=short", "-p",
           "no:cacheprovider", "-p", "no:randomly", "-k",
           "manifest or x4 or spanned",
           *(os.path.join(harness, f) for f in (
               "test_benchmark_manifest.py", "test_benchmark_stages.py",
               "test_benchmark_churn.py", "test_benchmark_setup.py",
               "test_benchmark_harness.py"))]
    out = subprocess.run(cmd, env=env, cwd=root, capture_output=True,
                         text=True, timeout=1200)
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    # both appended cells ran (one of them on four devices), nothing was
    # skipped but this test itself, and no file that was there was written
    for cell in ("appended-10k-t2.spanned", "lattice-100k.steady-x4"):
        assert f"test_cell_at_toy_size[{cell}]" in out.stdout.split(
            "PASSED", 1)[1], out.stdout[-2000:]
    assert " 1 skipped" in out.stdout, out.stdout[-500:]
    assert _files(root).items() >= before.items()


def test_run_py_refuses_to_measure_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(mf.BENCH_DIR, "run.py"), "--workload",
           MANIFEST["workloads"][0]["name"], "--seed", "1", "--seconds", "1"]
    out = subprocess.run(cmd, env=env, cwd=mf.ROOT, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""
    assert "TPU" in out.stderr

    # a directory that holds only BENCHMARK.json and the files under
    # `paths`: there is no program to measure
    bare = str(tmp_path)
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), bare)
    shutil.copytree(mf.BENCH_DIR, os.path.join(bare, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    cmd[1] = os.path.join(bare, "benchmark", "run.py")
    out = subprocess.run(cmd, env=env, cwd=bare, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode != 0 and out.stdout.strip() == ""
