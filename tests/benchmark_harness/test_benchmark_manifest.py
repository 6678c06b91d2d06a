"""BENCHMARK.json against the driver's naming rules, and the proof that
the harness is driven by data: a configuration, a traffic mix, a cell and
a per-layer metric are each added as new files plus manifest entries."""

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
    for w in MANIFEST["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200
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


def test_new_cell_mix_config_and_metric_are_only_new_files(tmp_path):
    root = str(tmp_path)
    shutil.copytree(mf.BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                before[p] = fh.read()

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
