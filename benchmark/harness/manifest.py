"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix, one
driver kind, one builder kind, one reference or one per-layer metric is
a file of its own under ``benchmark/``, found by the name the manifest
(or the file that refers to it) gives. Adding one never edits another.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

#: the driver's rule for every name (metric, layer, cell, configuration,
#: traffic, key of ``reduced``) and for every unit
NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
UNIT_RE = re.compile(r"[A-Za-z0-9_.\-/%]{1,16}\Z")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class ManifestError(ValueError):
    """BENCHMARK.json or a file it names breaks a rule."""


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def bench_dir(root: str = ROOT) -> str:
    return os.path.join(root, "benchmark")


def find_cell(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    known = ", ".join(c["name"] for c in manifest["workloads"])
    raise ManifestError(f"no workload {name!r} in BENCHMARK.json ({known})")


def load_config(manifest: dict, name: str, root: str = ROOT) -> dict:
    for entry in manifest["configs"]:
        if entry["name"] == name:
            return load_json(os.path.join(root, entry["file"]))
    raise ManifestError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str, root: str = ROOT) -> dict:
    return load_json(os.path.join(bench_dir(root), "traffic", name + ".json"))


def load_plugin(kind: str, name: str, root: str = ROOT):
    """The module ``benchmark/<kind>/<name>.py``; names may hold ``.`` and
    ``-``, so it is loaded by path."""
    if not NAME_RE.match(name):
        raise ManifestError(f"{kind} name {name!r} is not a slug")
    path = os.path.join(bench_dir(root), kind, name + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"no {kind} file {path}")
    slug = re.sub(r"[^A-Za-z0-9_]", "_", name)
    spec = importlib.util.spec_from_file_location(
        f"_benchmark_{kind}_{slug}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(manifest: dict, cell: str, group: str) -> list:
    """The metrics of ``group`` ("end_to_end" / "per_layer") that ``cell``
    reports: those without a ``workloads`` key, and those that list it."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def four_chip_breaches(workloads: list) -> list:
    """The driver's rule on four-chip cells (each costs four chips in every
    run of every later check): of the cells at most half, rounded down,
    ask for 4 chips, and one always may."""
    four = [w["name"] for w in workloads if w["chips"] == 4]
    allowed = max(1, len(workloads) // 2)
    if len(four) <= allowed:
        return []
    return [f"{len(four)} of {len(workloads)} workloads ask for 4 chips "
            f"({', '.join(four)}): at most {allowed} may"]


def check_manifest(manifest: dict, root: str = ROOT) -> list:
    """Every breach of the naming and wiring rules, as strings (empty:
    none). What the driver refuses before any run, checked here first."""
    bad = []

    def name(kind, s):
        if not isinstance(s, str) or not NAME_RE.match(s):
            bad.append(f"{kind} {s!r} is not a slug")

    def line(kind, s):
        if (not isinstance(s, str) or not 1 <= len(s) <= 200
                or "\n" in s or "\t" in s):
            bad.append(f"{kind} {s!r}: 1 to 200 characters on one line")

    cfg_names = [c["name"] for c in manifest["configs"]]
    for c in manifest["configs"]:
        name("config", c["name"])
        line(f"source of config {c['name']}", c["source"])
        line(f"why of config {c['name']}", c["why"])
        for key in c["reduced"]:
            name(f"reduced key of {c['name']}", key)
        if not c["file"].startswith(tuple(p + "/" for p in manifest["paths"])):
            bad.append(f"config file {c['file']} lies outside paths")
        elif not os.path.exists(os.path.join(root, c["file"])):
            bad.append(f"config file {c['file']} is missing")
    cells = [w["name"] for w in manifest["workloads"]]
    for w in manifest["workloads"]:
        name("workload", w["name"])
        name(f"traffic of {w['name']}", w["traffic"])
        line(f"why of workload {w['name']}", w["why"])
        if w["config"] not in cfg_names:
            bad.append(f"workload {w['name']}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
        path = os.path.join(bench_dir(root), "traffic", w["traffic"] + ".json")
        if not os.path.exists(path):
            bad.append(f"workload {w['name']}: no traffic file {path}")
    bad.extend(four_chip_breaches(manifest["workloads"]))
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        bad.append("end_to_end lacks setup_s")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        name("metric", m["name"])
        if not isinstance(m["unit"], str) or not UNIT_RE.match(m["unit"]):
            bad.append(f"unit {m['unit']!r} of {m['name']}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"better {m['better']!r} of {m['name']}")
        if m["source"] not in SOURCES:
            bad.append(f"source {m['source']!r} of {m['name']}")
        for c in m.get("workloads", []):
            if c not in cells:
                bad.append(f"metric {m['name']}: unknown workload {c}")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end {m['name']}: source {m['source']}")
        if not 0 < m["bound"] <= 0.25:
            bad.append(f"end-to-end {m['name']}: bound {m['bound']}")
    for m in manifest["per_layer"]:
        name(f"layer of {m['name']}", m["layer"])
        moved = e2e.get(m["moves"])
        if moved is None:
            bad.append(f"per-layer {m['name']} moves unknown {m['moves']}")
            continue
        reporting = moved.get("workloads", cells)
        for c in m.get("workloads", cells):
            if c not in reporting:
                bad.append(f"per-layer {m['name']}: cell {c} does not "
                           f"report {m['moves']}")
        path = os.path.join(bench_dir(root), "readers", m["name"] + ".py")
        if not os.path.exists(path):
            bad.append(f"per-layer {m['name']}: no reader {path}")
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    for group in (names, cells, cfg_names):
        if len(set(group)) != len(group):
            bad.append(f"duplicate name in {group}")
    return bad
