"""The relayout loops of a cell's compiled window, counted without the
chip: the window is built as ``benchmark/run.py`` builds it, compiled for
a DESCRIBED ``v5e:2x2`` device (nothing attached, nothing runs: counts and
texts, never a time) and its text searched for ``while`` ops.

    JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \\
        python scripts/window_whiles.py --workload random-100k.stepped \\
        [--text /root/scratch/window.txt] [--n-peers 3000] [--lower-only]

XLA merges or splits ``(N, K) <-> N*K`` under a tiled layout through a
1-D ``u32[...]{0:T(1024)}`` buffer, one word an iteration, where the minor
of the two axes is not a whole number of lanes: those loops stand right
before and after a general gather's fusion and were a quarter of the
round at 100k peers (PERF.md §6, PR 34). One JSON line: the ``while`` ops,
the ops inside their bodies, and those whose tuple carries such a buffer,
after the sha256 of the window's LOWERED text (StableHLO, constants and
all: two commits whose windows lower to one text run one program).
``--lower-only`` stops there (seconds, where the compile takes a minute;
``lower_s`` / ``compile_s`` count from the builder's call on).
``edge_gathers`` counts the gathers under ``gs.edge_gather`` by the stage
they serve, the rows they give, the rows of their table and the words of a
row, with the memory space XLA gave table, indices and output (``S(1)`` is
the fast one: PERF.md §5 item 5): a plane that crossed in column slices
(``ops/edges.word_slices``) shows as one gather a slice, each of at most a
tile of words, not as one of the whole width.
``edge_tables_hbm_per_dispatch`` counts those of at least Np rows (N
rounded up to 128: the head gathers, one a sub-round and one a slice of the
control head) whose table is NOT in ``S(1)``: 0 where every big gather of
the one-phase window reads its table from the fast space (5 of 10 at
``sybil-50k.stepped`` until PR 40, PERF.md §6).
"""

from __future__ import annotations

import argparse
import collections
import hashlib
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

FLAT_BUFFER = re.compile(r"u32\[(\d+)\]\{0:T\(1024\)")


def whiles(text: str) -> list[dict]:
    """Every ``while`` op of a compiled module's text: its name, the ops
    of its body and the 1-D tiled u32 buffers its tuple carries (words)."""
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
            bodies[name] = 0
        elif line.startswith("}"):
            name = None
        elif name is not None and " = " in line:
            bodies[name] += 1
    out = []
    for line in text.splitlines():
        op = re.match(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*?) while\(", line)
        if op:
            body = re.search(r"body=%?([\w.\-]+)", line)
            out.append({
                "name": op.group(1),
                "body_ops": bodies.get(body.group(1)) if body else None,
                "flat_u32_words": [int(w) for w in
                                   FLAT_BUFFER.findall(op.group(2))],
            })
    return out


def edge_gathers(text: str) -> list[dict]:
    """The gathers of a compiled module's text that stand under
    ``gs.edge_gather``, equal ones counted together: the stage around the
    scope (a part may stand between the two), output rows, table rows,
    words a row (a one-word plane's gather is 1-D), which of table, indices
    (the fusion's operands) and output (its result) lie in ``S(1)``, and
    the fusions that hold them (two gathers in one multi-output fusion
    keep both tables live at once)."""
    name = r"%?([\w.\-]+)"
    lines = text.splitlines()
    defs = {m.group(1): line.split(" = ", 1)[1] for line in lines
            if (m := re.match(rf"^\s+(?:ROOT )?{name} = ", line))}
    callers = {m.group(2): m.group(1) for line in lines
               if (m := re.search(rf" = (\S+) fusion\(.* calls={name}", line))}

    def fast(op):    # the operand's parameter, through the fusion's own ops
        while " parameter(" not in defs[op]:
            op = re.search(rf" [\w\-]+\({name}", defs[op]).group(1)
        return "S(1)" in defs[op].split(" ")[0]

    found, fusions, comp = collections.Counter(), {}, None
    for line in lines:
        head = re.match(rf"^(?:ENTRY )?{name} \(.*\{{\s*$", line)
        if head:
            comp = head.group(1)
        op = re.search(
            rf" = u32\[(\d+)(?:,(\d+))?\]\S* gather\({name}, {name}\)", line)
        stage = re.search(r"gs\.(\w+)/(?:gsx\.\w+/)?gs\.edge_gather/", line)
        if op and stage:
            rows, words, table, index = op.groups()
            table_rows = re.match(r"u32\[(\d+)", defs[table]).group(1)
            key = (stage.group(1), int(rows), int(table_rows), int(words or 1),
                   fast(table), fast(index), "S(1)" in callers.get(comp, ""))
            found[key] += 1
            fusions.setdefault(key, set()).add(comp)
    return [dict(zip(("stage", "rows", "table_rows", "words", "table_fast",
                      "indices_fast", "output_fast"), key), count=n,
                 fusions=len(fusions[key]))
            for key, n in sorted(found.items(), key=str)]


def edge_tables_hbm(gathers: list[dict], n_peers: int) -> int:
    """Of ``edge_gathers``' list: the gathers of at least Np rows (``n_peers``
    rounded up to 128) whose table is not in the fast memory space."""
    padded = -(-n_peers // 128) * 128
    return sum(g["count"] for g in gathers
               if g["rows"] >= padded and not g["table_fast"])


def lower_window(workload: str, chip, n_peers: int | None = None):
    """A cell's window as ``benchmark/run.py`` builds it, lowered for
    ``chip`` (a sharding on a described device) from shapes alone: the
    cell, what its builder built, and the lowered program."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import manifest as mf

    manifest = mf.load_manifest(ROOT)
    cell = mf.find_cell(manifest, workload)
    config = mf.load_config(manifest, cell["config"], ROOT)
    mix = mf.load_traffic(cell["traffic"], ROOT)
    built = mf.load_plugin("builders", config["builder"], ROOT).build(
        config, 1, jax.devices()[:1], n_peers=n_peers)
    window = built.make_window(int(mix["unroll_phases"]))
    on = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)
    rounds = int(mix["segment_phases"]) * built.rounds_per_phase
    pubs = (rounds, int(mix["pubs_per_round"]))
    xs = (jax.ShapeDtypeStruct(pubs, jnp.int32),
          jax.ShapeDtypeStruct(pubs, jnp.int32),
          jax.ShapeDtypeStruct(pubs, bool))
    return cell, built, window.lower(on(jax.eval_shape(built.fresh)), *on(xs))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--text", help="write the compiled text here")
    ap.add_argument("--n-peers", type=int,
                    help="a rehearsal at another size (small planes go "
                         "through copies: no loop on either side)")
    ap.add_argument("--lower-only", action="store_true",
                    help="print the lowered text's sha256 and compile "
                         "nothing")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    t0 = time.perf_counter()
    cell, built, lowered = lower_window(
        args.workload, SingleDeviceSharding(topo.devices[0]), args.n_peers)
    sha = hashlib.sha256(lowered.as_text().encode()).hexdigest()
    if args.lower_only:
        print(json.dumps({"workload": cell["name"], "n_peers": built.n_peers,
                          "lowered_sha256": sha,
                          "lower_s": time.perf_counter() - t0}))
        return 0
    text = lowered.compile().as_text()
    found = whiles(text)
    if args.text:
        with open(args.text, "w") as f:
            f.write(text)
    flat = [w for w in found if w["flat_u32_words"]]
    gathers = edge_gathers(text)
    print(json.dumps({
        "workload": cell["name"], "compiled_for": str(topo.devices[0]),
        "lowered_sha256": sha,
        "compile_s": time.perf_counter() - t0, "text_bytes": len(text),
        "whiles": len(found),
        "while_body_ops": sum(w["body_ops"] or 0 for w in found),
        "whiles_with_flat_u32_buffer": len(flat),
        "flat_u32_words": sorted(
            (w for f in flat for w in f["flat_u32_words"]), reverse=True),
        "gathers": len(re.findall(r" gather\(", text)),
        "edge_tables_hbm_per_dispatch": edge_tables_hbm(gathers,
                                                        built.n_peers),
        "edge_gathers": gathers,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
