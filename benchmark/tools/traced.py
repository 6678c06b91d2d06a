"""One traced run of one cell, as ``run.py --trace 1`` makes it, with the
readers named by ``--readers`` read besides those the manifest lists for
the cell: for a reader that has a file under ``benchmark/readers/`` and no
line in BENCHMARK.json yet. Run on the chip:

  python3 benchmark/tools/traced.py --workload eth2-100k.stepped --seed 7 \\
      --readers part_us_fanout,edge_rows_per_round

Prints the result line with the extra readings under ``unlisted``, and
before it (``--top N``) the N ops inside the window's programs that took
most device self time, each with its stage, its part and the end of its
``op_name``: what a stage or a part is made of.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def top_ops(run: dict, top: int) -> list:
    """The ``top`` ops of the one traced window by self seconds, and the
    seconds by (stage, part) over all of them."""
    import re

    import jax

    from benchmark.harness import stages, trace

    windows = stages.traced_windows() or []
    ran = {stages.module_base(m[0])
           for dev in run["device_trace"]["devices"].values()
           for m in dev["modules"]}
    ours = [w for w in windows if w.module_name in ran]
    if len(ours) != 1 or ours[0].stages() is None:
        return []
    window = ours[0]
    stage_of = window.stages()
    part_of = getattr(window, "parts", dict)() or {}
    treedef, leaves = window.signature
    a, kw = jax.tree_util.tree_unflatten(treedef, leaves)
    text = window.jitted.lower(*a, **kw).compile().as_text()
    op_name = {}
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = ", ln)
        o = re.search(r'op_name="([^"]*)"', ln)
        if m and o:
            op_name[m.group(1)] = o.group(1)
    (dev,) = run["device_trace"]["devices"].values()
    times = trace.self_times(stages.ops_inside(dev, window.module_name))
    by_pair: dict = {}
    for name, sec in times.items():
        key = f"{stage_of.get(name, 'unscoped')}/{part_of.get(name, '-')}"
        by_pair[key] = by_pair.get(key, 0.0) + sec
    rounds = run["rounds"]
    out = [{"us_per_round_by_stage_and_part":
            {k: 1e6 * v / rounds for k, v in sorted(by_pair.items())}}]
    for name, sec in sorted(times.items(), key=lambda kv: -kv[1])[:top]:
        out.append({"op": name, "us_per_round": 1e6 * sec / rounds,
                    "stage": stage_of.get(name, "unscoped"),
                    "part": part_of.get(name),
                    "op_name": "/".join(op_name.get(name, "").split("/")[-4:])})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--readers", required=True)
    ap.add_argument("--top", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from benchmark import run as bench_run
    from benchmark.harness import manifest as mf
    from go_libp2p_pubsub_tpu.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    manifest = mf.load_manifest(ROOT)
    cell = mf.find_cell(manifest, args.workload)
    out = bench_run.measure(manifest, cell, args.seed, args.seconds, True,
                            jax.devices()[:cell["chips"]], T_START)
    result, run = out["result"], out["run"]
    result["unlisted"] = {
        name: mf.load_plugin("readers", name, ROOT).read(run)
        for name in args.readers.split(",")}
    if args.top:
        for line in top_ops(run, args.top):
            print(json.dumps(line), file=sys.stderr)
    for x in result["compared"]:
        print(f"compared {x['name']} = {x['value']} (limit {x['limit']})",
              file=sys.stderr)
    print(json.dumps({"workload": cell["name"], "seed": args.seed,
                      "setup_s": run["setup_s"], "check_s": run["check_s"],
                      "setup_parts": run["setup_parts"]}), file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
