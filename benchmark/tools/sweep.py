"""Many seeds of one cell in one process, short windows: the readings a
limit is set from (the program's dozen seeds, and the control's three or
more, at the cell's own size). Run on the chip:

  python3 benchmark/tools/sweep.py --workload random-100k.stepped \\
      --seeds 101,102,103 --seconds 3 [--control '{"chaos_loss_rate": 0.02}']
      [--faults half_batch,answer_altered] [--graph-seeds 2,3,4]

``--faults`` plants each fault of ``harness/faults.py`` under the timed
path in turn (same compiled window, one process), ``--graph-seeds`` runs
every seed on each of those graphs instead of the file's (another graph is
another compile, paid here and not in the cell). Prints one line per run
with every number compared, and per fault a last line with the largest
and smallest reading of each.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", default=None)
    ap.add_argument("--faults", default=None)
    ap.add_argument("--graph-seeds", default=None)
    args = ap.parse_args(argv)

    import jax

    from benchmark import run as bench_run
    from benchmark.harness import manifest as mf
    from go_libp2p_pubsub_tpu.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    manifest = mf.load_manifest(ROOT)
    cell = mf.find_cell(manifest, args.workload)
    control = json.loads(args.control) if args.control else None
    graph_seeds = ([int(g) for g in args.graph_seeds.split(",")]
                   if args.graph_seeds else [None])
    for fault in (args.faults.split(",") if args.faults else [None]):
        worst, least = {}, {}
        for graph_seed in graph_seeds:
            for seed in (int(s) for s in args.seeds.split(",")):
                overrides = {"control": control, "fault": fault}
                if graph_seed is not None:
                    overrides["graph_seed"] = graph_seed
                out = bench_run.measure(
                    manifest, cell, seed, args.seconds, False,
                    jax.devices()[:cell["chips"]], time.perf_counter(),
                    overrides=overrides)
                res, run = out["result"], out["run"]
                nums = {x["name"]: x["value"] for x in res["compared"]}
                for k, v in nums.items():
                    worst[k] = max(worst.get(k, v), v)
                    least[k] = min(least.get(k, v), v)
                print(json.dumps({
                    "workload": args.workload, "seed": seed,
                    "graph_seed": graph_seed, "control": control,
                    "fault": fault, "correct": res["correct"],
                    "segments": res["attempted"],
                    "rounds_per_s": run["rounds_per_s"],
                    "seg_median_ms": run["seg_median_ms"],
                    "seg_p95_ms": run["seg_p95_ms"],
                    "setup_s": run["setup_s"], "check_s": run["check_s"],
                    "memory_peak_bytes": run["memory_peak_bytes"],
                    "numbers": {k: v for k, v in nums.items() if v}}),
                    flush=True)
        print(json.dumps({"workload": args.workload, "control": control,
                          "fault": fault, "largest": worst,
                          "smallest": least}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
