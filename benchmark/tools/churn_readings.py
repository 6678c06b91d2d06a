"""The readings ``churn-100k``'s file states (``full_delivery_rounds``,
``catchup.floor``): runs of several lengths of one cell, many seeds in one
process through ONE built window (the builder draws the rows and the
state from ``built.seed``), each judged by the cell's reference, every
number printed. Run on the chip:

  python3 benchmark/tools/churn_readings.py --workload churn-100k.stepped \\
      --seeds 3700000101,3700000102 --segments 7,60 \\
      [--control '{"program_mesh_params": {"D_lazy": 0, "gossip_factor": 0}}']
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
    ap.add_argument("--segments", default="7",
                    help="window lengths to run each seed at, in segments")
    ap.add_argument("--control", default=None)
    ap.add_argument("--n-peers", type=int, default=None,
                    help="a rehearsal at another size (no device reading)")
    args = ap.parse_args(argv)

    import jax

    from benchmark.harness import manifest as mf
    from go_libp2p_pubsub_tpu.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    manifest = mf.load_manifest(ROOT)
    cell = mf.find_cell(manifest, args.workload)
    config = mf.load_config(manifest, cell["config"], ROOT)
    mix = mf.load_traffic(cell["traffic"], ROOT)
    builder = mf.load_plugin("builders", config["builder"], ROOT)
    driver = mf.load_plugin("drivers", mix["driver"], ROOT)
    reference = mf.load_plugin("references", config["reference"], ROOT)
    control = json.loads(args.control) if args.control else None
    built = builder.build(config, 0, jax.devices()[:cell["chips"]],
                          n_peers=args.n_peers, control=control)
    for seed in (int(s) for s in args.seeds.split(",")):
        for segments in (int(k) for k in args.segments.split(",")):
            built.seed = seed
            run = driver.run(built, mix, seed, 1e9, False, time.perf_counter(),
                             max_segments=segments)
            t0 = time.perf_counter()
            numbers = reference.check(
                run["answers"], built.graph, built.subs, built.config,
                run["tail"], run["rounds_run"], run["summaries"])
            print(json.dumps({
                "workload": args.workload, "seed": seed, "segments": segments,
                "control": control, "rounds_run": run["rounds_run"],
                "correct": all(x["value"] <= x["limit"] for x in numbers),
                "failed": [x["name"] for x in numbers
                           if x["value"] > x["limit"]],
                "numbers": {x["name"]: x["value"] for x in numbers
                            if x["value"]},
                "negative_scores": int((run["answers"]["scores"] < 0).sum()),
                "seg_median_ms": run["seg_median_ms"],
                "dispatch_median_ms": 1e3 * sorted(run["spans"]["dispatch"])[
                    len(run["spans"]["dispatch"]) // 2],
                "check_s": time.perf_counter() - t0,
                "memory_peak_bytes": run["memory_peak_bytes"],
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
