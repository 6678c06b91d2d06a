"""How long the slowest first receipt of a message takes, by the round the
message was born in: young runs of one cell, many seeds in one process (the
window is compiled once). The reading ``mesh_build_rounds`` was set from.

  python3 benchmark/tools/delivery_by_birth.py --workload random-100k.stepped \\
      --seeds 3700000307,101,102 --segments 6
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--segments", default="6",
                    help="window lengths to run each seed at, in segments")
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
    he = int(config["heartbeat_every"])
    windows = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        for segments in (int(k) for k in args.segments.split(",")):
            built = builder.build(config, seed, jax.devices()[:cell["chips"]])
            make = built.make_window
            built.make_window = lambda u: windows.setdefault(u, make(u))
            run = driver.run(built, mix, seed, 1e9, False, time.perf_counter(),
                             max_segments=segments)
            ans = run["answers"]
            birth = ans["msg_birth"].astype(np.int64)
            fr = ans["first_round"].astype(np.int64)
            live = np.flatnonzero(birth >= 0)
            took = np.where(fr[:, live] >= 0, fr[:, live] - birth[live], -1)
            slowest = took.max(axis=0)                          # per message
            missing = (fr[:, live] < 0).sum(axis=0)
            by_phase = {}
            for b, s, miss in zip(birth[live], slowest, missing):
                row = by_phase.setdefault(int(b) // he * he, [0, 0])
                row[0] = max(row[0], int(s))
                row[1] += int(miss)
            worst = int(np.argmax(slowest))
            late_peers = np.flatnonzero(took[:, worst] == slowest[worst])
            print(json.dumps({
                "workload": args.workload, "seed": seed, "segments": segments,
                "rounds_run": run["rounds_run"],
                "slowest_by_birth_phase": {str(k): v[0] for k, v in
                                           sorted(by_phase.items())},
                "missing_by_birth_phase": {str(k): v[1] for k, v in
                                           sorted(by_phase.items())},
                "worst": {"birth": int(birth[live][worst]),
                          "rounds": int(slowest[worst]),
                          "peers": late_peers[:4].tolist(),
                          "peers_over_8_rounds": int(
                              (took[:, worst] > 8).sum()),
                          "mesh_degree_at_end": ans["mesh"][
                              late_peers[:4]].sum(axis=(1, 2)).tolist(),
                          "graph_degree": built.graph["nbr_ok"][
                              late_peers[:4]].sum(axis=1).tolist()},
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
