"""The readings ``sybil-50k``'s file states (``mesh_build_rounds``,
``full_delivery_rounds``, ``sybil_mesh_share``): runs of several lengths of
one cell, many seeds in one process (the window is compiled once), each
judged by the cell's reference and read besides for how long the slowest
HONEST first receipt of an honest-origin message took by the phase the
message was born in, what share of the honest peers' mesh edges point at
sybils, and whose validation queues overflowed when. Run on the chip:

  python3 benchmark/tools/sybil_readings.py --workload sybil-50k.stepped \\
      --seeds 3500000101,3500000102 --segments 12,80 \\
      [--control '{"program_score": {"mesh_message_deliveries_weight": 0}}']
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
    ap.add_argument("--segments", default="12",
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
    he = int(config["heartbeat_every"])
    windows = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        for segments in (int(k) for k in args.segments.split(",")):
            built = builder.build(config, seed, jax.devices()[:cell["chips"]],
                                  n_peers=args.n_peers, control=control)
            make = built.make_window
            built.make_window = lambda u: windows.setdefault(u, make(u))
            run = driver.run(built, mix, seed, 1e9, False, time.perf_counter(),
                             max_segments=segments)
            ans = run["answers"]
            numbers = reference.check(
                ans, built.graph, built.subs, built.config, run["tail"],
                run["rounds_run"], run["summaries"])
            sybil = ans["sybil"]
            honest = ~sybil
            birth = ans["msg_birth"].astype(np.int64)
            fr = ans["first_round"].astype(np.int64)[honest]
            live = np.flatnonzero(
                (birth >= 0) & honest[np.clip(ans["msg_origin"], 0, None)])
            took = np.where(fr[:, live] >= 0, fr[:, live] - birth[live], -1)
            by_phase = {}
            for b, s, miss in zip(birth[live], took.max(axis=0),
                                  (fr[:, live] < 0).sum(axis=0)):
                row = by_phase.setdefault(int(b) // he * he, [0, 0])
                row[0] = max(row[0], int(s))
                row[1] += int(miss)
            nbr, ok = built.graph["nbr"], built.graph["nbr_ok"]
            sybil_nbr = sybil[np.clip(nbr, 0, None)] & ok
            mesh = ans["mesh"][:, 0]
            last = ans["gater_last_throttle"].astype(np.int64)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "segments": segments,
                "control": control, "rounds_run": run["rounds_run"],
                "correct": all(x["value"] <= x["limit"] for x in numbers),
                "numbers": {x["name"]: x["value"] for x in numbers
                            if x["value"]},
                "slowest_by_birth_phase": {str(k): v[0] for k, v in
                                           sorted(by_phase.items())},
                "missing_by_birth_phase": {str(k): v[1] for k, v in
                                           sorted(by_phase.items()) if v[1]},
                "honest_mesh_degree": [int(mesh[honest].sum(axis=1).min()),
                                       float(mesh[honest].sum(axis=1).mean())],
                "sybil_mesh_degree_mean": float(mesh[sybil].sum(axis=1).mean()),
                "negative_share_of_sybil_edges": float(
                    (ans["scores"][sybil_nbr] < 0).mean()),
                "negative_share_of_honest_edges": float(
                    (ans["scores"][ok & ~sybil_nbr] < 0).mean()),
                "honest_last_throttle_max": int(last[honest].max()),
                "honest_ever_throttled": int((last[honest] >= 0).sum()),
                "sybils_ever_throttled": int((last[sybil] >= 0).sum()),
                "sybils_throttled_last_phase": int(
                    (last[sybil] >= run["rounds_run"] - he).sum()),
                "p3_active_edges": int(ans["mmd_active"].sum()),
                "seg_median_ms": run["seg_median_ms"],
                "memory_peak_bytes": run["memory_peak_bytes"],
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
