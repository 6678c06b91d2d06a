"""Record a short traced window of one cell with the stage map of its
compiled window, and say how the two fit: the recorded run that
``tests/benchmark_harness/test_benchmark_stages.py`` pins. Run on the
chip: ``python3 benchmark/tools/record_stages.py --workload
random-100k.stepped --segments 3 --out chiprun_out/x.json``.

The file holds the trace's extract (``harness/trace.extract``), the
window's module name and the stage of every instruction whose name any
op event in the trace bears (the whole map has tens of thousands of
entries). The line printed last counts what a reader needs to hold: op
events inside the window's modules, op names the map lacks, instructions
of the compiled text under a ``gs.*`` scope, seconds the map took the
first reader (a retrace, a cache load, the parse).
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
    ap.add_argument("--segments", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax

    from benchmark import run as bench_run
    from benchmark.harness import manifest as mf
    from benchmark.harness import stages
    from go_libp2p_pubsub_tpu.compile_cache import enable_persistent_cache
    from go_libp2p_pubsub_tpu.perf import stages as program

    enable_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    manifest = mf.load_manifest(ROOT)
    cell = mf.find_cell(manifest, args.workload)
    # what the readers' first call pays for the map: a retrace, a cache
    # load and the parse of the text
    lowering_s = []
    lower = program.TracedWindow.stages

    def timed(window):
        t0 = time.perf_counter()
        out = lower(window)
        lowering_s.append(time.perf_counter() - t0)
        return out

    program.TracedWindow.stages = timed
    run = bench_run.measure(
        manifest, cell, args.seed, 1e9, True, jax.devices()[:cell["chips"]],
        time.perf_counter(), overrides={"max_segments": args.segments})["run"]
    extract = run["device_trace"]
    ran = {stages.module_base(m[0]) for dev in extract["devices"].values()
           for m in dev["modules"]}
    (window,) = [w for w in stages.traced_windows() if w.module_name in ran]
    stage_of = window.stages()
    red = stages.stage_trace(run)
    named = {e[0] for dev in extract["devices"].values() for e in dev["ops"]}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump({"device_kind": run["device_kind"],
                   "workload": args.workload, "segments": args.segments,
                   "rounds": run["rounds"],
                   "module_name": window.module_name,
                   "stage_map": {k: v for k, v in stage_of.items()
                                 if k in named},
                   **extract}, f)
    print(json.dumps({
        "module_name": window.module_name, "modules_ran": sorted(ran),
        "map_s": lowering_s[0], "instructions": len(stage_of),
        "scoped_instructions": sum(v != stages.UNSCOPED
                                   for v in stage_of.values()),
        "ops_inside": red["ops"], "unmapped": red["unmapped"][:20],
        "n_unmapped": len(red["unmapped"]),
        "seconds": red["seconds"], "busy_s": run["trace"]["busy_s"],
        "window_s": run["trace"]["window_s"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
