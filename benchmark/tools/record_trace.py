"""Record a short traced window of one cell and write the trace's extract
(``harness/trace.extract``) as JSON: the recorded trace the reducer's test
pins. Run on the chip: ``python3 benchmark/tools/record_trace.py
--workload lattice-100k.stepped --segments 3 --out chiprun_out/x.json``.
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
    from go_libp2p_pubsub_tpu.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    manifest = mf.load_manifest(ROOT)
    cell = mf.find_cell(manifest, args.workload)
    out = bench_run.measure(
        manifest, cell, args.seed, 1e9, True, jax.devices()[:cell["chips"]],
        time.perf_counter(), overrides={"max_segments": args.segments})
    extract = out["run"]["device_trace"]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump({"device_kind": out["run"]["device_kind"],
                   "workload": args.workload, "segments": args.segments,
                   "rounds": out["run"]["rounds"], **extract}, f)
    print(json.dumps({k: out["run"]["trace"][k] for k in ("window_s", "busy_s")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
