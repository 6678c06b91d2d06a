"""The two sets of runs a bound is set from: every seed of ``--seeds`` run
once per set through the command of BENCHMARK.json, each run a process of
its own (this one never touches JAX, so the chip is the child's). Prints
each run's result line and, per metric, both sets' medians and spreads.

  python3 benchmark/tools/sets.py --workload lattice-100k.stepped \\
      --seeds 2147483659,2147483693,... [--sets 2] [--traces 0]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import stats  # noqa: E402


def one(command, workload, seed, seconds, trace):
    cmd = command + ["--workload", workload, "--seed", str(seed),
                     "--seconds", str(seconds), "--trace", str(trace)]
    out = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                         timeout=1500)
    if out.returncode != 0 or not out.stdout.strip():
        print(out.stderr[-3000:], file=sys.stderr)
        raise SystemExit(f"run failed: {' '.join(cmd)} rc={out.returncode}")
    res = json.loads(out.stdout.strip().splitlines()[-1])
    logs = [ln for ln in out.stderr.splitlines() if ln.startswith('{"workload"')]
    return res, (json.loads(logs[-1]) if logs else {})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traces", type=int, default=0,
                    help="traced runs to add after the sets, on fresh seeds")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        manifest = json.load(f)
    command, seconds = manifest["command"], manifest["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    table = []
    for k in range(args.sets):
        values = {}
        for seed in seeds:
            res, logline = one(command, args.workload, seed, seconds, 0)
            row = {m: v["value"] for m, v in res["metrics"].items()}
            print(json.dumps({"set": k, "seed": seed, "correct": res["correct"],
                              "segments": res["attempted"], **row,
                              "setup_parts": logline.get("setup_parts"),
                              "span_median_ms": logline.get("span_median_ms"),
                              "memory_peak_bytes":
                                  res["device"]["memory_peak_bytes"]}),
                  flush=True)
            if not res["correct"]:
                print(json.dumps(res["compared"]), flush=True)
            for m, v in row.items():
                values.setdefault(m, []).append(v)
        table.append(values)
    for m in table[0]:
        print(json.dumps({
            "workload": args.workload, "metric": m,
            "medians": [statistics.median(t[m]) for t in table],
            "spreads": [stats.spread(t[m]) for t in table],
            "first_run_left_out_setup_median": [
                statistics.median(t[m][1:]) for t in table]
            if m == "setup_s" else None}), flush=True)
    for j in range(args.traces):
        res, _ = one(command, args.workload, seeds[-1] + 1 + j, seconds, 1)
        print(json.dumps({"trace": j, "seed": seeds[-1] + 1 + j, **res}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
