"""``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one cell, once, in one process, on the TPU this machine
holds. Prints one JSON object as the last line of standard output.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result: there is no platform option.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # process start, as near as Python lets us

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(**fields) -> None:
    print(json.dumps(fields), file=sys.stderr, flush=True)


def measure(manifest: dict, cell: dict, seed: int, seconds: float,
            traced: bool, devices, t_start: float, root: str = ROOT,
            overrides: dict | None = None) -> dict:
    """Build the cell on ``devices``, run its driver, judge its answers and
    reduce the run to the result object.

    ``overrides`` is for the tests and ``tools/sweep.py`` alone; the
    command passes none. ``n_peers`` and ``max_segments`` make a toy run,
    ``graph_seed`` draws another graph, ``fault`` plants one of
    ``harness/faults.py`` under the timed path, and ``control`` switches on
    a control of "How correct is decided" (``chaos_loss_rate``: the
    program's lossy links; ``program_mesh_params``: the program built with
    other mesh parameters than the file states; ``score_dtype``: the score
    plane recomputed in a lower precision and put in the program's place).
    A run with a fault or a control must come out as not correct."""
    ov = overrides or {}
    control = ov.get("control")
    from benchmark.harness import manifest as mf
    from benchmark.harness import trace as trace_mod

    config = mf.load_config(manifest, cell["config"], root)
    mix = mf.load_traffic(cell["traffic"], root)
    builder = mf.load_plugin("builders", config["builder"], root)
    driver = mf.load_plugin("drivers", mix["driver"], root)
    reference = mf.load_plugin("references", config["reference"], root)

    if "graph_seed" in ov:
        config["graph"] = dict(config["graph"], seed=int(ov["graph_seed"]))
    t_imported = time.perf_counter()
    built = builder.build(config, seed, devices, n_peers=ov.get("n_peers"),
                          control=control)
    if ov.get("fault"):
        from benchmark.harness import faults

        faults.plant(built, ov["fault"])
    run = driver.run(built, mix, seed, seconds, traced, t_start,
                     max_segments=ov.get("max_segments"))
    run["setup_parts"]["until_build"] = t_imported - t_start
    run["device_kind"] = devices[0].device_kind
    run["rounds_per_phase"] = built.rounds_per_phase
    if run["device_trace"] is not None:
        run["trace"] = trace_mod.reduce(run["device_trace"])

    # the comparison that decides `correct`, after the window and after
    # the memory peak was read; host numpy, so it moves neither
    t0 = time.perf_counter()
    answers = run.pop("answers")
    if control and control.get("score_dtype"):
        answers["scores"] = reference.scores_from_counters(
            answers, built.graph, built.subs, built.config["score"],
            reference.dtype_of(control["score_dtype"])).astype("float32")
    numbers = reference.check(
        answers, built.graph, built.subs, built.config,
        run["tail"], run["rounds_run"], run["summaries"])
    run["check_s"] = time.perf_counter() - t0
    correct = all(x["value"] <= x["limit"] for x in numbers)

    group = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in mf.cell_metrics(manifest, cell["name"], group):
        if group == "end_to_end":
            value = run.get(m["name"])
        else:
            value = mf.load_plugin("readers", m["name"], root).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": run["memory_peak_bytes"],
    }
    result = {
        "correct": correct,
        "attempted": run["segments"],
        "failed": sum(1 for rounds, tick in run["summaries"] if rounds != tick),
        "metrics": metrics,
        "device": device,
    }
    if traced:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = {
            "device_ops": run["trace"]["device_ops"],
            "idle_gaps": run["trace"]["idle_gaps"],
        }
    result["compared"] = numbers
    return {"result": result, "run": run}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark.harness import manifest as mf

    manifest = mf.load_manifest(ROOT)
    cell = mf.find_cell(manifest, args.workload)

    import jax

    from go_libp2p_pubsub_tpu.compile_cache import enable_persistent_cache

    t_jax = time.perf_counter()
    devices = jax.devices()
    t_devices = time.perf_counter()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"benchmark: needs {cell['chips']} TPU chip(s), found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]
    # <checkout>/.jax_cache, or where JAX_COMPILATION_CACHE_DIR says. The
    # program keeps programs that compiled in under 0.5 s out of the cache;
    # building the net and the state runs dozens of those, in every run, so
    # the benchmark keeps them too
    enable_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    out = measure(manifest, cell, args.seed, args.seconds, bool(args.trace),
                  devices, T_START)
    run, result = out["run"], out["result"]
    run["setup_parts"].update(import_jax=t_jax - T_START,
                              jax_devices=t_devices - t_jax)
    log(workload=cell["name"], seed=args.seed, platform=devices[0].platform,
        device_kind=devices[0].device_kind, device_count=len(devices),
        peak_bytes_in_use=run["memory_peak_bytes"],
        setup_s=run["setup_s"], setup_parts=run["setup_parts"],
        window_s=run["window_s"],
        rounds=run["rounds"], segments=run["segments"],
        ahead_segments=run["ahead_segments"],
        rounds_per_s=run["rounds_per_s"], seg_p95_ms=run["seg_p95_ms"],
        seg_median_ms=run["seg_median_ms"],
        span_median_ms={k: 1e3 * sorted(v)[len(v) // 2]
                        for k, v in run["spans"].items()},
        window_compiles=run["window_compiles"], check_s=run["check_s"],
        receipts_last=run["receipts"][-1])
    for x in result["compared"]:
        print(f"compared {x['name']} = {x['value']} (limit {x['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
