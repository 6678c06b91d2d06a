"""Device time by the engine's stage: the trace's op events inside the
window's programs, summed by the stage map the program gives for its
compiled window (``go_libp2p_pubsub_tpu.perf.stages``: the innermost
``gs.*`` named scope of each instruction, ``unscoped`` for what XLA
itself puts in).

The trace names an op by its instruction name, and instruction names are
per program: ``fusion.1`` of the harness's own ``jit_summary`` is another
op than the window's. So only op events that begin inside a module event
of the window's name (``jit_gs_window_v1(...)``) are looked up. Self
times are ``trace.self_times``: a ``while`` spans its body's ops.

A trace of several device planes (a window sharded over chips) reduces
to the MEAN over the planes, as ``trace.reduce`` takes ``busy_s``; one
plane reads its own sums. Where the program has no such module (a commit
before the scopes), or gives no map for its window (its answer for a
sharded window, today), every function here returns ``None`` and raises
nothing.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness import trace

MEMO_KEY = "stage_trace"
UNSCOPED = "unscoped"


def traced_windows() -> list | None:
    """The program's registry of traced windows, ``None`` on a commit that
    has none."""
    try:
        from go_libp2p_pubsub_tpu.perf import stages as program
    except ImportError:
        return None
    return program.traced_windows()


def module_base(name: str) -> str:
    """``jit_gs_window_v1(6857045950223489047)`` -> ``jit_gs_window_v1``."""
    return name.split("(", 1)[0]


def ops_inside(dev: dict, module_name: str) -> list:
    """The op events of one device plane that begin inside a module event
    of that name."""
    mods = sorted(dev["modules"], key=lambda m: m[1])
    if not mods or not dev["ops"]:
        return []
    starts = np.asarray([m[1] for m in mods], np.int64)
    ends = starts + np.asarray([m[2] for m in mods], np.int64)
    ours = np.asarray([module_base(m[0]) == module_name for m in mods])
    at = np.asarray([e[1] for e in dev["ops"]], np.int64)
    i = np.searchsorted(starts, at, side="right") - 1
    j = np.maximum(i, 0)
    keep = (i >= 0) & (at < ends[j]) & ours[j]
    return [e for e, k in zip(dev["ops"], keep) if k]


def self_seconds_by(device_trace: dict, module_name: str, key_of) -> tuple:
    """``({key: self seconds}, op events)`` inside the modules of that name,
    each summed over the device planes and divided by their number (the
    mean ``trace.reduce`` takes for ``busy_s``); ``key_of(op name)`` gives
    the key an op is booked to, ``None`` to leave it out. One plane reads
    its own sums."""
    n_dev = len(device_trace["devices"])
    seconds: dict = {}
    ops = 0
    for dev in device_trace["devices"].values():
        inside = ops_inside(dev, module_name)
        ops += len(inside)
        for name, sec in trace.self_times(inside).items():
            key = key_of(name)
            if key is not None:
                seconds[key] = seconds.get(key, 0.0) + sec
    return {k: v / n_dev for k, v in seconds.items()}, ops / n_dev


def reduce(device_trace: dict, window) -> dict | None:
    """``{"seconds": {stage: self seconds}, "ops": op events, "unmapped":
    op names the map lacks}`` inside the modules of ``window`` (an entry of
    the program's registry), the MEAN over the device planes of the trace.
    An op the compiled text does not hold is none of the program's: it is
    booked as ``unscoped``, and counted. A window that gives no map (the
    program's answer for a sharded one, today) reduces to ``None``."""
    stage_of = window.stages()
    if stage_of is None:
        return None
    unmapped = []

    def key_of(name):
        if name not in stage_of and name not in unmapped:
            unmapped.append(name)
        return stage_of.get(name, UNSCOPED)

    seconds, ops = self_seconds_by(device_trace, window.module_name, key_of)
    if not ops:
        return None
    return {"seconds": seconds, "ops": ops, "unmapped": unmapped}


def the_window(tr: dict | None, windows):
    """The one traced window whose module ran in the trace. Two windows of
    one module name that both ran cannot be told apart by their ops'
    names: ``None``."""
    if not tr or not windows:
        return None
    ran = {module_base(m[0]) for dev in tr["devices"].values()
           for m in dev["modules"]}
    ours = [w for w in windows if w.module_name in ran]
    return ours[0] if len(ours) == 1 else None


def stage_trace(run: dict, windows=None) -> dict | None:
    """``reduce`` of the run's trace over the one traced window whose
    module ran in it (``the_window``), worked out once per run (ten
    readers, one reduction)."""
    if MEMO_KEY in run:
        return run[MEMO_KEY]
    tr = run.get("device_trace")
    if windows is None:
        windows = traced_windows()
    window = the_window(tr, windows)
    out = None if window is None else reduce(tr, window)
    run[MEMO_KEY] = out
    return out


def stage_seconds(run: dict, windows=None) -> dict | None:
    """``{stage: seconds}`` of device self time inside the window's
    modules over the traced window."""
    red = stage_trace(run, windows)
    return None if red is None else red["seconds"]


def stage_us_per_round(run: dict, stage: str):
    """One stage's device self time in microseconds per delivery round
    (0.0 for a stage that ran no op, such as ``score`` with scoring off)."""
    seconds = stage_seconds(run)
    if seconds is None or not run.get("rounds"):
        return None
    return 1e6 * seconds.get(stage, 0.0) / run["rounds"]
