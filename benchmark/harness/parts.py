"""Device time by part: the trace's op events inside the window's programs
that lie inside one of the program's finer scopes (``gsx.<part>``,
``go_libp2p_pubsub_tpu.perf.stages.PARTS``): a mechanism inside the
stages, such as the fanout path. A part's ops stay booked to their stage
too (``harness/stages.py``): parts do not add up to the window, and a
fusion whose root lies outside the scope is not counted.

A trace of several device planes reduces to the mean over them, as
``harness/stages.py``'s does. Where the program gives no part map (a
commit before the scopes, its answer for a sharded window today, no one
traced window in the trace), every function here returns ``None`` and
raises nothing.
"""

from __future__ import annotations

from benchmark.harness import stages

MEMO_KEY = "part_trace"


def part_seconds(run: dict, windows=None) -> dict | None:
    """``{part: self seconds}`` of the ops inside the window's modules
    over the traced window, worked out once per run."""
    if MEMO_KEY in run:
        return run[MEMO_KEY]
    tr = run.get("device_trace")
    if windows is None:
        windows = stages.traced_windows()
    window = stages.the_window(tr, windows)
    part_of = getattr(window, "parts", lambda: None)()
    out = None
    if part_of is not None:
        out, _ = stages.self_seconds_by(tr, window.module_name, part_of.get)
    run[MEMO_KEY] = out
    return out


def part_us_per_round(run: dict, part: str):
    """One part's device self time in microseconds per delivery round
    (0.0 for a part the window never traced, such as ``fanout`` in a cell
    built without fanout slots)."""
    seconds = part_seconds(run)
    if seconds is None or not run.get("rounds"):
        return None
    return 1e6 * seconds.get(part, 0.0) / run["rounds"]
