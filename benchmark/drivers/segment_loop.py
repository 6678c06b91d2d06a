"""Driver kind ``segment_loop``: one client drives the scanned window in
segments and reads a scalar summary of each, with a fixed number of
segments dispatched ahead of the summary it waits for.

A segment is: draw its publish schedule from the seed and put it on the
device (``xs_assembly``), call the window (``dispatch``: returns once the
work is enqueued), enqueue the summary of the new state and, once
``ahead_segments`` later segments are in flight, read the oldest summary
back (``summary_readback``: returns once the device is done with that
segment). With ``ahead_segments`` 0 the loop is closed: a segment is
sent only when the last one's summary is on the host. The schedule does
not depend on the summaries, so a user who watches a run can send ahead;
what they wait for is the next summary.

``seg_p95_ms`` is the 95th percentile over ALL segments of the wall time
from one summary's arrival on the host to the next one's (the first from
the window's start): in a closed loop that is the segment's wall time,
and the intervals add up to the window. ``rounds_per_s`` is all rounds
over all wall seconds. When the time is up nothing more is sent, every
segment in flight is waited for, and the clock is read after that wait:
all the work counts, over all of that time.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import tempfile
import time

import numpy as np

from benchmark.harness import stats, trace, traffic

#: with ``--trace 1`` nothing more is sent after this long, and the window
#: is traced whole (a trace of the full window would be hundreds of MB); it
#: still holds this many segments, so that the run is old enough for
#: every comparison of ``correct`` to have something to judge, and has at
#: most this many segments in flight, so that the wait for them does not
#: triple the trace
TRACE_WINDOW_S = 2.0
TRACE_MIN_SEGMENTS = 6
TRACE_AHEAD_SEGMENTS = 2
SPANS = ("xs_assembly", "dispatch", "summary_readback")


def _annotate(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


def peak_memory(devices) -> int | None:
    peaks = []
    for d in devices:
        st = d.memory_stats()
        if st and "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run(built, mix: dict, seed: int, seconds: float, traced: bool,
        t_start: float, max_segments: int | None = None) -> dict:
    """Warm up, measure one window, and hand back its timings and the
    answers its final state holds. ``max_segments`` ends the window after
    that many segments whatever the clock says (the tests' toy runs)."""
    import jax
    import jax.numpy as jnp

    traffic.check_mix(mix)
    r = built.rounds_per_phase
    seg_rounds = int(mix["segment_phases"]) * r
    ahead = int(mix["ahead_segments"])
    if traced:
        ahead = min(ahead, TRACE_AHEAD_SEGMENTS)
    window = built.make_window(int(mix["unroll_phases"]))
    summary = built.summary_fn()
    spans = {name: [] for name in SPANS}
    in_flight = collections.deque()
    arrivals, summaries = [], []

    def assemble(i):
        po, pt, pv = traffic.segment_schedule(
            mix, seed, i, seg_rounds, built.n_peers, built.n_topics)
        return jnp.asarray(po), jnp.asarray(pt), jnp.asarray(pv)

    def send(state, i, annotate):
        t0 = time.perf_counter()
        with _annotate("xs_assembly", annotate):
            xs = assemble(i)
        t1 = time.perf_counter()
        with _annotate("dispatch", annotate):
            state = window(state, *xs)
        t2 = time.perf_counter()
        spans["xs_assembly"].append(t1 - t0)
        spans["dispatch"].append(t2 - t1)
        return state

    def read_back(state, keep, annotate):
        """Enqueue the summary of ``state`` (the next window call donates
        it) and read summaries back until ``keep`` are left in flight."""
        t0 = time.perf_counter()
        with _annotate("summary_readback", annotate):
            if state is not None:
                in_flight.append(summary(state))
            while len(in_flight) > keep:
                tick, receipts = in_flight.popleft()
                summaries.append((int(tick), int(receipts)))
                arrivals.append(time.perf_counter())
        spans["summary_readback"].append(time.perf_counter() - t0)

    # set-up: state, compile or cache load, one warm-up segment
    t_built = time.perf_counter()
    state = built.fresh()
    jax.block_until_ready(state)
    t_state = time.perf_counter()
    state_shapes = built.state_shapes(state)
    state = send(state, 0, False)
    read_back(state, 0, False)
    compiles_before = window._cache_size()
    t_warm = time.perf_counter()
    for timings in (*spans.values(), arrivals, summaries):
        timings.clear()

    limit = min(seconds, TRACE_WINDOW_S) if traced else seconds
    with contextlib.ExitStack() as stack:
        trace_dir = None
        if traced:
            trace_dir = stack.enter_context(
                tempfile.TemporaryDirectory(prefix="bench_trace_"))
            jax.profiler.start_trace(trace_dir)
        # no collector pause lands in a segment: the loop makes no cycles
        gc.collect()
        gc.disable()
        stack.callback(gc.enable)
        t_window = time.perf_counter()
        setup_s = t_window - t_start
        i = 0
        while True:
            i += 1
            state = send(state, i, traced)
            read_back(state, ahead, traced)
            enough = not traced or i >= TRACE_MIN_SEGMENTS
            if ((time.perf_counter() - t_window >= limit and enough)
                    or i == max_segments):
                break
        # the time is up: nothing more is sent, all that was sent counts
        read_back(None, 0, traced)
        t_close = arrivals[-1]
        device_trace = None
        if traced:
            jax.profiler.stop_trace()
            device_trace = trace.read_trace_dir(trace_dir)
    compiles = window._cache_size() - compiles_before
    memory_peak = peak_memory(built.devices)

    # the answers of the timed path, judged once the window has closed
    answers = built.answers(state)
    del state
    tail_rounds = -(-int(built.config["msg_slots"]) // int(mix["pubs_per_round"]))
    tail_segments = -(-tail_rounds // seg_rounds)
    first = max(0, i - tail_segments + 1)
    planes = [traffic.segment_schedule(mix, seed, j, seg_rounds,
                                       built.n_peers, built.n_topics)
              for j in range(first, i + 1)]
    tail = {"start": first * seg_rounds,
            "origin": np.concatenate([p[0] for p in planes]),
            "topic": np.concatenate([p[1] for p in planes])}
    window_s = t_close - t_window
    window_rounds = i * seg_rounds                   # less the warm-up segment
    rounds_run = window_rounds + seg_rounds
    seg_s = [b - a for a, b in zip([t_window] + arrivals, arrivals)]
    return {
        "setup_s": setup_s,
        "setup_parts": {"until_built": t_built - t_start,
                        "fresh_state": t_state - t_built,
                        "compile_and_warm_up": t_warm - t_state},
        "window_s": window_s,
        "rounds": window_rounds,
        "segments": i,
        "segment_rounds": seg_rounds,
        "ahead_segments": ahead,
        "rounds_per_s": stats.rate(window_rounds, window_s),
        "seg_p95_ms": 1e3 * stats.percentile(seg_s, 95),
        "seg_median_ms": 1e3 * stats.median(seg_s),
        "seg_s": seg_s,
        "spans": spans,
        "window_compiles": compiles,
        "memory_peak_bytes": memory_peak,
        "state_shapes": state_shapes,
        "device_trace": device_trace,
        "answers": answers,
        "tail": tail,
        "rounds_run": rounds_run,
        "summaries": [((k + 2) * seg_rounds, tick)
                      for k, (tick, _) in enumerate(summaries)],
        "receipts": [rc for _, rc in summaries],
    }
