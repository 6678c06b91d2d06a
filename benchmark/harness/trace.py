"""From the profiler's trace to numbers: the reduction every PR shares.

Two stages. ``extract`` reads an ``.xplane.pb`` with nothing but JAX
(``jax.profiler.ProfileData``) into plain lists of ``[name, start_ns,
duration_ns]``: per device plane the ops (line ``XLA Ops``) and the
programs (line ``XLA Modules``), and the harness's own host spans
(``bench.*`` ``TraceAnnotation``s). ``reduce`` turns those lists into
busy and idle seconds, the idle time by what the host was doing, and the
ops that took most time. A recorded extract is kept under
``benchmark/data/`` and pinned by a test.

XLA's op names are fusion names (``fusion.123``): they change with any
recompile, so ``device_ops`` says where the time goes today and is not a
key to compare across PRs.
"""

from __future__ import annotations

import glob
import os

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
DEVICE_PLANE_PREFIX = "/device:TPU:"


class TraceError(RuntimeError):
    """The trace does not hold what the reduction needs."""


def short_name(name: str) -> str:
    """The TPU trace names an op by its whole HLO instruction
    (``%fusion.12 = u32[...] fusion(...)``): keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def extract(profile, device_prefix: str = DEVICE_PLANE_PREFIX) -> dict:
    """Plain lists from a ``ProfileData``."""
    devices, spans, seen = {}, [], {}
    for plane in profile.planes:
        seen[plane.name] = [line.name for line in plane.lines]
        if plane.name.startswith(device_prefix):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                dev[key] = [[short_name(ev.name), int(ev.start_ns),
                             int(ev.duration_ns)] for ev in line.events]
            devices[plane.name] = dev
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name[len(SPAN_PREFIX):],
                                      int(ev.start_ns), int(ev.duration_ns)])
    if not devices or not any(d["ops"] for d in devices.values()):
        raise TraceError(f"no device ops in the trace; planes and lines: {seen}")
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "spans": spans}


def read_trace_dir(trace_dir: str) -> dict:
    """``extract`` of the one ``.xplane.pb`` under ``trace_dir``."""
    import jax

    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise TraceError(f"{len(found)} .xplane.pb files under {trace_dir}")
    return extract(jax.profiler.ProfileData.from_file(found[0]))


def merge(starts: np.ndarray, ends: np.ndarray):
    """Union of intervals as sorted, disjoint ``(starts, ends)``."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.concatenate([[True], s[1:] > reach[:-1]])
    first = np.flatnonzero(new)
    return s[first], np.maximum.reduceat(e, first)


def covered(ms: np.ndarray, me: np.ndarray, a, b) -> np.ndarray:
    """Length of the merged union ``(ms, me)`` inside each ``[a, b]``."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if ms.size == 0:
        return np.zeros_like(a)
    total = np.concatenate([[0.0], np.cumsum(me - ms)])

    def upto(x):
        i = np.searchsorted(ms, x, side="right")      # intervals begun by x
        j = np.maximum(i - 1, 0)
        over = np.where(i > 0, np.maximum(me[j] - x, 0.0), 0.0)
        return total[i] - over

    return upto(b) - upto(a)


def self_times(events: list) -> dict:
    """Seconds by op name, each event less what its children cover (a
    ``while`` spans its body's ops)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [ev[2] for ev in events]
    stack = []
    for i in order:
        _, start, dur = events[i]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            own[stack[-1]] -= dur
        stack.append(i)
    by_name: dict = {}
    for (name, _, _), ns in zip(events, own):
        by_name[name] = by_name.get(name, 0) + max(ns, 0)
    return {name: ns * 1e-9 for name, ns in by_name.items()}


def reduce(tr: dict, top: int = 10) -> dict:
    """Busy and idle seconds of the traced window, averaged over the
    device planes. The window runs from the first harness span's start to
    the last one's end. Idle time is split: ``inside_program`` while a
    program was on the device, otherwise by the harness span the host was
    in (``between_segments`` outside any)."""
    spans = tr["spans"]
    if not spans:
        raise TraceError("no harness span in the trace")
    w0 = float(min(s[1] for s in spans))
    w1 = float(max(s[1] + s[2] for s in spans))
    window_ns = w1 - w0
    busy_ns, idle_by, ops_by = [], {}, {}
    for dev in tr["devices"].values():
        arr = np.asarray([[e[1], e[1] + e[2]] for e in dev["ops"]], np.float64)
        arr = arr.reshape(-1, 2)
        bs, be = merge(arr[:, 0], arr[:, 1])
        busy = float(covered(bs, be, w0, w1))
        busy_ns.append(busy)
        mod = np.asarray([[e[1], e[1] + e[2]] for e in dev["modules"]],
                         np.float64).reshape(-1, 2)
        mod = np.clip(mod, w0, w1)
        ps, pe = merge(np.concatenate([arr[:, 0], mod[:, 0]]),
                       np.concatenate([arr[:, 1], mod[:, 1]]))
        on_device = float(covered(ps, pe, w0, w1))
        parts = {"inside_program": on_device - busy}
        for name in sorted({s[0] for s in spans}):
            a = np.asarray([s[1] for s in spans if s[0] == name], np.float64)
            b = a + np.asarray([s[2] for s in spans if s[0] == name], np.float64)
            parts[name] = float(np.sum((b - a) - covered(ps, pe, a, b)))
        parts["between_segments"] = (window_ns - busy) - sum(parts.values())
        for name, ns in parts.items():
            idle_by[name] = idle_by.get(name, 0.0) + ns
        inside = [e for e in dev["ops"] if w0 <= e[1] and e[1] + e[2] <= w1]
        for name, sec in self_times(inside).items():
            ops_by[name] = ops_by.get(name, 0.0) + sec
    n_dev = len(tr["devices"])
    ranked = sorted(ops_by.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_by.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy_ns) / n_dev * 1e-9,
        "device_ops": [[name, sec / n_dev] for name, sec in ranked],
        "idle_gaps": [[name, ns / n_dev * 1e-9] for name, ns in gaps],
    }
