"""``benchmark/tools/traced.py`` (same arguments; the seven ``setup_*``
readers are read if ``--readers`` is left out), then what the program's
host-span recorder (``perf/spans.py``) holds of the run, one JSON line on
stderr: every span name with its count and seconds, the recorder's
counters, the set-up spans' self time (a span less its children), the
programs ``compile_or_get_cached`` took longest over with the span they
ran under and whether the persistent cache held them, and what jax spent
on the window's own program. What a cell's ``setup_s`` is made of, from
inside the program; ``setup_parts`` on the line before is the harness's
cut of the same run."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT]
TOP_PROGRAMS = 12


def summary(recorded: list, window_name: str) -> dict:
    """``recorded`` (``spans.recorded()``) by parent and by program."""
    by_id = {s.id: s for s in recorded}
    children: dict = {}
    for s in recorded:
        if s.parent in by_id and not s.name.startswith("compile.cache_"):
            children[s.parent] = (children.get(s.parent, 0)
                                  + s.end_ns - s.start_ns)
    self_s = {}
    for s in recorded:
        if s.name.startswith("setup."):
            self_s[s.name] = self_s.get(s.name, 0.0) + (
                s.end_ns - s.start_ns - children.get(s.id, 0)) / 1e9
    # a program's hit or miss is the cache event recorded inside its
    # ``compile.backend`` span
    marks = [s for s in recorded
             if s.name in ("compile.cache_hit", "compile.cache_miss")]
    programs = []
    for s in recorded:
        if s.name != "compile.backend":
            continue
        mark = [m.name[len("compile.cache_"):] for m in marks
                if s.start_ns <= m.end_ns <= s.end_ns]
        under = by_id.get(s.parent)
        programs.append({"fun_name": s.attrs.get("fun_name"),
                         "seconds": (s.end_ns - s.start_ns) / 1e9,
                         "cache": mark[0] if mark else None,
                         "under": under.name if under else None})
    programs.sort(key=lambda p: -p["seconds"])
    by_under: dict = {}
    for p in programs:
        u = by_under.setdefault(p["under"], {"n": 0, "seconds": 0.0})
        u["n"] += 1
        u["seconds"] += p["seconds"]
    # the window's first trace, lowering and compile (a later one, the
    # readers' stage map, is overwritten by the earlier)
    window = {s.name: (s.end_ns - s.start_ns) / 1e9 for s in reversed(recorded)
              if s.name in ("compile.trace", "compile.lower",
                            "compile.backend")
              and window_name in str(s.attrs.get("fun_name", ""))}
    return {"setup_self_seconds": self_s,
            "backend_by_span": by_under, "window_first": window,
            "top_programs": programs[:TOP_PROGRAMS]}


def main(argv=None) -> int:
    from benchmark.harness import setup
    from benchmark.tools import traced
    from go_libp2p_pubsub_tpu.perf import spans, stages

    argv = list(sys.argv[1:] if argv is None else argv)
    if "--readers" not in argv:
        argv += ["--readers", ",".join(setup.NAMES)]
    rc = traced.main(argv)
    names = {name: {"n": len(seconds), "seconds": sum(seconds)}
             for name, seconds in spans.seconds_by_name().items()}
    print(json.dumps({"counts": spans.counts(), "spans": names,
                      **summary(spans.recorded(), stages.window_name())}),
          file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
