"""The set-up as the program itself recorded it, reduced to the seven
``setup_*`` per-layer numbers (all move ``setup_s``).

The program's host-span recorder (``go_libp2p_pubsub_tpu/perf/spans.py``)
holds one tuple ``(id, parent, name, start_ns, end_ns, attrs)`` per span:
``setup.net_build``, ``setup.step_build`` and ``setup.state_init`` around
the callees, and, off
``jax.monitoring``, ``compile.trace`` / ``compile.lower`` /
``compile.backend`` with ``attrs["fun_name"]`` for every program jax
traced, lowered and compiled or loaded from the persistent cache, and a
zero-length ``compile.cache_miss`` for every program it wrote there.

"Up to the window" means: ended no later than the FIRST ``compile.backend``
span whose ``fun_name`` holds the window's name. The window's own compile
is the anchor, so no number here needs a time from the harness; what a
traced run compiles after it (the readers' stage maps, a second window)
is left out. Where no window was compiled everything recorded counts.

Not in the seven: imports, ``jax.devices()``, the harness's graph draw, the
first call's run on the device, the summary program (``setup_parts`` on
the run's stderr line sizes them).
"""

from __future__ import annotations

import math

NAMES = ("setup_net_build_s", "setup_state_init_s", "setup_window_compile_s",
         "setup_small_programs_s", "setup_programs_compiled",
         "setup_cache_misses", "setup_step_build_s")
COMPILE = ("compile.trace", "compile.lower", "compile.backend")


def reduce(recorded: list, window_name: str) -> dict:
    """The seven numbers of one process's recorded spans."""
    def ours(span):
        return window_name in str(span[5].get("fun_name", ""))

    anchor = min((s[4] for s in recorded
                  if s[2] == "compile.backend" and ours(s)),
                 default=math.inf)
    upto = [s for s in recorded if s[4] <= anchor]

    def seconds(keep):
        return sum(s[4] - s[3] for s in upto if keep(s)) / 1e9

    backend = [s for s in upto if s[2] == "compile.backend"]
    return {
        "setup_net_build_s": seconds(lambda s: s[2] == "setup.net_build"),
        "setup_state_init_s": seconds(lambda s: s[2] == "setup.state_init"),
        "setup_window_compile_s": seconds(
            lambda s: s[2] in COMPILE and ours(s)),
        "setup_small_programs_s": seconds(
            lambda s: s[2] == "compile.backend" and not ours(s)),
        "setup_programs_compiled": len(backend),
        "setup_cache_misses": sum(1 for s in upto
                                  if s[2] == "compile.cache_miss"),
        "setup_step_build_s": seconds(lambda s: s[2] == "setup.step_build"),
    }


def read(name: str):
    """``name`` of ``NAMES`` from the program's recorder as it stands;
    ``None`` on a commit without the recorder."""
    try:
        from go_libp2p_pubsub_tpu.perf import spans, stages
    except ImportError:
        return None
    return reduce(spans.recorded(), stages.window_name())[name]
