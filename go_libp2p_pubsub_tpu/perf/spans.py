"""The program's host spans, recorded in one place.

``perf/stages.py`` names what the DEVICE does; this module records what
the HOST does where the program's own host code runs: the set-up
(``Net.build``, the state init, the step's build, every program's trace /
lower / compile-or-load) and the served loop's segments. No other module
of the package opens a profiler annotation or keeps a clock pair of its
own for a span named here (tests/test_spans.py).

* ``span(name, **attrs)``: context manager and decorator. Records
  ``(id, parent, name, start_ns, end_ns, attrs)`` on
  ``time.perf_counter_ns`` (``parent``: the span open in this context when
  it began) and lies under ``stages.host_scope(name)``, so a running
  profiler shows it in the host plane of the device's own trace. ``name``
  is one of ``SPANS``.
* ``watch_compiles()``: turns the ``jax.monitoring`` events jax emits
  while it traces, lowers and compiles (or loads from the persistent
  cache) into child spans of whatever span is open: ``compile.trace`` /
  ``compile.lower`` / ``compile.backend`` with ``attrs["fun_name"]``
  (``backend`` wraps ``compile_or_get_cached``: it IS compile or cache
  load), and the cache's two counters as zero-length events
  (``compile.cache_hit`` / ``cache_miss``). jax counts a miss when it
  WRITES the compiled program: one the cache's thresholds keep out is a
  compile without a miss. Of the traces only a program's outermost is
  kept (a window's trace runs thousands of inner ``jit`` traces, ``jnp``'s
  own functions): the last one named as the lowered program is
  (``jit(<name>)``; one jax cannot name, a ``partial``, keeps none). The
  spans' clock is not ``time.time``, which jax's time-span listener
  reports on: a section's start is the listener's ``now`` less the
  duration. A cached jit call emits nothing: the steady state pays nothing.
* ``recorded()`` hands out the newest ``KEPT_SPANS`` as plain tuples in the
  order they ended, ``seconds_by_name()`` their durations, ``counts()`` the
  compile counters, ``clear()`` empties all. Nothing is written anywhere.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import itertools
import threading
import time
from typing import NamedTuple

from . import stages

SPANS = (
    # the set-up every caller pays (moves ``setup_s``)
    "setup.net_build", "setup.net_build.plan", "setup.net_build.planes",
    "setup.state_init", "setup.step_build",
    # off ``jax.monitoring``
    "compile.trace", "compile.lower", "compile.backend",
    "compile.cache_hit", "compile.cache_miss",
    # ``serve.Supervisor.run`` (the operator's report row, ``host_ms``)
    "serve.restore", "serve.segment", "serve.stack_args", "serve.dispatch",
    "serve.probe_readback", "serve.ev_drain", "serve.checkpoint_save",
    "serve.heartbeat_write", "serve.report_row",
    # ``ensemble.WindowRunner.run`` (``EnsembleRun.seconds``)
    "ensemble.run",
)
#: an always-on service must not grow
KEPT_SPANS = 4096

_COMPILE = "/jax/core/compile/"
_CACHE = "/jax/compilation_cache/"
#: jax's events (the first three carry a duration) -> the span's name
_JAX = {
    _COMPILE + "jaxpr_trace_duration": "compile.trace",
    _COMPILE + "jaxpr_to_mlir_module_duration": "compile.lower",
    _COMPILE + "backend_compile_duration": "compile.backend",
    _CACHE + "cache_hits": "compile.cache_hit",
    _CACHE + "cache_misses": "compile.cache_miss",
}
#: ``counts()``: how many of which event were recorded
_COUNTED = {"programs_compiled": "compile.backend",
            "cache_hits": "compile.cache_hit",
            "cache_misses": "compile.cache_miss"}


class Span(NamedTuple):
    id: int
    parent: int | None
    name: str
    start_ns: int
    end_ns: int
    attrs: dict


_RING: collections.deque = collections.deque(maxlen=KEPT_SPANS)
_IDS = itertools.count(1)
_OPEN = contextvars.ContextVar("gs_host_span", default=None)
_COUNTS: collections.Counter = collections.Counter()
_PENDING = threading.local()     # .traces: {fun_name: its last trace here}
_watching = False


class span(contextlib.ContextDecorator):
    """One host span of ``SPANS``; ``seconds`` is its duration once it has
    ended. As a decorator it records a span per call."""

    def __init__(self, name: str, **attrs):
        if name not in SPANS:
            raise ValueError(f"no span {name!r} in perf.spans.SPANS")
        self.name, self.attrs = name, attrs

    def _recreate_cm(self):
        return span(self.name, **self.attrs)

    def __enter__(self):
        watch_compiles()
        self.id = next(_IDS)
        self._parent = _OPEN.get()
        _OPEN.set(self.id)
        self._scope = stages.host_scope(self.name)
        self._scope.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.end_ns = time.perf_counter_ns()
        self._scope.__exit__(*exc)
        _OPEN.set(self._parent)
        _RING.append(Span(self.id, self._parent, self.name, self.start_ns,
                          self.end_ns, self.attrs))
        return False

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


def _record(name: str, start_ns: int, end_ns: int, attrs: dict) -> None:
    _RING.append(Span(next(_IDS), _OPEN.get(), name, start_ns, end_ns, attrs))
    _COUNTS[name] += 1


def _on_duration(event: str, seconds: float, **kw) -> None:
    name = _JAX.get(event)
    if name is None:
        return
    now = time.perf_counter_ns()
    found = (name, now - int(seconds * 1e9), now, kw)
    traces = vars(_PENDING).setdefault("traces", {})
    if name == "compile.trace":
        traces[kw.get("fun_name")] = found   # kept if this one is lowered
        return
    if name == "compile.lower":      # of ``jit(<the traced name>)``
        outermost = traces.get(str(kw.get("fun_name"))[4:-1])
        traces.clear()               # the others ran inside it
        if outermost:
            _record(*outermost)
    _record(*found)


def _on_event(event: str, **kw) -> None:
    if event in _JAX:
        now = time.perf_counter_ns()
        _record(_JAX[event], now, now, kw)


def watch_compiles() -> None:
    """Register the ``jax.monitoring`` listeners, once a process (called by
    ``compile_cache.enable_persistent_cache`` and the first ``span``)."""
    global _watching
    if _watching:
        return
    _watching = True
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def recorded() -> list:
    """The newest ``KEPT_SPANS`` spans and events, as they ended."""
    return list(_RING)


def seconds_by_name(prefix: str = "") -> dict:
    """``{name: [seconds of each recorded span]}`` for the names that begin
    with ``prefix``, in the order the spans ended."""
    by: dict = {}
    for s in _RING:
        if s.name.startswith(prefix):
            by.setdefault(s.name, []).append((s.end_ns - s.start_ns) * 1e-9)
    return by


def counts() -> dict:
    """Programs compiled or loaded, persistent-cache hits and misses, so
    far (since ``clear``)."""
    return {k: _COUNTS[name] for k, name in _COUNTED.items()}


def clear() -> None:
    _RING.clear()
    _COUNTS.clear()
