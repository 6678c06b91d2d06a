"""Supervised service loop: fault-tolerant always-on simulation.

``ensemble.WindowRunner`` compiles a segment into one XLA dispatch;
this module wraps it in the machinery a *production* multi-hour run
needs (docs/DESIGN.md §17, the ROADMAP's streaming-service-loop item):

  * **pipeline** — a continuous double-buffered segment loop: segment
    k's window is dispatched asynchronously (JAX dispatch returns
    before the program finishes), segment k+1's stacked scan ``xs``
    are assembled host-side WHILE the device runs, and the only host
    sync per segment is the probe/verdict readback at the boundary.
    The segment length is the checkpoint quantum.
  * **durability** — rolling checksummed v6 checkpoints through
    :class:`serve.store.CheckpointStore` (atomic writes, retention,
    manifest): a ``kill -9`` at ANY point — including mid-checkpoint-
    write — resumes bit-exact vs the uninterrupted run, because resume
    replays deterministically from the last committed snapshot.
  * **detection & recovery** — the :mod:`oracle.probes` health probes
    (NaN/Inf sweep, events-monotone, delivery-floor) fold into every
    segment boundary alongside the scan-folded invariant oracle; on a
    violation the supervisor rolls back to the last good checkpoint and
    REPLAYS the segment per-dispatch with ``replay_check_every=1`` to
    localize the first violating dispatch, emits a forensic bundle
    (violation masks, NaN census, telemetry rows), and either retries
    the segment (transient corruption recovers to a bit-exact final
    state) or halts with the bundle once the per-segment recovery
    budget is spent.
  * **degradation & retry** — transient dispatch failures retried with
    exponential backoff + jitter through the injectable dispatch seam
    (serve/faults.py); when the budget is exhausted the loop degrades
    — shrink the segment length, then drop optional observers — before
    stopping. Rounds are never silently dropped.
  * **liveness** — an atomically-rewritten ``HEARTBEAT.json`` plus an
    incremental per-segment report (jsonl + self-contained HTML), so a
    multi-hour run is watchable and restartable from anywhere.

The supervised loop is OBSERVATIONAL: with probes off, invariants off
and no observer, the compiled window is identical to a bare
``WindowRunner`` program (the service-smoke census leg), and a clean
supervised run's final state tree is bit-exact vs the bare window.
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import logging
import math
import os
import random
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import checkpoint as _ckpt
from ..ensemble.runner import WindowRunner
from ..oracle import invariants as _oinv
from ..oracle.probes import HealthConfig, make_health_probe
from ..perf import spans
from .faults import TransientDispatchError
from .store import CheckpointStore, RetentionPolicy, write_json_atomic

_log = logging.getLogger(__name__)

#: the host spans a report row gives under ``host_ms`` (``serve.<name>``)
ROW_SPANS = ("stack_args", "dispatch", "probe_readback", "ev_drain",
             "checkpoint_save", "heartbeat_write", "report_row")

try:  # the real-dispatch-failure class worth retrying, when available
    from jax.errors import JaxRuntimeError as _JaxRuntimeError
except Exception:  # pragma: no cover — older jax
    class _JaxRuntimeError(Exception):
        pass


class ServiceError(RuntimeError):
    """Base class for supervised-loop failures."""


class ServiceHalted(ServiceError):
    """The loop stopped without completing: recovery/degradation budget
    exhausted. ``bundle`` is the last forensic bundle (dict with its
    on-disk ``path``) when a health violation caused the halt."""

    def __init__(self, msg: str, bundle: dict | None = None):
        super().__init__(msg)
        self.bundle = bundle


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """The supervised run's shape and policies. ``n_dispatches`` is the
    whole run in engine dispatches (rounds = n_dispatches ×
    rounds_per_dispatch); ``segment_len`` is the checkpoint quantum in
    dispatches and must divide ``n_dispatches``."""

    n_dispatches: int
    segment_len: int
    rounds_per_dispatch: int = 1
    health: HealthConfig | None = HealthConfig()
    retention: RetentionPolicy = RetentionPolicy()
    #: checkpoint every k committed segments (1 = every boundary)
    checkpoint_every_segments: int = 1
    max_retries: int = 3
    backoff_base_s: float = 0.05
    backoff_factor: float = 2.0
    backoff_jitter: float = 0.25
    max_recoveries_per_segment: int = 2
    #: localization cadence of the rollback replay (1 = every dispatch)
    replay_check_every: int = 1
    degrade: bool = True
    report_name: str | None = "service"
    #: drain the device EV counters into a host int64 accumulator at
    #: every committed segment boundary and ZERO them on device, so the
    #: i32 counters only ever hold ONE segment's growth no matter how
    #: long the service runs — the range audit's overflow horizons
    #: (RANGE_AUDIT.json: DUPLICATE_MESSAGE wraps i32 within ~4k rounds
    #: at the dense shards) stop bounding service lifetime. The running
    #: totals ride checkpoint meta and restore on resume, so a crash
    #: loses nothing. OFF by default: draining trades the bare-window
    #: bit-exactness contract (zeroed counters) for an unbounded horizon.
    drain_event_counters: bool = False

    def __post_init__(self):
        if self.n_dispatches < 1 or self.segment_len < 1:
            raise ValueError("n_dispatches and segment_len must be >= 1")
        if self.n_dispatches % self.segment_len:
            raise ValueError(
                f"segment_len {self.segment_len} does not divide the "
                f"{self.n_dispatches}-dispatch run")
        if self.checkpoint_every_segments < 1:
            raise ValueError("checkpoint_every_segments must be >= 1")
        if self.drain_event_counters and self.checkpoint_every_segments != 1:
            raise ValueError(
                "drain_event_counters needs checkpoint_every_segments=1 — "
                "a fast-forward through undrained boundaries would double-"
                "count the drained totals")


@dataclasses.dataclass
class ServiceReport:
    """What one :meth:`Supervisor.run` did. ``window_compiles`` maps
    each window shape (segment length) to its jit-cache growth — the
    one-compile-per-window-shape sentinel ``make service-smoke``
    asserts."""

    states: object
    n_dispatches: int
    rounds: int
    segments: int
    segment_rounds: int
    seconds: float
    recoveries: int
    retries: int
    degradations: list
    resumed_from: int | None
    window_compiles: dict
    checkpoints: list
    heartbeat_path: str
    invariant_checks: int
    probes: tuple
    retention: RetentionPolicy
    bundles: list
    #: stacked per-dispatch observe() pytree ([D, ...] leaves) over the
    #: COMMITTED dispatches, or None without an observer (rolled-back
    #: segments' observations are discarded with the segment)
    observations: object = None
    #: [N_EVENTS] np.int64 drained EV totals over the whole run (the
    #: counters a bare run would hold on device, summed on host past the
    #: i32 horizon), or None when ``drain_event_counters`` is off
    ev_totals: object = None

    def fingerprint(self) -> dict:
        """The schema-v3 ``fingerprint["service"]`` block
        (perf/artifacts.py; legacy artifacts read ``SERVICE_OFF``)."""
        from ..perf.artifacts import service_fingerprint

        return service_fingerprint(
            segment_rounds=self.segment_rounds,
            keep_last=self.retention.keep_last,
            keep_every=self.retention.keep_every,
            probes=self.probes,
            recoveries=self.recoveries,
            segments=self.segments,
            resumes=0 if self.resumed_from is None else 1,
        )


def _core_of(st):
    return st.core if hasattr(st, "core") else st


def _with_events(st, ev):
    """The state tree with its EV counter vector replaced (gossip trees
    nest it under .core; bare SimStates hold it directly)."""
    core = _core_of(st).replace(events=ev)
    return st.replace(core=core) if hasattr(st, "core") else core


def state_digest(state) -> str:
    """Order-stable SHA-256 over the keyless state leaves — the
    cross-process bit-exactness witness the crash-recovery tests and
    ``make service-smoke`` compare (PRNG keys hash their key_data, the
    same normalization the checkpoint backend uses)."""
    import hashlib

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(state):
        arr = np.asarray(jax.random.key_data(leaf)
                         if _ckpt.is_prng_key(leaf) else leaf)
        h.update(str(arr.dtype).encode())
        h.update(str(arr.shape).encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


def overflow_horizon_note(total_rounds: int | None = None,
                          repo_root: str | None = None) -> str | None:
    """One-line startup note from the committed range audit
    (``RANGE_AUDIT.json``, analysis/ranges.py §23): the tightest proven
    int32 event-counter horizon and its f32 telemetry-exactness analogue,
    compared against the planned run length when given. Reads the JSON
    artifact directly — no interpreter import, so startup cost is one
    file read — and returns ``None`` when the artifact is absent or
    malformed (a missing audit never blocks serving; ``make range-audit``
    is the gate that enforces its presence in CI, not the service)."""
    root = repo_root or os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    try:
        with open(os.path.join(root, "RANGE_AUDIT.json")) as f:
            horizons = json.load(f)["horizons"]
        active = [(name, row) for name, row in horizons["events"].items()
                  if row["i32_horizon_rounds"] is not None]
        if not active:
            return None
        i32_name, i32_row = min(active,
                                key=lambda kv: kv[1]["i32_horizon_rounds"])
        f32_name, f32_row = min(active,
                                key=lambda kv: kv[1]["f32_exact_horizon_rounds"])
    except (OSError, ValueError, KeyError, TypeError):
        return None
    i32_h = int(i32_row["i32_horizon_rounds"])
    f32_h = int(f32_row["f32_exact_horizon_rounds"])
    note = (
        f"range audit horizons: tightest int32 event counter is {i32_name} "
        f"at {i32_h} rounds (per-round delta bound "
        f"{int(i32_row['per_round_delta_hi'])}); f32 telemetry columns stay "
        f"exact to {f32_name} at {f32_h} rounds"
    )
    if total_rounds is not None:
        worst = min(i32_h, f32_h)
        note += (f"; planned {int(total_rounds)} rounds "
                 + ("fits every horizon" if total_rounds <= worst else
                    f"EXCEEDS the {worst}-round horizon — drain counters "
                    "(trace.drain.counter_events) within that window"))
    return note


class Supervisor:
    """Drive a long run as supervised checkpoint-quantum segments.

    * ``step`` — the jitted per-dispatch engine step (donating, the
      ``make_*_step`` contract; lifted ensemble steps work unchanged —
      pass ``batched=True`` so probes/invariants vmap).
    * ``make_args(i)`` — the per-dispatch positional arrays after the
      state (the ``ensemble.run_rounds`` contract).
    * ``template_fn()`` — a FRESH initial state tree (same configs /
      topology / seed every call): the cold-start state AND the
      checkpoint restore template.
    * ``root`` — the service directory: ``checkpoints/`` (store),
      ``HEARTBEAT.json``, ``<report_name>.jsonl/.html``,
      ``forensics/``.
    * ``heartbeat_fn(i)`` — static cadence flags (global dispatch
      index; must be periodic with the period dividing
      ``segment_len``); ``invariants`` an ``oracle.ScanInvariants``
      built for this engine (``check_every`` must divide
      ``segment_len``); ``observe`` a device fn folded per dispatch.
    * ``faults`` — a serve.faults.FaultPlan (tests/smoke only).
    """

    def __init__(self, step, make_args, template_fn, root: str,
                 svc: ServiceConfig, *, heartbeat_fn=None, invariants=None,
                 observe=None, batched: bool = False, faults=None,
                 unroll: int = 1, retryable=None):
        self.step = step
        self.make_args = make_args
        self.template_fn = template_fn
        self.root = str(root)
        self.svc = svc
        self.heartbeat_fn = heartbeat_fn
        self.invariants = invariants
        self.observe = observe
        self.batched = bool(batched)
        self.faults = faults
        self.unroll = int(unroll)
        self._retryable = tuple(retryable) if retryable is not None else (
            TransientDispatchError, _JaxRuntimeError)
        os.makedirs(self.root, exist_ok=True)
        self._cur_segment = -1
        hook = (faults.store_hook(lambda: self._cur_segment)
                if faults is not None else None)
        self.store = CheckpointStore(
            os.path.join(self.root, "checkpoints"), svc.retention,
            write_hook=hook)
        if svc.health is not None:
            self._probe, self._probe_names = make_health_probe(
                svc.health, batched=batched)
        else:
            self._probe, self._probe_names = None, ()
        self._replay_probe = None  # built lazily on first rollback
        if invariants is not None and svc.segment_len % invariants.check_every:
            raise ValueError(
                f"invariant check_every {invariants.check_every} must "
                f"divide segment_len {svc.segment_len}")
        self._seg_len = int(svc.segment_len)
        self._runners: dict = {}
        self._compiles_base: dict = {}
        self._degradations: list = []
        self._bundles: list = []
        self._rows: list | None = None  # report rows (lazy jsonl load)
        self._host_ms = dict.fromkeys(ROW_SPANS, 0.0)

    # -- window plumbing ------------------------------------------------

    def _runner_for(self, L: int) -> WindowRunner:
        key = (L, self.observe is not None)
        runner = self._runners.get(key)
        if runner is None:
            runner = WindowRunner(
                self.step, L, rounds_per_phase=self.svc.rounds_per_dispatch,
                heartbeat_fn=self.heartbeat_fn, invariants=self.invariants,
                observe=self.observe, unroll=self.unroll)
            self._runners[key] = runner
            self._compiles_base[key] = runner._cache_size()
        return runner

    def window_compiles(self) -> dict:
        """jit-cache growth per window shape since runner creation."""
        out = {}
        for key, runner in self._runners.items():
            before, after = self._compiles_base[key], runner._cache_size()
            out[f"L{key[0]}" + ("+obs" if key[1] else "")] = (
                -1 if before is None or after is None else after - before)
        return out

    def _segment_due(self, start: int, L: int):
        """Global-tick due rows for dispatches [start, start+L) — the
        supervisor owns the schedule, so the per-segment rows carry the
        RUN's ticks, not segment-local ones."""
        spec = self.invariants
        if spec is None:
            return None, ()
        ce = spec.check_every
        rows, ticks = [], []
        for j in range(L):
            if (j + 1) % ce:
                continue
            tick = (start + j + 1) * self.svc.rounds_per_dispatch
            rows.append(np.asarray(
                spec.due_fn(tick) if spec.due_fn is not None
                else _oinv.due_vector(), np.int32))
            ticks.append(tick)
        due = jnp.asarray(np.stack(rows) if rows
                          else np.zeros((0, len(_oinv.due_vector())),
                                        np.int32))
        return due, tuple(ticks)

    def _step_once(self, st, i: int):
        """One per-dispatch engine step at global dispatch ``i`` — the
        rollback replay's unit (bit-identical to the window's body;
        tests/test_window.py pins the parity)."""
        args = tuple(self.make_args(i))
        kw = {}
        if self.heartbeat_fn is not None:
            kw["do_heartbeat"] = bool(self.heartbeat_fn(i))
        return self.step(st, *args, **kw)

    # -- state reconstruction -------------------------------------------

    def _state_at(self, start: int):
        """The state tree at dispatch boundary ``start``: newest usable
        checkpoint at-or-before it, fast-forwarded deterministically
        through the same window programs when the checkpoint cadence is
        sparser than the rollback target."""
        rps = self.svc.rounds_per_dispatch
        st, d0 = None, 0
        entries = self.store.entries()
        while entries:
            e = entries[-1]
            d = int(e.get("meta", {}).get("dispatch", e["tick"] // rps))
            if d > start:
                entries.pop()
                continue
            try:
                st = _ckpt.restore(os.path.join(self.store.root, e["file"]),
                                   self.template_fn())
                d0 = d
                break
            except (_ckpt.CheckpointCorrupt, FileNotFoundError) as err:
                _log.warning("rollback: snapshot ordinal %d unusable (%s)",
                             e["ordinal"], err)
                entries.pop()
        if st is None:
            st, d0 = self.template_fn(), 0
        while d0 < start:
            L = min(self._seg_len, start - d0)
            runner = self._runner_for(L)
            xs = runner.stack_args(self.make_args, d0, d0 + L)
            due, _ = self._segment_due(d0, L)
            st, _ys = runner.dispatch(st, xs, due)
            d0 += L
        return st

    # -- dispatch with retry / degradation -------------------------------

    def _dispatch_retrying(self, seg: int, start: int, L: int, states,
                           xs, due):
        """One segment dispatch through the injectable seam, with
        exponential-backoff retries and the degradation ladder. Returns
        ``(states, ys, retries, degraded)``; ``states is None`` signals
        "shape changed — re-enter the loop" (the caller rebuilds xs)."""
        svc = self.svc
        retries = 0
        while True:
            try:
                if self.faults is not None:
                    self.faults.before_dispatch(seg)
                out, ys = self._runner_for(L).dispatch(states, xs, due)
                return out, ys, retries, False
            except self._retryable as e:
                retries += 1
                if not isinstance(e, TransientDispatchError):
                    # the window may have started: donated buffers are
                    # gone — rebuild the segment-entry state
                    states = self._state_at(start)
                if retries <= svc.max_retries:
                    delay = (svc.backoff_base_s
                             * svc.backoff_factor ** (retries - 1)
                             * (1.0 + svc.backoff_jitter * random.random()))
                    _log.warning(
                        "segment %d dispatch failed (%s) — retry %d/%d "
                        "in %.3fs", seg, e, retries, svc.max_retries, delay)
                    time.sleep(delay)
                    continue
                # budget spent: degrade before giving up — never
                # silently drop rounds
                if svc.degrade and self._try_degrade(L):
                    return states, None, retries, True
                # liveness: a monitor must see THIS death, not a stale
                # 'running' heartbeat (the recovery-budget halt path
                # writes the same status before raising)
                self._heartbeat(start, "halted")
                raise ServiceHalted(
                    f"segment {seg}: dispatch failed {retries} times and "
                    f"the degradation ladder is exhausted: {e}") from e

    def _try_degrade(self, L: int) -> bool:
        """One rung down: first shrink the segment length (halve while
        alignment allows), then drop optional observers. True = a rung
        was taken and the caller should rebuild the segment."""
        period = 1
        if self.heartbeat_fn is not None:
            from ..driver import min_cycle

            period = len(min_cycle(
                self.heartbeat_fn(i) for i in range(self._seg_len)))
        ce = (self.invariants.check_every
              if self.invariants is not None else 1)
        block = math.lcm(period, ce)
        half = self._seg_len // 2
        if half >= block and half % block == 0:
            self._seg_len = half
            self._degradations.append(f"shrink-segment:{half}")
            # the delivery floor is per SEGMENT: a shrunk segment
            # delivers proportionally less, so the boundary probe must
            # scale with it or every healthy degraded segment trips
            health = self.svc.health
            if health is not None and health.delivery_floor > 0:
                scaled = (health.delivery_floor * half
                          // self.svc.segment_len)
                self._probe, self._probe_names = make_health_probe(
                    dataclasses.replace(health, delivery_floor=scaled),
                    batched=self.batched)
            _log.warning("degraded: segment length halved to %d", half)
            return True
        if self.observe is not None:
            self.observe = None
            self._degradations.append("drop-observers")
            _log.warning("degraded: optional observers dropped")
            return True
        return False

    # -- violation handling ----------------------------------------------

    def _rollback_replay(self, seg: int, start: int, L: int, states_bad,
                         probe_fail, window_report):
        """Roll back to the segment-entry state and replay per dispatch
        with ``replay_check_every`` localization, emitting the forensic
        bundle. Returns the bundle dict (with its on-disk path)."""
        svc = self.svc
        rps = svc.rounds_per_dispatch
        spec = self.invariants
        ce = max(1, int(svc.replay_check_every))
        st = self._state_at(start)
        prev_ev = jnp.copy(_core_of(st).events)
        first_bad, replay_fail = None, []
        if self._probe is not None and self._replay_probe is None:
            # the delivery floor is a PER-SEGMENT quantity — applying it
            # to a single dispatch's delta would spuriously trip at the
            # first replayed dispatch and mislocalize; the replay probe
            # zeroes it (non-negativity still rides events-monotone)
            self._replay_probe, _ = make_health_probe(
                dataclasses.replace(svc.health, delivery_floor=0),
                batched=self.batched)
        for j in range(L):
            i = start + j
            st = self._step_once(st, i)
            if self.faults is not None:
                st = self.faults.corrupt_state(st, seg, j, L)
            fails = []
            if self._probe is not None:
                pm = np.asarray(self._replay_probe(st, prev_ev))
                flat = pm.reshape(-1, pm.shape[-1])
                fails += [self._probe_names[k]
                          for k in np.nonzero(~flat.all(axis=0))[0]]
            if spec is not None and (j + 1) % ce == 0:
                tick = (i + 1) * rps
                due = jnp.asarray(np.asarray(
                    spec.due_fn(tick) if spec.due_fn is not None
                    else _oinv.due_vector(), np.int32))
                om = np.asarray(spec.check(st, prev_ev, due))
                flat = om.reshape(-1, om.shape[-1])
                fails += [f"invariant:{spec.names[k]}"
                          for k in np.nonzero(~flat.all(axis=0))[0]]
            if fails:
                first_bad, replay_fail = i, fails
                break
            prev_ev = jnp.copy(_core_of(st).events)
        return self._write_bundle(seg, start, L, first_bad, replay_fail,
                                  probe_fail, window_report, states_bad)

    def _write_bundle(self, seg, start, L, first_bad, replay_fail,
                      probe_fail, window_report, states_bad) -> dict:
        rps = self.svc.rounds_per_dispatch
        # keyed by start dispatch, not segment ordinal: after a
        # segment-shrink degradation several windows share one ordinal,
        # and a second bundle must never overwrite the first's evidence
        bdir = os.path.join(self.root, "forensics", f"d{start:07d}")
        os.makedirs(bdir, exist_ok=True)
        nan_census = {}
        arrays = {}
        flat, _ = jax.tree_util.tree_flatten_with_path(states_bad)
        for path, leaf in flat:
            if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype,
                                                         jnp.floating):
                n_bad = int(np.asarray(
                    jnp.sum(~jnp.isfinite(leaf))))
                if n_bad:
                    nan_census[jax.tree_util.keystr(path)] = n_bad
        core = _core_of(states_bad)
        if hasattr(core, "telem") and core.telem is not None:
            arrays["telemetry_panel"] = np.asarray(core.telem.panel)
        if window_report is not None:
            arrays["invariant_ok"] = np.asarray(window_report.ok)
        doc = {
            "segment": seg,
            "start_dispatch": start,
            "segment_len": L,
            "first_bad_dispatch": first_bad,
            "first_bad_tick": (None if first_bad is None
                               else (first_bad + 1) * rps),
            "replay_failures": replay_fail,
            "window_probe_failures": probe_fail,
            "window_invariants": (window_report.artifact_block()
                                  if window_report is not None else None),
            "nan_census": nan_census,
            "written_at": time.time(),
        }
        write_json_atomic(os.path.join(bdir, "bundle.json"), doc)
        if arrays:
            np.savez_compressed(os.path.join(bdir, "masks.npz"), **arrays)
        doc["path"] = bdir
        self._bundles.append(doc)
        return doc

    # -- liveness ---------------------------------------------------------

    @property
    def heartbeat_path(self) -> str:
        return os.path.join(self.root, "HEARTBEAT.json")

    def _heartbeat(self, dispatch: int, status: str) -> None:
        with self._span("serve.heartbeat_write"):
            write_json_atomic(self.heartbeat_path, {
                "status": status,
                "dispatch": int(dispatch),
                "total_dispatches": int(self.svc.n_dispatches),
                "tick": int(dispatch) * self.svc.rounds_per_dispatch,
                "segments_run": self._segments_run,
                "recoveries": self._recoveries,
                "retries": self._retries,
                "degradations": list(self._degradations),
                "pid": os.getpid(),
                "updated_at": time.time(),
            })

    def _report_paths(self):
        if self.svc.report_name is None:
            return None, None
        base = os.path.join(self.root, self.svc.report_name)
        return base + ".jsonl", base + ".html"

    @contextlib.contextmanager
    def _span(self, name: str, **attrs):
        """A host span of ``perf.spans`` whose milliseconds also add to
        the next report row's ``host_ms``, where the row names it."""
        with spans.span(name, **attrs) as sp:
            yield sp
        key = name[len("serve."):]
        if key in self._host_ms:
            self._host_ms[key] += 1e3 * sp.seconds

    def _report_row(self, row: dict) -> None:
        """``row`` gains ``host_ms``: the host milliseconds by span since
        the last row. ``stack_args``, ``dispatch``, ``probe_readback``,
        ``ev_drain`` and ``checkpoint_save`` lie inside the row's
        ``seconds`` (the ``serve.segment`` span around them); ``stack_args``
        of a first segment, ``heartbeat_write`` and ``report_row`` lie
        outside it, and ``report_row`` is the write of the row BEFORE this
        one (a row cannot time its own write)."""
        row["host_ms"] = {k: round(v, 3) for k, v in self._host_ms.items()}
        self._host_ms = dict.fromkeys(ROW_SPANS, 0.0)
        with self._span("serve.report_row"):
            self._write_row(row)

    def _write_row(self, row: dict) -> None:
        jsonl, html = self._report_paths()
        if jsonl is None:
            return
        if self._rows is None:
            # one-time load of a previous run's rows (resume); after
            # this the in-memory list is authoritative — re-parsing the
            # whole jsonl per segment would be O(segments²) host work
            # on a million-round run
            self._rows = []
            try:
                with open(jsonl) as f:
                    self._rows = [json.loads(line) for line in f
                                  if line.strip()]
            except (FileNotFoundError, ValueError):
                pass
        with open(jsonl, "a") as f:
            f.write(json.dumps(row) + "\n")
        self._rows.append(row)
        with open(html + ".tmp", "w") as f:
            f.write(_render_report_html(self._rows, self.svc))
        os.replace(html + ".tmp", html)

    # -- the loop ---------------------------------------------------------

    def run(self, *, fresh: bool = False) -> ServiceReport:
        """Run (or resume) the supervised loop to completion."""
        svc = self.svc
        rps = svc.rounds_per_dispatch
        total = svc.n_dispatches
        self._segments_run = 0
        self._recoveries = 0
        self._retries = 0
        self._host_ms = dict.fromkeys(ROW_SPANS, 0.0)
        t0 = time.perf_counter()
        resumed_from = None
        states, start = self.template_fn(), 0
        ev_totals = (np.zeros_like(np.asarray(_core_of(states).events),
                                   np.int64)
                     if svc.drain_event_counters else None)
        if not fresh:
            with self._span("serve.restore"):
                st, entry = self.store.restore_latest(self.template_fn())
            if st is not None:
                states = st
                start = int(entry.get("meta", {}).get(
                    "dispatch", entry["tick"] // rps))
                resumed_from = start
                if ev_totals is not None:
                    # drained totals ride checkpoint meta: a checkpoint's
                    # device counters are zeroed AT its boundary, so the
                    # pair (zeroed counters, meta totals) is the full
                    # count — a legacy checkpoint without the key simply
                    # resumes the accumulator from its own counters
                    ev_totals = np.asarray(
                        entry.get("meta", {}).get("ev_totals",
                                                  ev_totals.tolist()),
                        np.int64)
                _log.info("resuming at dispatch %d (tick %d) from %s",
                          start, start * rps, entry["file"])
        prev_events = jnp.copy(_core_of(states).events)
        recov_per_segment: dict = {}
        xs_cache: dict = {}
        inv_checks = 0
        obs_acc: list = []
        self._heartbeat(start, "running")
        note = overflow_horizon_note(total_rounds=total * rps)
        if note:
            _log.info("%s", note)
        while start < total:
            L = min(self._seg_len, total - start)
            seg = start // svc.segment_len
            self._cur_segment = seg
            runner = self._runner_for(L)
            xs = xs_cache.pop(start, None)
            if xs is None:
                with self._span("serve.stack_args"):
                    xs = runner.stack_args(self.make_args, start, start + L)
            due, ticks = self._segment_due(start, L)
            # the segment's clock: `seconds` of its report row
            with self._span("serve.segment", segment=seg) as seg_span:
                with self._span("serve.dispatch"):
                    out, ys, retries, degraded = self._dispatch_retrying(
                        seg, start, L, states, xs, due)
                self._retries += retries
                if degraded:
                    # shape changed (or observers dropped): rebuild the
                    # segment from an intact state on the new ladder rung
                    states = (out if out is not None
                              else self._state_at(start))
                    xs_cache.clear()
                    continue
                states = out
                # double-buffer: assemble the NEXT segment's xs while the
                # device is still executing this one (dispatch is async)
                nxt = start + L
                if nxt < total:
                    Ln = min(self._seg_len, total - nxt)
                    with self._span("serve.stack_args"):
                        xs_cache[nxt] = runner.stack_args(self.make_args, nxt,
                                                          nxt + Ln)
                # injected silent corruption lands before the probe reads
                if (self.faults is not None
                        and self.faults.wants_corruption(seg)):
                    states = self.faults.corrupt_state(
                        states, seg,
                        self.faults.resolved_dispatch(L), L)
                # the segment's one host sync: probe + verdict readback
                probe_fail = []
                if self._probe is not None:
                    with self._span("serve.probe_readback"):
                        pm = np.asarray(self._probe(states, prev_events))
                    flat = pm.reshape(-1, pm.shape[-1])
                    probe_fail = [self._probe_names[k]
                                  for k in np.nonzero(~flat.all(axis=0))[0]]
                window_report = None
                if self.invariants is not None and ys and "ok" in ys:
                    window_report = self.invariants.report(ys["ok"],
                                                           ticks=ticks)
                inv_bad = (window_report is not None
                           and not window_report.all_ok)
                if probe_fail or inv_bad:
                    self._recoveries += 1
                    n = recov_per_segment.get(start, 0) + 1
                    recov_per_segment[start] = n
                    bundle = self._rollback_replay(
                        seg, start, L, states, probe_fail, window_report)
                    _log.warning(
                        "segment %d unhealthy (%s) — rolled back; replay "
                        "localized first violating dispatch %s (bundle %s)",
                        seg, probe_fail or "invariants",
                        bundle["first_bad_dispatch"], bundle["path"])
                    if n > svc.max_recoveries_per_segment:
                        self._heartbeat(start, "halted")
                        what = bundle["replay_failures"] or probe_fail
                        raise ServiceHalted(
                            f"segment {seg}: {n} recoveries exceeded the "
                            f"budget ({svc.max_recoveries_per_segment}) — "
                            f"persistent violation ({what}); forensic "
                            f"bundle at {bundle['path']}", bundle)
                    states = self._state_at(start)
                    prev_events = jnp.copy(_core_of(states).events)
                    continue
                if self.faults is not None:
                    self.faults.maybe_kill("post-segment", seg)
                # commit
                self._segments_run += 1
                if window_report is not None:
                    inv_checks += window_report.n_checks
                if ys and "obs" in ys:
                    obs_acc.append(ys["obs"])
                start += L
                if ev_totals is not None:
                    # segment-boundary EV drain (the probe/invariant verdict
                    # above already validated this segment): the segment's
                    # i32 counter growth folds into the host i64 totals and
                    # the device counters zero, so no device counter ever
                    # holds more than ONE segment's growth — the overflow
                    # horizon becomes per-segment, not per-run
                    with self._span("serve.ev_drain"):
                        ev_totals += (
                            np.asarray(_core_of(states).events, np.int64)
                            - np.asarray(prev_events, np.int64))
                        states = _with_events(
                            states, jnp.zeros_like(_core_of(states).events))
                if (self._segments_run % svc.checkpoint_every_segments == 0
                        or start >= total):
                    meta = {"dispatch": start}
                    if ev_totals is not None:
                        meta["ev_totals"] = ev_totals.tolist()
                    with self._span("serve.checkpoint_save"):
                        self.store.save(states, tick=start * rps, meta=meta)
                prev_events = jnp.copy(_core_of(states).events)
            dt = seg_span.seconds
            self._heartbeat(start, "running")
            self._report_row({
                "segment": seg,
                "dispatch": start,
                "tick": start * rps,
                "seconds": round(dt, 4),
                "rounds_per_sec": round(L * rps / dt, 2) if dt > 0 else 0.0,
                "probes_ok": not probe_fail,
                "invariants_ok": not inv_bad,
                "invariant_checks": (window_report.n_checks
                                     if window_report else 0),
                "retries": retries,
                "recoveries_total": self._recoveries,
            })
        jax.block_until_ready(states)
        self._heartbeat(start, "done")
        observations = None
        if obs_acc:
            observations = jax.tree_util.tree_map(
                lambda *a: np.concatenate([np.asarray(x) for x in a]),
                *obs_acc)
        return ServiceReport(
            states=states,
            n_dispatches=total,
            rounds=total * rps,
            segments=self._segments_run,
            segment_rounds=svc.segment_len * rps,
            seconds=time.perf_counter() - t0,
            recoveries=self._recoveries,
            retries=self._retries,
            degradations=list(self._degradations),
            resumed_from=resumed_from,
            window_compiles=self.window_compiles(),
            checkpoints=self.store.entries(),
            heartbeat_path=self.heartbeat_path,
            invariant_checks=inv_checks,
            probes=self._probe_names,
            retention=svc.retention,
            bundles=list(self._bundles),
            observations=observations,
            ev_totals=ev_totals,
        )


def _render_report_html(rows: list, svc: ServiceConfig) -> str:
    """Minimal self-contained incremental dashboard: per-segment table
    + a rate sparkline + status chips. Rewritten atomically after every
    segment so a browser mid-run always sees a consistent page."""
    import html as _html

    rates = [r.get("rounds_per_sec", 0.0) for r in rows]
    done = rows[-1]["dispatch"] if rows else 0
    total = svc.n_dispatches
    spark = ""
    if rates:
        hi = max(max(rates), 1e-9)
        w, h = 360, 48
        pts = " ".join(
            f"{i * w / max(len(rates) - 1, 1):.1f},"
            f"{h - 4 - (v / hi) * (h - 8):.1f}"
            for i, v in enumerate(rates))
        spark = (f'<svg width="{w}" height="{h}" role="img">'
                 f'<polyline fill="none" stroke="#36f" stroke-width="1.5" '
                 f'points="{pts}"/></svg>')
    trs = "".join(
        "<tr><td>{segment}</td><td>{dispatch}</td><td>{tick}</td>"
        "<td>{rounds_per_sec}</td><td>{p}</td><td>{v}</td>"
        "<td>{retries}</td></tr>".format(
            p="ok" if r.get("probes_ok", True) else "FAIL",
            v="ok" if r.get("invariants_ok", True) else "FAIL",
            **{k: r.get(k, "") for k in
               ("segment", "dispatch", "tick", "rounds_per_sec",
                "retries")})
        for r in rows[-200:])
    return (
        "<!doctype html><html><head><meta charset='utf-8'>"
        "<title>supervised service loop</title>"
        "<style>body{font:13px system-ui;margin:1.5em;color:#222}"
        "table{border-collapse:collapse}td,th{border:1px solid #ccc;"
        "padding:2px 8px;text-align:right}th{background:#f5f5f5}"
        ".big{font-size:1.4em;font-weight:600}</style></head><body>"
        f"<h1>supervised service loop</h1>"
        f"<p class='big'>{done} / {total} dispatches "
        f"({100.0 * done / max(total, 1):.1f}%)</p>"
        f"<p>segment quantum {svc.segment_len} dispatches · "
        f"{_html.escape(str(len(rows)))} segments reported</p>"
        f"{spark}"
        "<table><tr><th>segment</th><th>dispatch</th><th>tick</th>"
        "<th>rounds/s</th><th>probes</th><th>invariants</th>"
        "<th>retries</th></tr>"
        f"{trs}</table></body></html>")
