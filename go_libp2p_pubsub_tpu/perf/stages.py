"""The engine's stages, named on the device.

Three things live here and nowhere else:

* the scope names. ``STAGES`` is the fixed tuple; every scope in the
  package is ``scope(stage)`` (``jax.named_scope("gs." + stage)``), used
  as a ``with`` block, as a decorator on the callee, or through the
  :class:`Cursor` that ``with_cursor`` hands ``_phase`` to move one scope
  along it. No other module spells a scope name (tests/test_stages.py
  greps for it).
* the way from a compiled window to a stage map. A named scope is HLO
  metadata: it ends up in ``metadata={op_name="..."}`` of every
  instruction of the compiled module. ``stage_of`` takes the innermost
  ``gs.*`` component of an ``op_name``; ``instruction_stages`` parses the
  compiled text into ``{instruction name: stage}``.
* the registry of the windows traced in this process (``note_window``,
  called by ``driver.make_window`` / ``make_scan`` while the window's
  Python body is traced, never per dispatch) and ``traced_windows()``,
  which hands the newest of them back, each with its XLA module name,
  the rows its edge gathers address per step call and, on demand and
  memoised, its stage map.

What each stage covers (the scope sits inside the callee wherever one
callee does the work, so every caller gets it):

  control_head  ``_phase`` from the control-head marker to the data-loop
                marker: peer transitions, accept gates, the control
                exchange, GRAFT/PRUNE, PX, IWANT service, IHAVE ingest,
                the phase-fixed send / receive gates
  pub_plan      ``state.PhasePubPlan`` (its construction, ``msgs_at``,
                ``apply_to_delivery``) and ``state.allocate_publishes``
  data_round    ``_phase`` from the data-loop marker to the phase tail:
                the loop's set-up and every sub-round's transmit
                composition, trans gates, accumulator folds, mcache
  edge_gather   ``Net.edge_gather`` / ``Net.peer_gather`` (banded rolls,
                the dense ``edge_permute`` and CSR alike)
  deliver       ``common.finish_delivery``, ``gossipsub.merge_extra_tx``
  score         ``score.engine``: ``on_deliveries``,
                ``apply_delivery_counts``, ``refresh_scores``,
                ``compute_scores``
  heartbeat     ``gossipsub.heartbeat``
  phase_tail    ``_phase`` from the phase-tail marker to its end, the
                heartbeat's own scope apart: deferred clears, gater, the
                state ``replace``, the telemetry row

Scopes nest (``gs.data_round/gs.edge_gather``): the innermost is the
stage. An instruction with none is ``unscoped``: what XLA itself puts
in (carry copies, layout conversions of the scan, the ``while``).

Parts. A second, finer family of scopes marks a mechanism INSIDE the
stages: ``part(name)`` is ``jax.named_scope("gsx." + name)``, ``PARTS``
the fixed tuple. ``stage_of`` does not see them (a part's instructions
stay booked to the stage around them, and the stages still add up to
the window); ``part_of`` / ``instruction_parts`` / ``TracedWindow.parts``
give ``{instruction: part}`` for the instructions inside one, from the
same compiled text.

  fanout        publishing to a topic the origin has not joined:
                ``gossipsub.update_fanout_on_publish``, ``fanout_carry_
                words[_packed]``, the packed form's pack / unpack, and the
                heartbeat's fanout maintenance and fanout gossip blocks
  attrib        what a v1.1 build pays to ATTRIBUTE deliveries beyond P1 /
                P2 / P7: the ``[N,K,W]`` ``trans`` (P4) and ``mcw`` (P3)
                planes' folds in the phase engine's ``_AccStack``, the P3
                window gate of every sub-round, ``apply_validation_
                throttle``, the static adversary's data-plane masks, and
                the P3 / P3b / P4 terms of ``score.engine`` (``on_
                deliveries``, ``apply_delivery_counts``, ``compute_
                scores``)
  gater         the peer gater: the ``dup`` / ``rejw`` / ``ignw`` planes'
                composition and folds, ``score.gater.gater_on_round`` with
                its popcounts, ``gater_accept`` and ``gater_decay``
  churn         what a ``dynamic_peers`` build pays at the head beyond a
                static one: the liveness code's packing and
                ``gossipsub.apply_peer_transitions`` (the dead-edge clears;
                with the coalesced wire the code crosses inside the head's
                own wire gather, outside the part, else in an edge gather
                of its own, under ``gs.edge_gather`` too),
                ``live_step_views``' traced arm, and the publish gate on
                ``up[origin]`` (``pub_holder``, which ``state.PhasePubPlan``
                / ``allocate_publishes`` take). A static window carries none
  exchange      the per-shard plan's crossing of a split peer axis
                (``ops/edges.edge_permute_sharded``): the pack of the rows
                the other shards read, the permutes, and their landing
                after the shard's own table; under ``gs.edge_gather``. A
                window on one device, or over a banded ring, carries none

Host spans. ``host_scope(name)`` is the profiler annotation
``gs.host.<name>`` a host span of ``perf/spans.py`` lies under in the host
plane of a running trace: spelled here with the other names, called from
there alone.

Collectives. A window traced over a mesh of more than one device is
``sharded``: the cross-peer traffic crosses in collectives the compiled
text names (GSPMD's, the banded gathers' explicit halo exchange,
``ops/edges._banded``, and the per-shard plan's,
``ops/edges.edge_permute_sharded``). A tracer carries the mesh's size but neither its
devices nor the specs, so the registry takes the concrete shardings of
the state argument from the call being traced (``CALLED_STATE``, set by
``driver``'s window; its output shardings are its input's, so they hold
for every call) and compiles the PARTITIONED module the devices run:
its instruction names
are those of each device plane's op events. The same text gives
``TracedWindow.collectives()`` (``{instruction: op}``, the ``-start`` and
``-done`` of an async pair both) and the counters
``collectives_per_dispatch`` / ``collective_bytes_per_dispatch``.

Known limits. A fusion carries one ``op_name``, its root's: a fusion
that spans two stages is booked to the root's. A sharded window traced
outside its call or lowering (``eval_shape``) is not noted.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
import functools
import re
from typing import Any

#: Part of the XLA module name of every window (``jit_gs_window_v1``).
#: JAX's persistent compilation cache strips debug info from its key, and
#: a named scope is debug info: an executable compiled before a scope
#: moved would be loaded for the program after it, with the old names.
#: The module name IS part of the key. So ANY PR THAT MOVES, ADDS OR
#: RENAMES A SCOPE BUMPS THIS. (The ``gsx.fanout`` part came without a
#: bump: it is traced only where ``fanout_slots`` > 0, and the PR that
#: brought it changed those programs' FanoutTTL constant, so no
#: executable from before it has their key. ``gsx.attrib`` and
#: ``gsx.gater`` likewise: they are read in builds with live P3 / P4
#: weights, a gater, a validation queue or an adversary vector, whose P3
#: constants the same PR moved onto the clock of rounds; in an honest
#: build the score terms' scope stands around weightless arithmetic, and
#: an executable cached before it simply lacks the name nobody reads.
#: ``gsx.churn`` likewise: it is traced under ``dynamic_peers`` alone, and
#: the PR that brought it put the publish gate into those programs, so no
#: executable from before it has their key. ``gsx.exchange`` likewise: it
#: is traced only where a planned net's gather is split over a 1-D
#: ``peers`` mesh, a program that was a partitioner's gather before it.)
VERSION = 1

PREFIX = "gs."
STAGES = ("control_head", "pub_plan", "data_round", "edge_gather", "deliver",
          "score", "heartbeat", "phase_tail")
UNSCOPED = "unscoped"
PART_PREFIX = "gsx."
PARTS = ("fanout", "attrib", "gater", "churn", "exchange")
HOST_PREFIX = PREFIX + "host."

_SCOPE_RE = re.compile(re.escape(PREFIX) + r"([a-z_]+)")
_PART_RE = re.compile(re.escape(PART_PREFIX) + r"([a-z_]+)")
_INSTRUCTION_RE = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def scope(stage: str):
    """``jax.named_scope`` of one of ``STAGES``: a context manager, and a
    decorator for a callee that is one stage."""
    if stage not in STAGES:
        raise ValueError(f"no stage {stage!r} in perf.stages.STAGES")
    import jax

    return jax.named_scope(PREFIX + stage)


def part(name: str):
    """``jax.named_scope`` of one of ``PARTS``: context manager and
    decorator, as ``scope``."""
    if name not in PARTS:
        raise ValueError(f"no part {name!r} in perf.stages.PARTS")
    import jax

    return jax.named_scope(PART_PREFIX + name)


def host_scope(name: str):
    """``jax.profiler.TraceAnnotation`` of one host span: under a running
    profiler the span lies in the host plane of the same trace as the
    device ops, on one clock. ``perf.spans.span`` is its one caller and
    checks the name."""
    import jax

    return jax.profiler.TraceAnnotation(HOST_PREFIX + name)


class Cursor(contextlib.ExitStack):
    """One scope that moves along a long traced function: ``cursor(stage)``
    closes the scope it holds and opens ``stage``'s, and leaving the
    ``with`` closes whatever is open. For ``_phase``, whose stages are
    hundreds of lines each and share their locals."""

    def __call__(self, stage: str) -> None:
        self.close()
        self.enter_context(scope(stage))


def with_cursor(fn):
    """Decorator: ``fn`` is called with a fresh :class:`Cursor` before its
    own arguments, closed when it returns or raises."""
    @functools.wraps(fn)
    def staged(*args, **kwargs):
        with Cursor() as cursor:
            return fn(cursor, *args, **kwargs)
    return staged


def window_name() -> str:
    """The ``__name__`` of every jitted window body (see ``VERSION``)."""
    return f"gs_window_v{VERSION}"


def stage_of(op_name: str) -> str:
    """The innermost ``gs.*`` component of an ``op_name``, else
    ``unscoped``."""
    found = [s for s in _SCOPE_RE.findall(op_name) if s in STAGES]
    return found[-1] if found else UNSCOPED


def part_of(op_name: str) -> str | None:
    """The innermost ``gsx.*`` component of an ``op_name``, else ``None``."""
    found = [p for p in _PART_RE.findall(op_name) if p in PARTS]
    return found[-1] if found else None


def _instruction_maps(hlo_text: str) -> tuple:
    """``({instruction name: stage}, {instruction name: part})`` of a
    COMPILED module's text: every instruction of the entry, of ``while``
    bodies, of called and of fused computations (a fusion is an
    instruction of its caller and has its own metadata). The second map
    holds only the instructions inside a part."""
    stage_map, part_map = {}, {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION_RE.match(line)
        if m is None:
            continue
        op = _OP_NAME_RE.search(line)
        stage_map[m.group(1)] = stage_of(op.group(1)) if op else UNSCOPED
        inside = part_of(op.group(1)) if op else None
        if inside is not None:
            part_map[m.group(1)] = inside
    return stage_map, part_map


def instruction_stages(hlo_text: str) -> dict:
    """``{instruction name: stage}`` of a compiled module's text."""
    return _instruction_maps(hlo_text)[0]


def instruction_parts(hlo_text: str) -> dict:
    """``{instruction name: part}`` of a compiled module's text, for the
    instructions inside a ``gsx.*`` scope."""
    return _instruction_maps(hlo_text)[1]


@dataclasses.dataclass(eq=False)
class TracedWindow:
    """One traced window: the jitted function and the abstract signature
    it was traced with."""

    jitted: Any
    module_name: str
    signature: tuple        # (treedef, ShapeDtypeStruct leaves)
    sharded: bool
    #: rows the edge gathers of ONE step call address by index, counted
    #: while the window was traced (``ops/edges.edge_rows_per_dispatch``:
    #: gather output rows plus scatter rows, 0 for rolls); ``None`` where
    #: the trace replayed a step traced before it
    edge_rows_per_dispatch: float | None = None
    #: rows of the largest table an edge gather of the window reads
    #: (``ops/edges.edge_table_rows``): N*K through the full ``edge_perm``,
    #: K*Np or K0*Np + T through a plan's full or compact table; ``None``
    #: for rolls and replays
    edge_table_rows: int | None = None
    #: the same in tile-rows, the unit the chip charges: rows times the
    #: sublane tiles a row of the widest slice read pads to
    #: (``ops/edges.tile_rows``)
    edge_table_tile_rows: int | None = None
    #: edge gathers of ONE step call that crossed in column slices
    #: (``ops/edges.word_slices``); 0 for rolls, ``None`` for replays
    edge_sliced_calls_per_dispatch: float | None = None
    #: gather sets (edge and peer) of ONE step call that crossed a split
    #: peer axis by an explicit exchange: one permute a ring offset of a
    #: banded ring (``ops/edges._peer_ring``), one a shard offset of the
    #: per-shard plan (``ops/edges.edge_permute_sharded``); 0 on one
    #: device, ``None`` for replays
    halo_exchanges_per_dispatch: float | None = None
    #: rows the PEER gathers of ONE step call address by index
    #: (``Net.peer_gather``: all N*K slots of ``v[nbr]`` a call; 0 for
    #: rolls, ``None`` for replays): 0 where every neighbour view of the
    #: step crosses as an edge gather
    peer_rows_per_dispatch: float | None = None
    #: rows one shard of ONE step call's edge gathers received from the
    #: other shards (``ops/edges.edge_permute_sharded``: the per-shard
    #: plan's exchange, padded as it crosses); 0 on one device and over a
    #: banded ring, ``None`` for replays
    remote_rows_per_dispatch: float | None = None
    #: step calls ONE call of the window runs (``ops/edges.mark_window``)
    dispatches: int | None = None
    _stages: dict | None = None
    _parts: dict | None = None
    _collectives: dict | None = None
    _collective_rows: list | None = None

    def _maps(self) -> tuple:
        """``(stages, parts, collectives, collective rows)`` of this
        window's compiled module (the partitioned one where ``sharded``).
        Lowers and compiles from the signature once: with the persistent
        cache on that is a retrace and a cache load."""
        if self._stages is None:
            import jax

            from ..parallel import sharding

            treedef, leaves = self.signature
            args, kwargs = jax.tree_util.tree_unflatten(treedef, leaves)
            text = self.jitted.lower(*args, **kwargs).compile().as_text()
            self._collectives = sharding.collective_names(text)
            self._collective_rows = sharding.collective_table(text)
            self._stages, self._parts = _instruction_maps(text)
        return (self._stages, self._parts, self._collectives,
                self._collective_rows)

    def stages(self) -> dict | None:
        """The stage map of this window's compiled module."""
        return self._maps()[0]

    def parts(self) -> dict | None:
        """``{instruction: part}`` of the same module, for the
        instructions inside a part."""
        return self._maps()[1]

    def collectives(self) -> dict | None:
        """``{instruction: op}`` of the same module's collectives (empty
        on one device)."""
        return self._maps()[2]

    def collective_rows(self) -> list | None:
        """``parallel.sharding.collective_table`` of the same module:
        ``(instruction, op, results, group, bytes sent, runs)``."""
        return self._maps()[3]

    def _per_dispatch(self, i: int) -> float | None:
        if not self.dispatches:
            return None
        return collectives_per_dispatch(self.collective_rows(),
                                        self.dispatches)[i]

    def collectives_per_dispatch(self) -> float | None:
        """Collectives ONE step call runs (an async pair once)
        (:func:`collectives_per_dispatch`)."""
        return self._per_dispatch(0)

    def collective_bytes_per_dispatch(self) -> float | None:
        """Bytes one device sends in the collectives of ONE step call
        (:func:`collectives_per_dispatch`)."""
        return self._per_dispatch(1)


def collectives_per_dispatch(rows: list, dispatches: int) -> tuple:
    """``(collectives, bytes one device sends)`` of ONE step call: the
    rows of ``parallel.sharding.collective_table`` of a compiled window
    (an async pair once), each as often as one call of the window runs
    it, over the ``dispatches`` step calls of a window call."""
    return (sum(r[5] for r in rows) / dispatches,
            sum(r[4] * r[5] for r in rows) / dispatches)


#: The registry holds its windows, because a trace is read once the loop
#: that made the window has returned; and only the newest few, because a
#: window holds its executables (a process that measures makes one or
#: two, a test session hundreds).
KEPT_WINDOWS = 16
_WINDOWS: collections.deque = collections.deque(maxlen=KEPT_WINDOWS)
#: the concrete state argument of the window call (or lowering) being
#: traced, set around it by ``driver``'s window: where a sharded window's
#: signature takes its state's shardings from
CALLED_STATE: contextvars.ContextVar = contextvars.ContextVar(
    "called_state", default=None)


def note_window(jitted, args: tuple, kwargs: dict, **counted) -> None:
    """Called from inside a window's traced Python body: note its
    signature once, with what its trace counted of its edge gathers
    (``counted``: the ``edge_*`` fields of ``TracedWindow``).
    Outside a trace (``jax.disable_jit``) nothing reaches the device as a
    module and nothing is noted."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    if not any(isinstance(x, jax.core.Tracer) for x in leaves):
        return
    avals = [jax.typeof(x) for x in leaves]
    # what a tracer does carry of its argument's placement: the abstract
    # mesh (its size, not its devices) of a `NamedSharding`
    sharded = any(
        getattr(getattr(getattr(a, "sharding", None), "mesh", None),
                "size", 1) > 1 for a in avals)
    # the state (the first argument, the first leaves) as the call placed
    # it; the schedules are placed by the call (uncommitted)
    shardings = [None] * len(avals)
    if sharded:
        state = CALLED_STATE.get()
        if state is None:
            return          # no concrete placement to compile from
        placed = [getattr(x, "sharding", None)
                  for x in jax.tree_util.tree_leaves(state)]
        shardings[:len(placed)] = placed
    signature = (treedef, tuple(
        jax.ShapeDtypeStruct(a.shape, a.dtype,
                             weak_type=getattr(a, "weak_type", False),
                             sharding=s)
        for a, s in zip(avals, shardings)))
    for w in _WINDOWS:
        if (w.jitted is jitted and w.signature == signature
                and w.sharded == sharded):
            return          # a retrace of what is noted (``stages`` lowers)
    _WINDOWS.append(TracedWindow(jitted, "jit_" + jitted.__name__,
                                 signature, sharded, **counted))


def traced_windows() -> list:
    """The newest ``KEPT_WINDOWS`` windows traced in this process."""
    return list(_WINDOWS)


def stages_of(jitted) -> dict | None:
    """The stage map of the one program traced for ``jitted`` (a window
    of ``driver.make_window`` / ``make_scan``); ``None`` where there is
    none, or more than one and the trace cannot tell whose op is whose."""
    entries = [w for w in traced_windows() if w.jitted is jitted]
    return entries[0].stages() if len(entries) == 1 else None
