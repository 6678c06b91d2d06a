"""The ensemble sweep / Monte Carlo driver.

One compile per (config, shape): the lifted step (batch.lift_step) is
a single fresh jit whose compile-cache size is the ONE-COMPILE
sentinel — ``run_rounds`` records it, and the ensemble-smoke gate
(scripts/ensemble_report.py) asserts it equals exactly 1 for the S=8
chaos smoke scenario. S sims execute together in each dispatch; a
sweep that used to run S seeds sequentially (S compiles + S runs, or
one compile amortized over S cold loops) becomes one program whose
arrays are S× wider — the shape XLA is built to keep a chip full with.

Sharding composition (docs/DESIGN.md §10, §14): three layouts, all
through :func:`shard_ensemble_state`.

  * ``axis="peers"`` (default) — the peer dimension (now axis 1, after
    the leading S) is sharded exactly as the unbatched state was
    (parallel/sharding.py), and the sim axis is vmapped WITHIN each
    shard: cross-peer halo permutes are unchanged in count, just S×
    wider — the right layout when one sim's peer axis is what needs
    the memory of multiple chips.
  * ``axis="sims"`` — the sim axis is sharded across chips and the
    peer axis stays local: embarrassingly parallel scaling with ZERO
    cross-chip collectives in the steady state (each chip runs S/D
    whole sims). The right layout when a single sim fits one chip —
    Monte Carlo at fleet width.
  * ``axis="sims+peers"`` (round 14) — the 2-D composition on a
    ``parallel.make_mesh_2d`` (sims × peers) mesh: the sim axis is
    sharded over the mesh's ``sims`` axis AND every peer-dim-1 leaf is
    additionally sharded over its ``peers`` axis. Halo permutes ride
    only the peers axis (each sims-row is an independent replica of
    the 1-D layout), so the collective count per phase is unchanged —
    the layout for S sims that each need a multi-chip peer axis.

Whole-run windows (round 14, docs/DESIGN.md §14): :class:`WindowRunner`
/ :func:`run_window` compile the ENTIRE segment into one
``driver.make_window`` program — per-dispatch inputs stacked as scan
``xs``, invariant checks (``oracle.ScanInvariants``) and device
observations folded into the scan body — so an S-sim, R-round, checked
and observed run is ONE dispatch (``EnsembleRun.dispatches`` is the
sentinel). ``run_rounds`` remains the per-dispatch face (the hook/
parity surface); the report cells and gates drive windows.
"""

from __future__ import annotations

import dataclasses
import time

from ..perf import spans


@dataclasses.dataclass
class EnsembleRun:
    """Result of one ensemble segment: the final batched state tree,
    the compile-count sentinel, and wall-clock aggregates. Window runs
    (round 14) additionally carry the dispatch count (the one-dispatch
    sentinel), the folded invariant report and the stacked per-dispatch
    observations."""

    states: object
    n_sims: int
    rounds: int          # simulated rounds PER SIM (ticks advanced)
    compiles: int        # jit-cache growth across the segment
                         # (-1 = unknown: the cache-size API is gone)
    seconds: float
    #: XLA dispatches the segment executed as (run_rounds: one per
    #: step; run_window: one per scan segment — 1 = whole-run program)
    dispatches: int = 0
    #: oracle.InvariantReport when invariants were folded/hooked
    invariant_report: object = None
    #: stacked per-dispatch observe() pytree ([D, ...] leaves) or None
    observations: object = None

    @property
    def aggregate_rounds_per_sec(self) -> float:
        """Total sim-rounds per wall second (S × rounds / time) — the
        Monte Carlo throughput number docs/PERF.md's ensemble row
        reports against S sequential runs."""
        return (self.n_sims * self.rounds / self.seconds
                if self.seconds > 0 else float("inf"))


def _cache_size(jit_fn) -> int | None:
    """The jit compile-cache size (jax 0.4.x private API — the same
    sentinel analysis/guards.py and the analyze gate rely on); None
    when unavailable so compile deltas degrade to 'unknown' (-1), not
    to a spurious count the one-compile gates would hard-fail on."""
    try:
        return int(jit_fn._cache_size())
    except Exception:  # pragma: no cover — newer-jax fallback
        return None


def run_rounds(ens_step, states, make_args, n_steps: int, *,
               rounds_per_phase: int = 1, heartbeat_fn=None,
               observe=None, invariants=None) -> EnsembleRun:
    """Drive ``n_steps`` dispatches of a lifted ensemble step.

    ``make_args(i)`` returns the tuple of per-step positional arrays
    after the state, each carrying the leading S axis (publish batches
    [S, P] / [S, r, P], churn rows [S, N], scheduled-chaos deny masks
    [S, N, K] — batch.tile for shared inputs). ``heartbeat_fn(i)``
    returns the static ``do_heartbeat`` bool for steps that take one
    (phase / static-heartbeat builds); None omits the kwarg.
    ``observe(i, states)`` is called after each dispatch with the live
    batched state (measurement hook — e.g. per-round mesh snapshots;
    readbacks here are host-side analysis, not part of the program).

    ``invariants`` is an ``oracle.InvariantHook`` (docs/DESIGN.md §12):
    every ``check_every`` dispatches it runs its jitted property
    checker on the live batched state and accumulates the ``[S, P]``
    violation mask on DEVICE — zero host transfers inside the window
    (the hook's due rows are materialized up front via
    ``precompute``); read the results back with ``invariants.report()``
    after the run.

    The state buffers are donated each dispatch (the lifted step's
    contract), so callers must not reuse the passed-in ``states``.
    Returns an :class:`EnsembleRun` carrying the compile-count
    sentinel for this segment."""
    import jax

    n_sims = jax.tree_util.tree_leaves(states)[0].shape[0]
    if invariants is not None:
        # no-op if the caller already precomputed (the transfer_guard
        # pattern: materialize due rows before entering the window)
        invariants.precompute(n_steps)
    before = _cache_size(ens_step)
    t0 = time.perf_counter()
    for i in range(n_steps):
        kw = {}
        if heartbeat_fn is not None:
            kw["do_heartbeat"] = bool(heartbeat_fn(i))
        states = ens_step(states, *make_args(i), **kw)
        if invariants is not None:
            invariants.on_step(i, states)
        if observe is not None:
            observe(i, states)
    jax.block_until_ready(states)
    dt = time.perf_counter() - t0
    after = _cache_size(ens_step)
    return EnsembleRun(
        states=states,
        n_sims=int(n_sims),
        rounds=n_steps * int(rounds_per_phase),
        compiles=(-1 if before is None or after is None
                  else after - before),
        seconds=dt,
        dispatches=int(n_steps),
    )


class WindowRunner:
    """One compiled run-window program, reusable across runs (warm
    re-runs hit the same jit — the zero-recompile sentinel gates rely
    on that).

    ``ens_step`` is a lifted ensemble step (batch.lift_step /
    lift_floodsub) or any unbatched jitted step — the window mechanics
    are batch-agnostic, but ``EnsembleRun.n_sims`` (and the aggregate
    rate built on it) reads the leading leaf axis, so it is only
    meaningful for batched trees (unbatched callers drive
    ``driver.make_window`` directly, like scan-smoke does);
    ``n_steps`` is the total dispatch count of a run;
    ``segment_len`` splits it into equal scan segments (the checkpoint
    quantum — ``run`` yields to ``on_segment`` between them), default
    the whole run as ONE dispatch. ``heartbeat_fn(i)`` supplies the
    static cadence (must be periodic with a period dividing
    ``segment_len``); ``invariants`` is an ``oracle.ScanInvariants``;
    ``observe(state) -> pytree`` is stacked per dispatch.
    """

    def __init__(self, ens_step, n_steps: int, *, rounds_per_phase: int = 1,
                 heartbeat_fn=None, invariants=None, observe=None,
                 segment_len: int | None = None, unroll: int = 1):
        from ..driver import make_window, min_cycle

        self.n_steps = int(n_steps)
        self.rounds_per_phase = max(int(rounds_per_phase), 1)
        self.invariants = invariants
        seg = int(segment_len) if segment_len else self.n_steps
        if self.n_steps % seg:
            raise ValueError(
                f"segment_len {seg} does not divide the {self.n_steps}"
                "-dispatch window")
        self.segment_len = seg
        hb = None
        if heartbeat_fn is not None:
            # min_cycle returns the exact minimal cycle of the flag
            # sequence (an aperiodic sequence comes back whole), so
            # divisibility into the segment is the only constraint
            hb = min_cycle(heartbeat_fn(i) for i in range(self.n_steps))
            if seg % len(hb):
                raise ValueError(
                    f"heartbeat_fn's minimal period {len(hb)} does not "
                    f"divide segment_len={seg} — every segment must "
                    "compile the same window program")
        ce = 1
        check = None
        if invariants is not None:
            check = invariants.check
            ce = invariants.check_every
            if seg % ce:
                raise ValueError(
                    f"segment_len {seg} must be a multiple of the "
                    f"invariant check_every {ce} (checks must land on "
                    "segment boundaries for exact resume)")
        self.window = make_window(ens_step, heartbeat=hb, check=check,
                                  check_every=ce, observe=observe,
                                  unroll=unroll)
        self._observe = observe is not None

    def _cache_size(self):
        try:
            return int(self.window._cache_size())
        except Exception:  # pragma: no cover — newer-jax fallback
            return None

    def dispatch(self, states, xs, due=None, consts=()):
        """One window invocation, ASYNC (no blocking, no timing) — the
        supervised service loop's seam (serve/supervisor.py): dispatch
        segment k, assemble segment k+1's ``xs`` host-side while the
        device executes, then read k's ``ys`` when needed. ``xs`` is a
        :meth:`stack_args` tuple sized to this runner's window; ``due``
        the segment's stacked due rows when invariants are folded
        (defaults to this runner's own precompute — segment-LOCAL
        ticks; schedule-aware callers pass their global rows).
        ``consts`` are window-invariant TRACED trailing args appended
        to every step call (driver.make_window's contract) — the tune/
        generation passes the stacked candidate plane here, so a new
        candidate population re-dispatches the SAME compiled window."""
        if self.invariants is None:
            return self.window(states, xs, None, tuple(consts))
        if due is None:
            due = self.invariants.due_rows(self.segment_len)
        return self.window(states, xs, due, tuple(consts))

    def stack_args(self, make_args, lo: int, hi: int) -> tuple:
        """Stack per-dispatch arg tuples ``make_args(i)`` for
        ``i in [lo, hi)`` into the window's xs arrays ([D, ...])."""
        import jax.numpy as jnp

        rows = [tuple(make_args(i)) for i in range(lo, hi)]
        width = {len(r) for r in rows}
        if len(width) != 1:
            raise ValueError(f"make_args returned ragged tuples: {width}")
        return tuple(jnp.stack([r[k] for r in rows])
                     for k in range(width.pop()))

    def run(self, states, make_args, *, on_segment=None,
            consts=()) -> EnsembleRun:
        """Execute the window: ONE dispatch per segment. ``make_args``
        is the run_rounds contract (per-dispatch arg tuples, leading S
        axis per array for lifted steps). ``on_segment(seg_idx,
        states)`` fires between segments — the checkpoint hook
        (checkpoint_every == segment_len, docs/DESIGN.md §14).
        ``consts`` are window-invariant traced trailing step args
        (see :meth:`dispatch`) shared by every segment."""
        import jax

        leaves = jax.tree_util.tree_leaves(states)
        n_sims = leaves[0].shape[0] if leaves[0].ndim else 1
        seg, D = self.segment_len, self.n_steps
        due = (self.invariants.due_rows(D)
               if self.invariants is not None else None)
        cpseg = seg // self.invariants.check_every if due is not None else 0
        consts = tuple(consts)
        before = self._cache_size()
        oks, obs = [], []
        with spans.span("ensemble.run") as ran:
            for g in range(D // seg):
                xs = self.stack_args(make_args, g * seg, (g + 1) * seg)
                dseg = (due[g * cpseg:(g + 1) * cpseg]
                        if due is not None else None)
                states, ys = self.window(states, xs, dseg, consts)
                if "ok" in ys:
                    oks.append(ys["ok"])
                if "obs" in ys:
                    obs.append(ys["obs"])
                if on_segment is not None and g + 1 < D // seg:
                    on_segment(g, states)
            jax.block_until_ready(states)
        after = self._cache_size()
        import numpy as _np

        report = None
        if self.invariants is not None:
            ok = (_np.concatenate([_np.asarray(o) for o in oks])
                  if oks else _np.zeros(
                      (0, len(self.invariants.names)), bool))
            report = self.invariants.report(ok)
        observations = None
        if obs:
            observations = jax.tree_util.tree_map(
                lambda *a: _np.concatenate([_np.asarray(x) for x in a]),
                *obs)
        return EnsembleRun(
            states=states,
            n_sims=int(n_sims),
            rounds=D * self.rounds_per_phase,
            compiles=(-1 if before is None or after is None
                      else after - before),
            seconds=ran.seconds,
            dispatches=D // seg,
            invariant_report=report,
            observations=observations,
        )


def run_window(ens_step, states, make_args, n_steps: int, *,
               rounds_per_phase: int = 1, heartbeat_fn=None,
               invariants=None, observe=None, segment_len=None,
               unroll: int = 1, on_segment=None,
               consts=()) -> EnsembleRun:
    """One-shot :class:`WindowRunner`: compile the whole run as a scan
    window and execute it (ONE dispatch per segment; default one
    segment = one dispatch for the entire run). Drop-in for
    :func:`run_rounds` call sites — same ``make_args`` contract, same
    :class:`EnsembleRun` result — with the invariant hook replaced by
    an ``oracle.ScanInvariants`` folded into the program and
    ``observe`` now a DEVICE function ``state -> pytree`` (stacked per
    dispatch in ``EnsembleRun.observations``)."""
    return WindowRunner(
        ens_step, n_steps, rounds_per_phase=rounds_per_phase,
        heartbeat_fn=heartbeat_fn, invariants=invariants, observe=observe,
        segment_len=segment_len, unroll=unroll,
    ).run(states, make_args, on_segment=on_segment, consts=consts)


def shard_ensemble_state(states, mesh, n_peers: int, axis: str = "peers",
                         n_edges: int | None = None):
    """Place a BATCHED state tree onto a device mesh (see the module
    docstring for the three layouts). ``axis="peers"`` shards dim 1 of
    every leaf whose dim-1 extent is ``n_peers`` (the batched analogue
    of parallel.shard_state); ``axis="sims"`` shards the leading sim
    axis and replicates nothing else — every leaf carries it;
    ``axis="sims+peers"`` composes both on a 2-D
    ``parallel.make_mesh_2d`` mesh (named axes ``sims``/``peers``):
    every leaf's leading sim dim rides the ``sims`` mesh axis and
    peer-dim-1 leaves are additionally split over ``peers``.

    ``n_edges`` (round 18) extends the dim-1 rule to the CSR-RESIDENT
    flat planes ([S, E, ...] leaves): the row-owner-ordered edge axis
    partitions with the peer axis (parallel.state_shardings has the
    alignment argument). Pass ``net.n_edges`` — None on dense builds."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.sharding import peer_spec

    def _row_dim(leaf) -> bool:
        if not (hasattr(leaf, "shape") and leaf.ndim >= 2):
            return False
        return leaf.shape[1] == n_peers or (
            n_edges is not None and leaf.shape[1] == n_edges)

    if axis == "sims":
        # peer_spec is "all mesh axes on one dim" — reused here for the
        # SIM dim: each chip owns S/D whole sims, peer axis local
        sims = NamedSharding(mesh, peer_spec(mesh))
        return jax.device_put(states, jax.tree_util.tree_map(
            lambda _: sims, states))
    if axis == "sims+peers":
        names = tuple(mesh.axis_names)
        if names != ("sims", "peers"):
            raise ValueError(
                "axis='sims+peers' needs a 2-D mesh with axis_names "
                f"('sims', 'peers') — parallel.make_mesh_2d; got {names}")
        both = NamedSharding(mesh, P("sims", "peers"))
        sims_only = NamedSharding(mesh, P("sims"))

        def choose2d(leaf):
            if _row_dim(leaf):
                return both
            return sims_only

        return jax.device_put(states, jax.tree_util.tree_map(
            choose2d, states))
    if axis != "peers":
        raise ValueError(
            f"axis must be 'peers', 'sims' or 'sims+peers', got {axis!r}")
    peer = NamedSharding(
        mesh, P(None, *(
            (tuple(mesh.axis_names),) if len(mesh.axis_names) > 1
            else (mesh.axis_names[0],)
        ))
    )
    repl = NamedSharding(mesh, P())

    def choose(leaf):
        if _row_dim(leaf):
            return peer
        return repl

    return jax.device_put(states, jax.tree_util.tree_map(choose, states))
