"""Fixed-schedule run-window compiler: one XLA program per bench window.

`make_gossipsub_step(static_heartbeat=True)` and the phase engine
(`make_gossipsub_phase_step`) both take a *static* ``do_heartbeat``
argument — the jit-idiomatic form of the reference's 1 Hz heartbeat timer
against continuous delivery (gossipsub.go:1278-1301): the cadence is
known at trace time, so non-heartbeat rounds contain no heartbeat code at
all (no lax.cond branch-materialization copies of the state).

That made the cadence a *caller-owned contract*
(``do_heartbeat == (tick % heartbeat_every == 0)``) with nothing
enforcing it. This module is the enforcement — and, since round 14, the
dispatch-amortization layer (docs/DESIGN.md §14): :func:`make_window`
compiles a WHOLE run window (every per-dispatch input stacked as scan
``xs`` — publish batches, churn ``up`` rows, scheduled chaos
``link_deny`` masks — state donated through the scan carry) into ONE
jitted program, with the observability hooks folded INTO the scan body:

  * invariant checks (oracle/invariants.py) run every ``check_every``
    dispatches inside the scan — due rows ride as stacked ``xs``, the
    previous-counters snapshot rides the carry, and the ``[P]`` (or
    batched ``[S, P]``) violation masks come back as scan ``ys``;
  * arbitrary device observations (``observe(state) -> pytree``) are
    stacked as per-dispatch ``ys`` (per-round mesh snapshots etc.);
  * the telemetry plane needs no folding at all — its panel rows are
    written by the step itself and ride the carry (docs/DESIGN.md §11).

so a chaos + telemetry + invariant-checked bench window is a single
XLA dispatch instead of one per round/phase. :func:`make_scan` (the
rounds-4..13 driver API) is now a thin adapter over the same window
body, so every driver — bench, sweeps, the ensemble runner, the report
cells — compiles through one code path.

DONATION RULE: the window donates the state tree through the scan carry
(``donate_argnums=0``), exactly like the jitted steps donate their
state — callers must NOT reuse a state tree after a window.

Edge layout (round 15): windows carry the sparse data plane for free —
a CSR-built step (cfg.edge_layout="csr", ops/csr.py) scans its flat
[E] exchange inside the same one-dispatch program, with the folded
invariant checker reading the unchanged state tree (`make scale-smoke`
drives an N=1M CSR window this way; tests/test_csr.py pins
scanned-vs-loop parity on the csr layout).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .ops import edges
from .perf import stages


def heartbeat_schedule(heartbeat_every: int, rounds_per_phase: int) -> list[bool]:
    """Static per-phase heartbeat flags over one schedule period.

    Phase p covers ticks [p*r, (p+1)*r); it heartbeats iff that window
    contains a tick ≡ 0 (mod heartbeat_every). The pattern repeats every
    lcm(he, r) ticks = lcm(he, r)//r phases. With r == 1 this is the
    per-round static-heartbeat contract (True on every he-th round)."""
    he, r = int(heartbeat_every), int(rounds_per_phase)
    assert he >= 1 and r >= 1
    period = math.lcm(he, r) // r
    return [
        any(((p * r + i) % he) == 0 for i in range(r))
        for p in range(period)
    ]


def form_mesh(step, st, *, rounds_per_phase: int, pub_width: int = 4,
              pv_dtype=jnp.bool_, up=None):
    """One-shot immediate-Join formation prelude for a phase step
    (gossipsub.go:1015-1064: Join selects mesh peers immediately; the
    reference never has a window where a joined topic has no mesh).

    The phase engine's first heartbeat otherwise fires at the first phase
    TAIL, so publishes in phase 0 find no mesh and only flood/fanout
    paths deliver (measured: 56% coverage at r=32 with a 24-round
    warmup). This runs ONE publish-free phase with ``do_heartbeat=True``:
    the tail heartbeat selects every node's mesh (the Join analogue, all
    nodes joining simultaneously) and the NEXT phase's control head
    ingests the resulting GRAFTs before any data sub-round — so the first
    phase a caller publishes into sees a formed, two-sided mesh, exactly
    like the per-round engine's round-0/1 formation.

    Advances ``tick`` by ``rounds_per_phase``. Alignment: with
    heartbeat_every <= rounds_per_phase (every standard phase config —
    any r-wide window then contains a heartbeat tick, so the schedule is
    all-True) the caller's subsequent make_scan schedule stays valid;
    he > r callers must account for the r-tick shift themselves.

    ``pv_dtype`` must match the verdict dtype of the caller's later
    publish batches (bool or int8 codes) or the prelude pays one extra
    trace of the jitted step. ``up`` is the [N] liveness plane for
    dynamic_peers builds."""
    r = int(rounds_per_phase)
    po = jnp.full((r, pub_width), -1, jnp.int32)
    pt = jnp.zeros((r, pub_width), jnp.int32)
    pv = jnp.zeros((r, pub_width), pv_dtype)
    args = (po, pt, pv) if up is None else (po, pt, pv, up)
    return step(st, *args, do_heartbeat=True)


def min_cycle(flags) -> list[bool]:
    """The minimal repeating pattern of a periodic flag sequence (the
    whole sequence when aperiodic) — so a window built from a full
    per-dispatch heartbeat list compiles the same program as one built
    from the schedule pattern."""
    flags = [bool(b) for b in flags]
    n = len(flags)
    for p in range(1, n + 1):
        if n % p == 0 and all(flags[i] == flags[i % p] for i in range(n)):
            return flags[:p]
    return flags


def _core_of(st):
    """The SimState face of any engine state (GossipSubState wraps it)."""
    return st.core if hasattr(st, "core") else st


def _key_as_placed(st):
    """``st`` with its typed PRNG key's base array in the sharding it was
    placed with. Over more than one device jax 0.9 returns a typed key
    from a jitted call with its base array sharded as the key's LOGICAL
    sharding (``P()``) where the call came back through its C++ fast path
    and as the physical one (``P(None)``) where it came back through
    Python, and the dispatch cache keys on the base: a window that carries
    a sharded key would add a second cache entry, after its first call,
    for the same executable. The buffers are the same; only the array
    that names them changes. One device (or no key): ``st`` unchanged."""
    from jax.sharding import NamedSharding, PartitionSpec

    core = _core_of(st)
    key = getattr(core, "key", None)
    if (not isinstance(key, jax.Array) or isinstance(key, jax.core.Tracer)
            or len(key.devices()) < 2
            or not jax.dtypes.issubdtype(key.dtype, jax.dtypes.prng_key)):
        return st
    base = jax.random.key_data(key)
    placed = base.sharding
    if (not isinstance(placed, NamedSharding)
            or len(placed.spec) >= base.ndim):
        return st
    spec = PartitionSpec(*placed.spec, *[None] * (base.ndim - len(placed.spec)))
    base = jax.make_array_from_single_device_arrays(
        base.shape, NamedSharding(placed.mesh, spec),
        [shard.data for shard in base.addressable_shards])
    core = core.replace(key=jax.random.wrap_key_data(
        base, impl=jax.random.key_impl(key)))
    return st.replace(core=core) if hasattr(st, "core") else core


class _Window:
    """A jitted window whose state argument's typed key is handed over as
    it was placed (:func:`_key_as_placed`), and whose call or lowering
    lets a trace see the concrete state it was made for
    (``perf.stages.CALLED_STATE``); everything else is the jitted
    function's (``_cache_size``, ...)."""

    def __init__(self, jitted):
        self.jitted = jitted

    def __call__(self, st, *args, **kwargs):
        st = _key_as_placed(st)
        token = stages.CALLED_STATE.set(st)
        try:
            return self.jitted(st, *args, **kwargs)
        finally:
            stages.CALLED_STATE.reset(token)

    def lower(self, st, *args, **kwargs):
        token = stages.CALLED_STATE.set(st)
        try:
            return self.jitted.lower(st, *args, **kwargs)
        finally:
            stages.CALLED_STATE.reset(token)

    def __getattr__(self, name):
        return getattr(self.jitted, name)


def _jit_window(run, donate: bool):
    """``jax.jit`` of a window body, as the one thing that reaches the
    device as a module: named from the scopes' version (the module name
    is part of the persistent cache's key, named scopes are not:
    ``perf.stages.VERSION``) and noted in the stage registry while its
    Python body is traced, so once per trace and never per dispatch. The
    same trace counts the rows its edge gathers address by index
    (``ops/edges.tally_index_rows``), per step call, the rows and the
    tile-rows of the table they read, the calls that crossed in column
    slices or by the explicit halo exchange, the rows its peer gathers
    address, the rows a shard received in the per-shard plan's exchange,
    and the step calls one call of it runs."""
    def window(*args, **kwargs):
        rows: list = []
        with edges.tally_index_rows(rows):
            out = run(*args, **kwargs)
        stages.note_window(
            wrapped, args, kwargs,
            edge_rows_per_dispatch=edges.edge_rows_per_dispatch(rows),
            edge_table_rows=edges.edge_table_rows(rows),
            edge_table_tile_rows=edges.edge_table_rows(rows, "tile_rows"),
            edge_sliced_calls_per_dispatch=edges.edge_rows_per_dispatch(
                rows, "sliced"),
            halo_exchanges_per_dispatch=edges.edge_rows_per_dispatch(
                rows, "halo"),
            peer_rows_per_dispatch=edges.edge_rows_per_dispatch(rows, "peer"),
            remote_rows_per_dispatch=edges.edge_rows_per_dispatch(
                rows, "remote"),
            dispatches=sum(n for kind, n in rows if kind == "window"))
        return out
    window.__name__ = window.__qualname__ = stages.window_name()
    wrapped = _Window(jax.jit(window, donate_argnums=0 if donate else ()))
    return wrapped


def make_window(
    step,
    *,
    heartbeat=None,
    check=None,
    check_every: int = 1,
    observe=None,
    unroll: int = 1,
    donate: bool = True,
):
    """Compile a whole run window into one program:
    ``run(state, xs, due=None) -> (state, ys)``.

    * ``xs`` is a tuple of per-dispatch arrays, each with leading axis
      ``D`` (the dispatch count): publish batches (``[D, P]`` per-round
      / ``[D, r, P]`` phase), churn ``up`` rows ``[D, N]``, scheduled
      chaos ``link_deny`` masks ``[D, N, K]`` — for ensemble windows
      every row additionally carries the sim axis (``[D, S, ...]``).
      Dispatch ``d`` consumes row ``d`` of every array, exactly as if
      ``step`` had been called ``D`` times from Python.
    * ``heartbeat`` is the static cadence pattern (a bool sequence,
      cycled over the window — :func:`heartbeat_schedule` shape) for
      steps that take a keyword-only ``do_heartbeat``; None for steps
      that own their cadence on device.
    * ``check`` folds the invariant oracle into the scan body: an EAGER
      predicate ``check(state, prev_events, due_row) -> [P]`` (batched:
      ``[S, P]``) evaluated every ``check_every`` dispatches — build it
      with ``oracle.invariants.ScanInvariants``. ``due`` is the stacked
      ``[n_checks, 6]`` due-row plane (``ScanInvariants.precompute``);
      the previous-counters snapshot rides the scan carry (initialized
      from the window-entry counters) and the violation masks come back
      in ``ys["ok"]`` (``[n_checks, P]`` / ``[n_checks, S, P]``).
    * ``observe`` is a device function ``state -> pytree`` evaluated
      after every dispatch; the per-dispatch stack comes back in
      ``ys["obs"]`` (leading axis D).
    * ``consts`` (run-time argument, round 16) is a tuple of TRACED
      window-invariant inputs appended to every step call after the
      per-dispatch row — the lifted score plane's seat: a whole window
      runs one weight set as ONE dispatch, and re-running the SAME
      compiled window with a different plane is recompile-free
      (tests/test_score_lift.py pins scanned-vs-loop parity and the
      window-level one-compile A/B).

    The window requires ``D`` to be a multiple of
    ``lcm(len(heartbeat pattern), check_every)``; the checker runs once
    per ``check_every`` dispatches via a nested scan when the cadence
    allows (the compiled program then contains the step body once, not
    ``check_every`` times). The state is donated (module docstring).
    """
    return _jit_window(
        _window_body(step, heartbeat=heartbeat, check=check,
                     check_every=check_every, observe=observe,
                     unroll=unroll),
        donate)


def _window_body(step, *, heartbeat=None, check=None, check_every: int = 1,
                 observe=None, unroll: int = 1):
    """The unjitted ``run(state, xs, due=None, consts=())`` of
    :func:`make_window`, for callers that trace it inside a window of
    their own (:func:`make_scan`)."""
    hb = None if heartbeat is None else min_cycle(heartbeat)
    period = 1 if hb is None else len(hb)
    ce = int(check_every)
    if ce < 1:
        raise ValueError(f"check_every must be >= 1, got {ce}")
    block = math.lcm(period, ce) if check is not None else period
    cpb = block // ce if check is not None else 0  # checks per block

    def call(st, args, j, consts=()):
        if hb is None:
            edges.mark_dispatch()
            return step(st, *args, *consts)
        do_heartbeat = hb[j % period]
        edges.mark_dispatch(do_heartbeat)
        return step(st, *args, *consts, do_heartbeat=do_heartbeat)

    def run(st, xs, due=None, consts=()):
        xs = tuple(xs)
        consts = tuple(consts)
        if not xs:
            raise ValueError("make_window: xs must carry at least one "
                             "per-dispatch array (the dispatch count is "
                             "read from its leading axis)")
        n_dispatch = xs[0].shape[0]
        edges.mark_window(n_dispatch)
        for a in xs[1:]:
            if a.shape[0] != n_dispatch:
                raise ValueError(
                    f"make_window: xs leading axes disagree "
                    f"({[a.shape[0] for a in xs]})")
        if n_dispatch % block:
            raise ValueError(
                f"window length {n_dispatch} dispatches is not a multiple "
                f"of lcm(heartbeat period={period}, check_every={ce}) = "
                f"{block}")
        n_blocks = n_dispatch // block
        if check is not None:
            if due is None:
                raise ValueError("make_window: a checked window needs the "
                                 "stacked [n_checks, 6] due rows")
            if due.shape[0] != n_blocks * cpb:
                raise ValueError(
                    f"due rows {due.shape[0]} != expected checks "
                    f"{n_blocks * cpb} ({n_dispatch} dispatches every {ce})")
        gro = lambda a: a.reshape((n_blocks, block) + a.shape[1:])
        bx = tuple(gro(a) for a in xs)
        bdue = (due.reshape((n_blocks, cpb) + due.shape[1:])
                if check is not None else None)

        nested = check is not None and ce % period == 0 and ce > period
        if nested:
            # the block is ONE check preceded by ce dispatches that the
            # inner scan rolls — the compiled program carries the step
            # body `period` times (once, in the common period-1 case),
            # not `check_every` times
            def inner_body(s, rows):
                obs = []
                for j in range(period):
                    s = call(s, tuple(r[j] for r in rows), j, consts)
                    if observe is not None:
                        obs.append(observe(s))
                ys = (jax.tree_util.tree_map(lambda *a: jnp.stack(a), *obs)
                      if observe is not None else None)
                return s, ys

            def body(carry, xs_blk):
                s, prev = carry
                rows, drow = xs_blk
                regro = lambda a: a.reshape(
                    (ce // period, period) + a.shape[1:])
                s, obs = jax.lax.scan(inner_body, s,
                                      tuple(regro(r) for r in rows),
                                      unroll=max(1, int(unroll)))
                ev = _core_of(s).events
                ok = check(s, prev, drow[0])
                ys = {"ok": ok[None]}
                if observe is not None:
                    ys["obs"] = obs
                return (s, ev), ys
        else:
            def body(carry, xs_blk):
                s, prev = carry
                rows, drows = xs_blk
                oks, obs = [], []
                for j in range(block):
                    s = call(s, tuple(r[j] for r in rows), j, consts)
                    if observe is not None:
                        obs.append(observe(s))
                    if check is not None and (j + 1) % ce == 0:
                        ev = _core_of(s).events
                        oks.append(check(s, prev, drows[(j + 1) // ce - 1]))
                        prev = ev
                ys = {}
                if oks:
                    ys["ok"] = jnp.stack(oks)
                if obs:
                    ys["obs"] = jax.tree_util.tree_map(
                        lambda *a: jnp.stack(a), *obs)
                return (s, prev), (ys or None)

        if check is not None:
            carry0 = (st, _core_of(st).events)
            (st, _), ys = jax.lax.scan(
                body, carry0, (bx, bdue),
                unroll=1 if nested else max(1, int(unroll)))
        elif observe is not None:
            def obs_body(s, rows):
                obs = []
                for j in range(block):
                    s = call(s, tuple(r[j] for r in rows), j, consts)
                    obs.append(observe(s))
                return s, jax.tree_util.tree_map(
                    lambda *a: jnp.stack(a), *obs)
            st, obs = jax.lax.scan(obs_body, st, bx,
                                   unroll=max(1, int(unroll)))
            ys = {"obs": obs}
        else:
            def plain_body(s, rows):
                for j in range(block):
                    s = call(s, tuple(r[j] for r in rows), j, consts)
                return s, None
            st, _ = jax.lax.scan(plain_body, st, bx,
                                 unroll=max(1, int(unroll)))
            ys = None

        out = {}
        if ys:
            if "ok" in ys:
                a = ys["ok"]
                out["ok"] = a.reshape((-1,) + a.shape[2:])
            if "obs" in ys:
                # nested mode stacks obs [n_blocks, inner, period, ...];
                # flat mode [n_blocks, block, ...] — per-dispatch order
                # is row-major either way
                lead = 3 if nested else 2
                out["obs"] = jax.tree_util.tree_map(
                    lambda a: a.reshape((n_dispatch,) + a.shape[lead:]),
                    ys["obs"])
        return st, out

    return run


def make_scan(
    step,
    *,
    heartbeat_every: int = 1,
    rounds_per_phase: int = 1,
    static_heartbeat: bool | None = None,
    unroll: int = 1,
    donate: bool = True,
):
    """Build ``run(state, pub_origin, pub_topic, pub_valid) -> state``
    scanning a full publish schedule through ``step`` with the heartbeat
    cadence owned here.

    * per-round step, plain build (heartbeat decided on device or
      heartbeat_every == 1): pub_* are [R, P]; plain scan.
    * per-round step built with ``static_heartbeat=True``: pub_* are
      [R, P]; rounds are grouped so ``do_heartbeat`` is True exactly on
      ticks ≡ 0 (mod heartbeat_every).
    * phase step (``rounds_per_phase`` = r > 1): pub_* are [R, P] and are
      grouped into R//r phases of [r, P]; each phase's ``do_heartbeat``
      is True iff its tick window contains a heartbeat tick.

    Steps built with ``dynamic_peers=True`` take the liveness schedule as
    ``run(st, po, pt, pv, up)`` with ``up`` a [R, N] bool plane; phase
    steps consume one row per phase (the phase head's — transitions land
    once per phase).

    Contract: the state's tick at entry must be ≡ 0 (mod lcm(he, r)) —
    any state freshly init'd (tick 0) or previously driven only through
    this function qualifies. R must be a multiple of lcm(he, r).

    Since round 14 this is a thin adapter over :func:`make_window` (the
    run-window compiler): it regroups the flattened ``[R, ...]``
    schedules into per-dispatch rows and compiles the same scan body
    every window-driven caller uses.
    """
    he = int(heartbeat_every)
    r = int(rounds_per_phase)
    if static_heartbeat is None:
        if r == 1 and he > 1:
            # a per-round step at he > 1 is either a plain build (decides
            # the heartbeat on device) or a static_heartbeat build (takes
            # the do_heartbeat kwarg) — the two have different call
            # signatures and nothing here can introspect a jitted wrapper
            raise ValueError(
                "make_scan: pass static_heartbeat=True/False explicitly "
                "for a per-round step with heartbeat_every > 1 (True for "
                "a make_gossipsub_step(static_heartbeat=True) build, "
                "False for a plain build)"
            )
        static_heartbeat = r > 1
    lcm = math.lcm(he, r)
    sched = heartbeat_schedule(he, r) if static_heartbeat else None
    # traced inside the adapter's own jit below
    raw = _window_body(step, heartbeat=sched, unroll=unroll)

    def run(st, po, pt, pv, up=None, consts=()):
        n_rounds = po.shape[0]
        if n_rounds % lcm != 0:
            raise ValueError(
                f"schedule length {n_rounds} is not a multiple of "
                f"lcm(heartbeat_every={he}, rounds_per_phase={r}) = {lcm}"
            )
        if r > 1:
            d = n_rounds // r
            gro = lambda a: a.reshape((d, r) + a.shape[1:])
            xs = (gro(po), gro(pt), gro(pv))
            if up is not None:
                # a phase consumes ONE liveness plane (peer transitions
                # land once per phase, at its head) — the first round's
                # row of the [R, N] schedule
                xs += (gro(up)[:, 0],)
        else:
            xs = (po, pt, pv) + (() if up is None else (up,))
        st, _ = raw(st, xs, None, tuple(consts))
        return st
    return _jit_window(run, donate)
