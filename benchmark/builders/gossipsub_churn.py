"""Builder kind ``gossipsub_churn``: the GossipSub v1.1 phase engine under
``driver.make_scan`` as ``builders/gossipsub_phase.py`` wires it, built
with ``dynamic_peers=True``: peers leave and come back under publish load.
The same ``build`` / ``Built`` surface, which it extends. The window it
hands the driver has the signature ``segment_loop`` calls
(``window(state, po, pt, pv)`` and ``_cache_size()``): INSIDE the call it
draws the segment's liveness rows from ``harness/churn.py``, one a phase,
puts them on the device and appends them to the program's window as its
``up`` plane. It keeps the phase count itself (the loop calls the window
once to warm up and then once a segment, in order), so the rows' draw and
transfer fall inside the driver's ``dispatch`` span.

The configuration's file states its timers in rounds as the program counts
them (``timers``) and the builder refuses a program that differs, as
``builders/gossipsub_sybil.py`` does; it also refuses a program that has
no churn part (``perf.stages.PARTS``): the commit that brought the part
brought the publish gate (a down origin publishes nothing), and a program
without it cannot run the configuration as stated.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.harness import churn, graphs
from benchmark.harness import manifest as mf

_phase = mf.load_plugin("builders", "gossipsub_phase")
_sybil = mf.load_plugin("builders", "gossipsub_sybil")

#: the per-edge planes the reference reads besides ``gossipsub_phase``'s
EDGE_ANSWERS = ("iwant_out", "served_lo", "served_hi", "promise_mid",
                "peerhave", "iasked", "fanout_peers")


class Window:
    """``window(state, po, pt, pv)`` over the program's dynamic window
    ``scan(state, po, pt, pv, up)``: the segment's liveness rows are drawn
    and put on the device here, the phase head's row of each phase."""

    def __init__(self, scan, process: churn.Process, rounds_per_phase: int,
                 static: bool = False, after=None):
        self.scan = scan
        self.process = process
        self.r = int(rounds_per_phase)
        self.static = static
        self.after = after
        self.last_row = None

    def __call__(self, state, po, pt, pv):
        import jax.numpy as jnp

        rows = self.process.rows(po.shape[0] // self.r)
        self.last_row = rows[-1]             # ``rows`` is this call's own
        if self.static:
            # the control: a program built without the liveness plane
            return self.scan(state, po, pt, pv)
        up = jnp.asarray(np.repeat(rows, self.r, axis=0))    # [R, N]
        # a copy: the window donates its state
        cursor = state.core.msgs.cursor + 0 if self.after else None
        state = self.scan(state, po, pt, pv, up)
        if self.after:
            state = self.after(state, cursor, po, up)
        return state

    def _cache_size(self) -> int:
        return self.scan._cache_size()

    def lower(self, state, po, pt, pv):
        """The program's window lowered for these shapes (``scripts/
        window_whiles.py``): the liveness plane is ``[rounds, N]`` bool,
        placed where the schedule is."""
        import jax

        if self.static:
            return self.scan.lower(state, po, pt, pv)
        up = jax.ShapeDtypeStruct((po.shape[0], self.process.n), bool,
                                  sharding=getattr(po, "sharding", None))
        return self.scan.lower(state, po, pt, pv, up)


def publish_gate_off():
    """The control that takes the publish gate out: after the window, a
    down origin holds its own publish as a program without the gate left
    it (seen-cache, forward set, first receipt, mcache), jitted."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def ungate(state, cursor, po, up):
        core = state.core
        rounds, p = po.shape
        m = core.msgs.capacity
        flat = po.reshape(-1)
        slot = (cursor + jnp.arange(rounds * p, dtype=jnp.int32)) % m
        tick = core.tick - rounds + jnp.arange(rounds * p, dtype=jnp.int32) // p
        down = ~up[jnp.arange(rounds * p) // p, flat]
        row = jnp.where(down, flat, up.shape[1])             # OOB: dropped
        bit = jnp.uint32(1) << (slot % 32).astype(jnp.uint32)
        words = jnp.zeros_like(core.dlv.have).at[row, slot // 32].add(
            bit, mode="drop")
        dlv = core.dlv.replace(
            have=core.dlv.have | words, fwd=core.dlv.fwd | words,
            first_round=core.dlv.first_round.at[row, slot].set(
                tick, mode="drop"))
        return state.replace(
            core=core.replace(dlv=dlv),
            mcache=state.mcache.at[:, 1, :].set(state.mcache[:, 1, :] | words))

    return ungate


class Built(_phase.Built):
    """``gossipsub_phase.Built`` whose window carries the liveness rows,
    with the liveness plane and the per-edge soft state among the answers
    and what the reference needs to draw the rows again."""

    def __init__(self, *args, seed, control):
        super().__init__(*args)
        self.seed = int(seed)
        self.control = control
        self.window = None
        self._scans = {}

    def make_window(self, unroll_phases: int):
        # one compiled window a Built: a tool that runs many seeds sets
        # ``seed`` and asks again, and gets a fresh process over it
        scan = self._scans.get(unroll_phases)
        if scan is None:
            scan = self._scans[unroll_phases] = super().make_window(
                unroll_phases)
        process = churn.Process(self.seed, self.n_peers,
                                self.config["churn"], self.rounds_per_phase)
        self.window = Window(
            scan, process, self.rounds_per_phase,
            static=bool(self.control.get("program_static_peers")),
            after=(publish_gate_off()
                   if self.control.get("publish_gate_off") else None))
        return self.window

    def answers(self, state) -> dict:
        import jax

        get = lambda x: np.asarray(jax.device_get(x))
        out = super().answers(state)
        out["up"] = get(state.up)
        out["fwd"] = get(state.core.dlv.fwd)
        for name in EDGE_ANSWERS:
            out[name] = get(getattr(state, name))
        out["churn_seed"] = self.seed
        out["rows_sent"] = self.window.process.phase
        out["last_row"] = self.window.last_row
        return out


def build(config: dict, seed: int, devices, n_peers: int | None = None,
          control: dict | None = None) -> Built:
    """Build ``config`` on ``devices``. ``n_peers`` overrides the size (the
    tests' toy runs only). ``control`` builds a program that breaks what
    the file states: ``chaos_loss_rate`` and ``program_mesh_params`` as in
    ``gossipsub_phase.build`` (lossy links; gossip switched off, a mesh of
    D = 3);
    ``{"program_static_peers": true}``: the program built with
    ``dynamic_peers=False`` under the same rows, which it never sees;
    ``{"publish_gate_off": true}``: a down origin's publish put back
    where a program without the gate left it."""
    import jax

    from go_libp2p_pubsub_tpu import graph as pgraph
    from go_libp2p_pubsub_tpu.config import (
        GossipSubParams,
        PeerScoreParams,
        PeerScoreThresholds,
        TopicScoreParams,
    )
    from go_libp2p_pubsub_tpu.models.gossipsub import (
        GossipSubConfig,
        GossipSubState,
    )
    from go_libp2p_pubsub_tpu.models.gossipsub_phase import (
        make_gossipsub_phase_step,
    )
    from go_libp2p_pubsub_tpu.perf import stages
    from go_libp2p_pubsub_tpu.score.engine import TopicParamsArrays
    from go_libp2p_pubsub_tpu.state import Net

    control = control or {}
    if n_peers is not None:
        config = dict(config, n_peers=int(n_peers))
    n = int(config["n_peers"])
    t = int(config["n_topics"])
    he = int(config["heartbeat_every"])
    devices = list(devices)
    if len(devices) != 1:
        raise ValueError("this builder puts the liveness rows on one device")
    if "churn" not in stages.PARTS:
        raise RuntimeError(
            f"{config['name']} needs a program in which a publish whose "
            "origin is down does not happen; this program has no churn part "
            "(perf.stages.PARTS), so it predates that gate: this program "
            "cannot run it")
    churn.check(config["churn"])
    jax.config.update("jax_default_prng_impl", config["prng_impl"])

    mp = dict(config["mesh_params"], **(control.get("program_mesh_params") or {}))
    params = dataclasses.replace(
        GossipSubParams(), D=mp["D"], Dlo=mp["D_lo"], Dhi=mp["D_hi"],
        Dscore=mp["D_score"], Dout=mp["D_out"], Dlazy=mp["D_lazy"],
        gossip_factor=mp["gossip_factor"],
        history_length=mp["history_length"],
        history_gossip=mp["history_gossip"], flood_publish=False)
    sc = config["score"]
    tp = TopicScoreParams(
        topic_weight=sc["topic_weight"],
        time_in_mesh_weight=sc["time_in_mesh_weight"],
        time_in_mesh_quantum=sc["time_in_mesh_quantum_s"],
        time_in_mesh_cap=sc["time_in_mesh_cap"],
        first_message_deliveries_weight=sc["first_message_deliveries_weight"],
        first_message_deliveries_decay=sc["first_message_deliveries_decay"],
        first_message_deliveries_cap=sc["first_message_deliveries_cap"],
        # honest net, every publish valid: P3, P3b and P4 never fire
        mesh_message_deliveries_weight=0.0,
        mesh_failure_penalty_weight=0.0,
        invalid_message_deliveries_weight=0.0,
    )
    sp = PeerScoreParams(
        topics={i: tp for i in range(t)}, skip_app_specific=True,
        behaviour_penalty_weight=sc["behaviour_penalty_weight"],
        behaviour_penalty_threshold=sc["behaviour_penalty_threshold"],
        behaviour_penalty_decay=sc["behaviour_penalty_decay"],
    )
    chaos = None
    if control.get("chaos_loss_rate"):
        from go_libp2p_pubsub_tpu.chaos import ChaosConfig

        chaos = ChaosConfig(loss_rate=float(control["chaos_loss_rate"]))
    cfg = GossipSubConfig.build(
        params, PeerScoreThresholds(), score_enabled=True, heartbeat_every=he,
        chaos=chaos)
    # tracer-detached, and no fanout slots: every peer subscribes the topic
    cfg = dataclasses.replace(cfg, count_events=False, fanout_slots=0)

    have = _sybil.program_timers(cfg, TopicParamsArrays.build(sp, t, 1.0, he))
    wrong = {name: (have.get(name), spec["rounds"])
             for name, spec in config["timers"].items()
             if have.get(name) != spec["rounds"]}
    if wrong:
        raise RuntimeError(
            f"{config['name']} states its timers in rounds and this program "
            f"counts them otherwise (program, file): {wrong}: this program "
            "cannot run it")

    g = graphs.build_graph(config["graph"], n)
    s = graphs.subscribe_all(n, t)
    topo = pgraph.Topology(
        nbr=g["nbr"], nbr_ok=g["nbr_ok"], rev=g["rev"],
        outbound=g["outbound"],
        degree=g["nbr_ok"].sum(axis=1).astype(np.int32))
    subs = pgraph.Subscriptions(
        subscribed=s["subscribed"], my_topics=s["my_topics"],
        slot_of=s["slot_of"])
    net = Net.build(topo, subs)

    step = make_gossipsub_phase_step(
        cfg, net, int(config["rounds_per_phase"]), score_params=sp,
        dynamic_peers=not control.get("program_static_peers"))
    built = Built(config, g, s, net, cfg, step, None, devices,
                  seed=seed, control=control)
    # the state's PRNG follows the run's seed, like the rows (a tool that
    # runs many seeds through one built window sets ``built.seed``)
    built.fresh = lambda: GossipSubState.init(
        net, int(config["msg_slots"]), cfg, score_params=sp, seed=built.seed)
    return built
