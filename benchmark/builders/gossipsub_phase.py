"""Builder kind ``gossipsub_phase``: the GossipSub phase engine under
``driver.make_scan``, wired as ``perf.sweep.bench_cell`` wires it (a copy
of that wiring: later PRs may edit ``perf/sweep.py``, not the yardstick).

This is the one place where the benchmark touches the program. It hands
the program the graph and subscriptions the harness made, builds the
step and the scanned window from the configuration file, and reads the
program's final state back as plain numpy ``answers`` for the reference.
"""

from __future__ import annotations

import dataclasses

import numpy as np


class Built:
    """One configuration, built: the window, fresh states and what the
    reference needs to judge the window's answers."""

    def __init__(self, config, graph, subs, net, cfg, step, fresh, devices):
        self.config = config
        self.graph = graph          # numpy, made by the harness
        self.subs = subs
        self.net = net
        self.cfg = cfg
        self.step = step
        self.fresh = fresh
        self.devices = devices
        self.n_peers = int(config["n_peers"])
        self.n_topics = int(config["n_topics"])
        self.rounds_per_phase = int(config["rounds_per_phase"])

    def make_window(self, unroll_phases: int):
        """The scanned window ``run(state, origin, topic, valid) -> state``
        over ``[rounds, P]`` schedules (``driver.make_scan``)."""
        from go_libp2p_pubsub_tpu.driver import make_scan

        return make_scan(
            self.step,
            heartbeat_every=int(self.config["heartbeat_every"]),
            rounds_per_phase=self.rounds_per_phase,
            static_heartbeat=True,
            unroll=max(1, int(unroll_phases)),
        )

    def summary_fn(self):
        """``state -> (tick, live receipts)``: the scalar summary a user
        reads between segments."""
        import jax
        import jax.numpy as jnp

        def summary(st):
            have = st.core.dlv.have
            return st.core.tick, jnp.sum(
                jax.lax.population_count(have).astype(jnp.int32))

        return jax.jit(summary)

    def state_shapes(self, state) -> list:
        """``(shape, itemsize)`` of every leaf of the carried state."""
        import jax

        out = []
        for leaf in jax.tree_util.tree_leaves(state):
            if jax.dtypes.issubdtype(leaf.dtype, jax.dtypes.prng_key):
                leaf = jax.random.key_data(leaf)
            out.append((tuple(leaf.shape), leaf.dtype.itemsize))
        return out

    def answers(self, state) -> dict:
        """The final state's answers as numpy, named for the reference."""
        import jax

        core = state.core
        get = lambda x: np.asarray(jax.device_get(x))
        out = {
            "tick": int(core.tick),
            "msg_origin": get(core.msgs.origin),
            "msg_birth": get(core.msgs.birth),
            "msg_topic": get(core.msgs.topic),
            "have": get(core.dlv.have),
            "first_round": get(core.dlv.first_round),
            "fe_words": get(core.dlv.fe_words),
            "mesh": get(state.mesh),
            "backoff_expire": get(state.backoff_expire),
            "backoff_present": get(state.backoff_present),
            "ihave_out": get(state.ihave_out),
            "graft_out": get(state.graft_out),
            "prune_out": get(state.prune_out),
            "mcache": get(state.mcache),
        }
        if self.config["score_enabled"]:
            out.update(
                scores=get(state.scores),
                mesh_time=get(state.score.mesh_time),
                graft_tick=get(state.score.graft_tick),
                fmd=get(state.score.fmd),
                bp=get(state.score.bp),
            )
        return out


def build(config: dict, seed: int, devices, n_peers: int | None = None,
          control: dict | None = None) -> Built:
    """Build ``config`` on ``devices``. ``n_peers`` overrides the size (the
    tests' toy runs only). ``control`` builds a program that breaks what
    the file states (``{"chaos_loss_rate": x}``: lossy links;
    ``{"program_mesh_params": {...}}``: other mesh parameters, such as
    gossip switched off): the control of "How correct is decided", never
    a cell."""
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
    from go_libp2p_pubsub_tpu.parallel import make_mesh, shard_state
    from go_libp2p_pubsub_tpu.state import Net

    from benchmark.harness import graphs

    if n_peers is not None:
        config = dict(config, n_peers=int(n_peers))
    n = int(config["n_peers"])
    t = int(config["n_topics"])
    devices = list(devices)
    if len(devices) > 1 and n % len(devices):
        raise ValueError(f"{n} peers do not divide over {len(devices)} devices")
    jax.config.update("jax_default_prng_impl", config["prng_impl"])

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
    if config["graph"]["kind"] == "ring_lattice" and net.band_off is None:
        raise RuntimeError("the program no longer takes the ring lattice as "
                           "banded: this would be another program")

    mp = dict(config["mesh_params"],
              **((control or {}).get("program_mesh_params") or {}))
    params = dataclasses.replace(
        GossipSubParams(), D=mp["D"], Dlo=mp["D_lo"], Dhi=mp["D_hi"],
        Dscore=mp["D_score"], Dout=mp["D_out"], Dlazy=mp["D_lazy"],
        gossip_factor=mp["gossip_factor"],
        history_length=mp["history_length"],
        history_gossip=mp["history_gossip"], flood_publish=False)
    score_on = bool(config["score_enabled"])
    sp = None
    if score_on:
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
    if control and control.get("chaos_loss_rate"):
        from go_libp2p_pubsub_tpu.chaos import ChaosConfig

        chaos = ChaosConfig(loss_rate=float(control["chaos_loss_rate"]))
    cfg = GossipSubConfig.build(
        params, PeerScoreThresholds(), score_enabled=score_on,
        heartbeat_every=int(config["heartbeat_every"]), chaos=chaos)
    # tracer-detached, and no fanout slots: every peer subscribes every
    # topic, so a publish never goes through fanout
    cfg = dataclasses.replace(cfg, count_events=False, fanout_slots=0)

    def fresh():
        st = GossipSubState.init(net, int(config["msg_slots"]), cfg,
                                 score_params=sp, seed=int(seed))
        if len(devices) > 1:
            st = shard_state(st, make_mesh(devices=devices), n)
        return st

    step = make_gossipsub_phase_step(
        cfg, net, int(config["rounds_per_phase"]), score_params=sp)
    return Built(config, g, s, net, cfg, step, fresh, devices)
