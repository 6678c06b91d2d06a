"""Builder kind ``gossipsub_subnets``: the GossipSub phase engine under
``driver.make_scan`` for a configuration in which a peer subscribes a few
of many topics and publishes to the others through fanout (the Ethereum
attestation subnets). The same ``build`` / ``Built`` surface as
``builders/gossipsub_phase.py``, which it extends: the graph and the
subscriptions come from ``harness/subnets.py``, the heartbeat interval
and the fanout slots from the configuration file, and the answers carry
the fanout planes for the reference.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.harness import manifest as mf
from benchmark.harness import subnets

_phase = mf.load_plugin("builders", "gossipsub_phase")


class Built(_phase.Built):
    """``gossipsub_phase.Built`` plus the fanout planes among the answers."""

    def answers(self, state) -> dict:
        import jax

        out = super().answers(state)
        for name in ("fanout_topic", "fanout_peers", "fanout_lastpub"):
            out[name] = np.asarray(jax.device_get(getattr(state, name)))
        return out


def build(config: dict, seed: int, devices, n_peers: int | None = None,
          control: dict | None = None) -> Built:
    """Build ``config`` on ``devices``. ``n_peers`` overrides the size (the
    tests' toy runs only). ``control`` builds a program that breaks what
    the file states, as in ``gossipsub_phase.build``, and besides
    ``{"fanout_slots": 0}``: the program without fanout, in which a
    publish from outside its topic goes nowhere."""
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

    control = control or {}
    if n_peers is not None:
        config = dict(config, n_peers=int(n_peers))
    n = int(config["n_peers"])
    devices = list(devices)
    if len(devices) > 1 and n % len(devices):
        raise ValueError(f"{n} peers do not divide over {len(devices)} devices")
    jax.config.update("jax_default_prng_impl", config["prng_impl"])

    hb_s = float(config["heartbeat_interval_s"])
    mp = dict(config["mesh_params"], **(control.get("program_mesh_params") or {}))
    params = dataclasses.replace(
        GossipSubParams(), D=mp["D"], Dlo=mp["D_lo"], Dhi=mp["D_hi"],
        Dscore=mp["D_score"], Dout=mp["D_out"], Dlazy=mp["D_lazy"],
        gossip_factor=mp["gossip_factor"],
        history_length=mp["history_length"],
        history_gossip=mp["history_gossip"], flood_publish=False,
        heartbeat_interval=hb_s, fanout_ttl=float(config["fanout_ttl_s"]))
    score_on = bool(config["score_enabled"])
    th = config["score_thresholds"]
    thresholds = dataclasses.replace(
        PeerScoreThresholds(), gossip_threshold=th["gossip"],
        publish_threshold=th["publish"])
    chaos = None
    if control.get("chaos_loss_rate"):
        from go_libp2p_pubsub_tpu.chaos import ChaosConfig

        chaos = ChaosConfig(loss_rate=float(control["chaos_loss_rate"]))
    cfg = GossipSubConfig.build(
        params, thresholds, score_enabled=score_on,
        heartbeat_every=int(config["heartbeat_every"]), chaos=chaos)
    # the file states the fanout's life in seconds; a program that counts
    # it in heartbeats and compares that against its clock of rounds
    # cannot run the configuration as stated: say so, and run nothing
    ttl_rounds = getattr(cfg, "fanout_ttl_rounds", None)
    if ttl_rounds != int(config["fanout_ttl_rounds"]):
        raise RuntimeError(
            f"the program's fanout slots live {ttl_rounds} rounds where "
            f"{config['name']} states {config['fanout_ttl_rounds']} "
            f"({config['fanout_ttl_s']} s): this program cannot run it")
    cfg = dataclasses.replace(
        cfg, count_events=False,
        fanout_slots=int(control.get("fanout_slots", config["fanout_slots"])))

    g, s = subnets.build(config, n)
    topo = pgraph.Topology(
        nbr=g["nbr"], nbr_ok=g["nbr_ok"], rev=g["rev"],
        outbound=g["outbound"],
        degree=g["nbr_ok"].sum(axis=1).astype(np.int32))
    subs = pgraph.Subscriptions(
        subscribed=s["subscribed"], my_topics=s["my_topics"],
        slot_of=s["slot_of"])
    net = Net.build(topo, subs)

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
            topics={i: tp for i in range(int(config["n_topics"]))},
            skip_app_specific=True,
            behaviour_penalty_weight=sc["behaviour_penalty_weight"],
            behaviour_penalty_threshold=sc["behaviour_penalty_threshold"],
            behaviour_penalty_decay=sc["behaviour_penalty_decay"],
        )

    def fresh():
        st = GossipSubState.init(net, int(config["msg_slots"]), cfg,
                                 score_params=sp, seed=int(seed))
        if len(devices) > 1:
            st = shard_state(st, make_mesh(devices=devices), n)
        return st

    step = make_gossipsub_phase_step(
        cfg, net, int(config["rounds_per_phase"]), score_params=sp,
        heartbeat_interval=hb_s)
    return Built(config, g, s, net, cfg, step, fresh, devices)
