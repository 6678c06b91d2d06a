"""Builder kind ``gossipsub_sybil``: the GossipSub v1.1 phase engine under
``driver.make_scan`` with everything v1.1 adds to DEFEND a network live:
a static adversary vector (squatters that run the control plane and
never transmit message data), the P3 / P3b / P4 weights, the peer gater
and a validation queue. The same ``build`` / ``Built`` surface as
``builders/gossipsub_phase.py``, which it extends: the sybil draw comes
from ``harness/sybils.py``, and the answers carry the defence's counters
and the sybil mask for the reference.

The configuration's file states every duration in seconds AND in rounds
as the program counts it (``timers``); the builder reads the built
program's constants and refuses to build on any mismatch.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.harness import graphs, sybils
from benchmark.harness import manifest as mf

_phase = mf.load_plugin("builders", "gossipsub_phase")

GATER_ANSWERS = ("validate", "throttle", "last_throttle", "deliver",
                 "duplicate", "reject", "ignore")


class Built(_phase.Built):
    """``gossipsub_phase.Built`` plus the defence's counters and the sybil
    mask among the answers."""

    def __init__(self, *args, sybil):
        super().__init__(*args)
        self.sybil = sybil

    def answers(self, state) -> dict:
        import jax

        get = lambda x: np.asarray(jax.device_get(x))
        out = super().answers(state)
        for name in ("mmd", "mfp", "imd", "mmd_active"):
            out[name] = get(getattr(state.score, name))
        for name in GATER_ANSWERS:
            out["gater_" + name] = get(getattr(state.gater, name))
        out["sybil"] = self.sybil
        return out


def program_timers(cfg, tpa) -> dict:
    """The built program's durations in ROUNDS, each as the program
    compares it against its clock (``core.tick`` counts delivery rounds);
    ``None`` where this program has no such constant (a commit that counts
    it in heartbeats cannot run the configuration as stated)."""
    return {
        "p3_activation": int(tpa.activation_ticks[0]) if tpa else None,
        "p3_window": int(tpa.window_rounds[0]) if tpa else None,
        "gater_quiet": getattr(cfg, "gater_quiet_rounds", None),
        "prune_backoff": cfg.prune_backoff_ticks,
        "p1_quantum": int(tpa.quantum_ticks[0]) if tpa else None,
        "score_decay_interval": cfg.heartbeat_every,
        "opportunistic_graft_period": cfg.opportunistic_graft_ticks,
        "backoff_clear": cfg.backoff_clear_ticks,
        "iwant_followup": cfg.iwant_followup_ticks,
    }


def build(config: dict, seed: int, devices, n_peers: int | None = None,
          control: dict | None = None) -> Built:
    """Build ``config`` on ``devices``. ``n_peers`` overrides the size (the
    tests' toy runs only; the sybil count follows the fraction).
    ``control`` builds a program that breaks what the file states, as in
    ``gossipsub_phase.build``, and besides ``{"program_score": {...}}``:
    the program built with other score parameters than the file's (the
    deficit switched off: squatters stay in the meshes), and
    ``{"program_sybils_forward": k}``: the program's adversary vector
    leaves out the first ``k`` of the drawn sybils, who then forward like
    anybody (the answers still name them as sybils);
    ``{"program_validation_capacity": c}``: a validation queue of another
    depth than the file's."""
    import jax

    from go_libp2p_pubsub_tpu import graph as pgraph
    from go_libp2p_pubsub_tpu.config import (
        GossipSubParams,
        PeerGaterParams,
        PeerScoreParams,
        PeerScoreThresholds,
        TopicScoreParams,
        score_parameter_decay,
    )
    from go_libp2p_pubsub_tpu.models.gossipsub import (
        GossipSubConfig,
        GossipSubState,
    )
    from go_libp2p_pubsub_tpu.models.gossipsub_phase import (
        make_gossipsub_phase_step,
    )
    from go_libp2p_pubsub_tpu.parallel import make_mesh, shard_state
    from go_libp2p_pubsub_tpu.score.engine import TopicParamsArrays
    from go_libp2p_pubsub_tpu.state import Net

    control = control or {}
    n_file = int(config["n_peers"])
    if n_peers is not None:
        config = dict(config, n_peers=int(n_peers))
    n = int(config["n_peers"])
    t = int(config["n_topics"])
    he = int(config["heartbeat_every"])
    devices = list(devices)
    if len(devices) > 1 and n % len(devices):
        raise ValueError(f"{n} peers do not divide over {len(devices)} devices")
    jax.config.update("jax_default_prng_impl", config["prng_impl"])

    mp = dict(config["mesh_params"], **(control.get("program_mesh_params") or {}))
    params = dataclasses.replace(
        GossipSubParams(), D=mp["D"], Dlo=mp["D_lo"], Dhi=mp["D_hi"],
        Dscore=mp["D_score"], Dout=mp["D_out"], Dlazy=mp["D_lazy"],
        gossip_factor=mp["gossip_factor"],
        history_length=mp["history_length"],
        history_gossip=mp["history_gossip"], flood_publish=False)
    th = config["score_thresholds"]
    thresholds = PeerScoreThresholds(
        gossip_threshold=th["gossip"], publish_threshold=th["publish"],
        graylist_threshold=th["graylist"],
        accept_px_threshold=th["accept_px"],
        opportunistic_graft_threshold=th["opportunistic_graft"])
    gt = config["gater"]
    gater = PeerGaterParams(
        threshold=gt["threshold"],
        global_decay=score_parameter_decay(gt["global_decay_s"]),
        source_decay=score_parameter_decay(gt["source_decay_s"]),
        quiet=gt["quiet_s"], duplicate_weight=gt["duplicate_weight"],
        ignore_weight=gt["ignore_weight"], reject_weight=gt["reject_weight"])
    chaos = None
    if control.get("chaos_loss_rate"):
        from go_libp2p_pubsub_tpu.chaos import ChaosConfig

        chaos = ChaosConfig(loss_rate=float(control["chaos_loss_rate"]))
    cfg = GossipSubConfig.build(
        params, thresholds, score_enabled=True, heartbeat_every=he,
        gater_params=gater,
        validation_capacity=int(control.get(
            "program_validation_capacity", config["validation_capacity"])),
        chaos=chaos)
    # tracer-detached, and no fanout slots: every peer subscribes the topic
    cfg = dataclasses.replace(cfg, count_events=False, fanout_slots=0)

    sc = dict(config["score"], **(control.get("program_score") or {}))
    # the file's keys are the program's field names, durations with "_s"
    tp = TopicScoreParams(**{key.removesuffix("_s"): value
                             for key, value in sc.items()
                             if not key.startswith("behaviour_penalty")})
    sp = PeerScoreParams(
        topics={i: tp for i in range(t)}, skip_app_specific=True,
        behaviour_penalty_weight=sc["behaviour_penalty_weight"],
        behaviour_penalty_threshold=sc["behaviour_penalty_threshold"],
        behaviour_penalty_decay=sc["behaviour_penalty_decay"])

    # the file states every duration in seconds and in rounds; a program
    # that counts one of them otherwise (P3's activation and window and the
    # gater's quiet period in heartbeats against its clock of rounds)
    # cannot run the configuration as stated: say so, and run nothing
    try:
        tpa = TopicParamsArrays.build(sp, t, 1.0, he)
    except TypeError:
        tpa = None
    have = program_timers(cfg, tpa)
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
    sybil = sybils.draw(config["sybils"], n, n_file)
    topo = pgraph.Topology(
        nbr=g["nbr"], nbr_ok=g["nbr_ok"], rev=g["rev"],
        outbound=g["outbound"],
        degree=g["nbr_ok"].sum(axis=1).astype(np.int32))
    subs = pgraph.Subscriptions(
        subscribed=s["subscribed"], my_topics=s["my_topics"],
        slot_of=s["slot_of"])
    net = Net.build(topo, subs)

    def fresh():
        st = GossipSubState.init(net, int(config["msg_slots"]), cfg,
                                 score_params=sp, seed=int(seed))
        if len(devices) > 1:
            st = shard_state(st, make_mesh(devices=devices), n)
        return st

    squat = sybil.copy()
    squat[np.flatnonzero(sybil)[:int(control.get("program_sybils_forward", 0))]] = False
    step = make_gossipsub_phase_step(
        cfg, net, int(config["rounds_per_phase"]), score_params=sp,
        gater_params=gater, adversary_no_forward=squat)
    return Built(config, g, s, net, cfg, step, fresh, devices, sybil=sybil)
