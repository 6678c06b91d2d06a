"""The plain reference for a GossipSub v1.1 cell under attack: a fixed
share of the peers are sybil SQUATTERS, which run the whole control plane
(subscribe, GRAFT, PRUNE, IHAVE, IWANT) and never transmit message data,
and everything v1.1 adds to defend a network is live (P3, P3b, P4, P7,
negative-score pruning, the validation queue, the peer gater). The
protocol's rules in numpy, applied to the answers the timed window left.

This configuration's copy of ``references/gossipsub.py`` (with
``references/gossipsub_subnets.py``'s repair of the score membership): it
imports nothing of the program and takes nothing the program made except
the answers it judges, among them the sybil mask the harness drew
(``harness/sybils.py``). It keeps every number of that reference under
its name, made sybil-aware, and adds what the attack makes checkable:

  push_gap_share      as there, over HONEST holders and honest mesh peers
                      (a squatter holder sends nothing; a receiver whose
                      validation queue overflowed inside the gater's quiet
                      period is left out: validation.go:230-244 refuses
                      the copy, and peer_gater.go:320-363 may be dropping
                      its senders' messages by a random draw)
  sybil_sourced       first-arrival edges whose far end is a sybil
  sybil_origin_spread holders of a sybil's own publish other than itself
  honest_undelivered  honest subscribers without an honest-origin message
                      ``full_delivery_rounds`` old and born once the meshes
                      were built (``mesh_build_rounds``)
  honest_delivery_rounds_max  the latest first receipt of such a message
                      by an honest peer, in rounds after its birth
  mesh_negative       mesh edges, after the last heartbeat, to a peer the
                      holder scores below 0 (gossipsub.go:1361-1368)
  score_gap           the f32 score plane against P1 + P2 + P3 + P3b + P4 +
                      P7 recomputed from the counters (score.go:263-335)
  mmd_short           edges whose mesh-delivery counter is under what the
                      live messages' first arrivals over that edge give it
                      after the decays since (one-sided, as ``fmd_short``:
                      near-first duplicates inside the window add to it)
  imd_nonzero         edges with an invalid-delivery count (every publish
                      of the schedule is valid)
  activation_early    edges whose P3 activation latch is set with a time
                      in mesh not over the file's ``p3_activation`` rounds
  gater_throttled     HONEST peers whose validation queue overflowed from
                      round ``queue_settle_rounds`` on (``last_throttle``),
                      against ``limits.gater_throttled``: a peer that is
                      served by gossip alone gets a heartbeat's messages
                      in one round and may overflow; while the first
                      meshes form and until the first squatters are
                      expelled, more are
  validate_short      honest peers whose gater counts fewer messages into
                      validation (``validate``) than their live first
                      receipts give after the decays since (one-sided)
  sybil_mesh_share    the share of honest peers' mesh edges that point at
                      sybils after the last heartbeat; judged from
                      ``sybil_mesh_share.after_rounds`` rounds on (a
                      younger run prints it under a limit of 1)

and ``publishes_sybil_share``, the share of the live publishes that are a
squatter's own and go nowhere (limit 1, cannot fail).
"""

from __future__ import annotations

import math

import numpy as np

WORD = 32
#: a counter that is a sum of decayed credits is compared one-sidedly
#: with this much room for a sum in another order (f32 eps 1.2e-7)
SUM_ROOM = 1e-5


def unpack_bits(words: np.ndarray, m: int) -> np.ndarray:
    """``[..., W]`` uint32 words -> ``[..., m]`` bool, bit b of word w is
    message ``w * 32 + b``."""
    shifts = np.arange(WORD, dtype=np.uint32)
    bits = (words[..., :, None] >> shifts) & np.uint32(1)
    return bits.reshape(words.shape[:-1] + (-1,))[..., :m].astype(bool)


def pack_bits(bits: np.ndarray) -> np.ndarray:
    """``[..., m]`` bool -> ``[..., ceil(m / 32)]`` uint32 words."""
    pad = -bits.shape[-1] % WORD
    if pad:
        bits = np.concatenate(
            [bits, np.zeros(bits.shape[:-1] + (pad,), bool)], axis=-1)
    b = bits.reshape(bits.shape[:-1] + (-1, WORD)).astype(np.uint32)
    return (b << np.arange(WORD, dtype=np.uint32)).sum(axis=-1, dtype=np.uint32)


def dtype_of(name: str) -> np.dtype:
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(name)


def allocate(tail_start: int, origin: np.ndarray, topic: np.ndarray,
             m: int) -> dict:
    """The message table after the schedule's last round: publish number
    ``g`` (counting every publish of the run) takes slot ``g mod m``.
    ``origin``/``topic`` are the last rounds' ``[L, P]`` planes, the first
    of them round ``tail_start``; L*P >= m publishes cover every slot."""
    rounds, p = origin.shape
    if rounds * p < m and tail_start > 0:
        raise ValueError("the schedule tail does not cover every slot")
    out = {k: np.full((m,), -1, np.int64) for k in ("origin", "birth", "topic")}
    for i in range(rounds):
        for j in range(p):
            g = (tail_start + i) * p + j
            out["origin"][g % m] = origin[i, j]
            out["topic"][g % m] = topic[i, j]
            out["birth"][g % m] = tail_start + i
    return out


def last_heartbeat_membership(ans: dict) -> tuple:
    """``(scored, pruned, grafted)``, each ``[N,S,K]``: the mesh as the
    last heartbeat's score refresh saw it (the scores are taken at the top
    of the heartbeat, gossipsub.go:1303 ff., before it prunes and grafts),
    the edges that heartbeat pruned, and the edges it grafted. An edge was
    in the mesh at the refresh exactly if the refresh just wrote its time
    in mesh; the PRUNE outbox also holds the answers to GRAFTs refused at
    the phase's head (gossipsub.go:753-792), which were in no mesh."""
    last = int(ans["tick"]) - 1
    graft = ans["graft_tick"].astype(np.int64)
    fresh = (graft >= 0) & (ans["mesh_time"] == last - graft)
    pruned = ans["prune_out"] & fresh & ~ans["mesh"]
    grafted = ans["mesh"] & (graft == last)
    return (ans["mesh"] & ~grafted) | pruned, pruned, grafted


def score_planes(ans: dict, graph: dict, sc: dict, dtype) -> tuple:
    """``[N, K]`` score of every neighbour slot from the score counters
    (score.go:263-335; P5 and P6 weightless), every product and sum in
    ``dtype``, twice: with the P3 activation latch as the final state has
    it, and with it set on the edges the last heartbeat grafted. The graft
    cleared those latches (score.go:642-660) AFTER the scores were taken,
    so the plane holds one of the two there."""
    f = lambda x: np.asarray(x, dtype=dtype)
    # the time-in-mesh quantum as the program counts it: ceil(seconds /
    # heartbeat interval) ticks of its clock
    quantum = max(1.0, math.ceil(sc["time_in_mesh_quantum_s"]))
    in_mesh, pruned, grafted = last_heartbeat_membership(ans)
    thr = f(sc["mesh_message_deliveries_threshold"])
    deficit = thr - f(ans["mmd"])
    short = deficit > 0
    # the sticky penalty a prune adds (score.go:662-684) came after the
    # scores too: take it off the edges the last heartbeat pruned
    mfp = f(ans["mfp"]) - np.where(pruned & ans["mmd_active"] & short,
                                   deficit * deficit, f(0.0))
    p1 = np.minimum(f(ans["mesh_time"]) / f(quantum), f(sc["time_in_mesh_cap"]))
    base = np.where(in_mesh, p1 * f(sc["time_in_mesh_weight"]), f(0.0))
    base = base + f(ans["fmd"]) * f(sc["first_message_deliveries_weight"])
    tail = mfp * f(sc["mesh_failure_penalty_weight"])
    tail = tail + f(ans["imd"]) * f(ans["imd"]) * f(
        sc["invalid_message_deliveries_weight"])
    excess = f(ans["bp"]) - f(sc["behaviour_penalty_threshold"])
    p7 = np.where(excess > 0, excess * excess, f(0.0)) * f(
        sc["behaviour_penalty_weight"])
    planes = []
    for active in (ans["mmd_active"], ans["mmd_active"] | grafted):
        p3 = np.where(active & short, deficit * deficit, f(0.0))
        topic = base + p3 * f(sc["mesh_message_deliveries_weight"]) + tail
        score = (topic * f(sc["topic_weight"])).sum(axis=1, dtype=dtype) + p7
        planes.append(np.where(graph["nbr_ok"], score, f(0.0)))
    return tuple(planes)


def scores_from_counters(ans: dict, graph: dict, subs: dict, sc: dict,
                         dtype) -> np.ndarray:
    """The first of ``score_planes``: what ``run.py`` puts in the
    program's place for the control that lowers the precision."""
    return score_planes(ans, graph, sc, dtype)[0]


def score_gap(program: np.ndarray, planes: tuple) -> float:
    """The widest gap between the program's score plane and the nearer of
    the reference's planes, against the plane's own scale (its largest
    magnitude, at least 1)."""
    got = program.astype(np.float64)
    refs = [p.astype(np.float64) for p in planes]
    scale = max(1.0, float(np.abs(refs[0]).max()))
    gap = np.minimum.reduce([np.abs(got - r) for r in refs])
    return float(gap.max()) / scale


def over_d_hi(mesh: np.ndarray, outbound: np.ndarray, mp: dict) -> np.ndarray:
    """``[N, S]``: meshes over D_hi after a heartbeat that the protocol
    does not allow. The heartbeat prunes a mesh over D_hi down to D and
    THEN tops up the outbound quota of every mesh of D_lo or more, D_hi
    included (gossipsub.go:1451-1476): it grafts D_out less the outbound
    members the mesh holds, each a peer this one dialled (``outbound``
    ``[N, K]``, the graph's own plane). So a mesh may stand over D_hi only
    by outbound members, no more than it holds, and holds no more than
    D_out of them (hence at most D_hi + D_out members); the next
    heartbeat prunes it."""
    deg = mesh.sum(axis=2)
    out = (mesh & outbound[:, None, :]).sum(axis=2)
    over = deg - int(mp["D_hi"])
    return (over > 0) & ((out > int(mp["D_out"])) | (over > out))


def check(ans: dict, graph: dict, subs: dict, config: dict, tail: dict,
          rounds_run: int, summaries: list) -> list:
    """Every number compared, as ``{"name", "value", "limit"}``; the run is
    correct when no value is over its limit. ``tail`` holds the schedule's
    last rounds (``start``, ``origin``, ``topic``); ``summaries`` the
    ``(rounds so far, tick read)`` pairs of the window's segments."""
    m = int(config["msg_slots"])
    r = int(config["rounds_per_phase"])
    he = int(config["heartbeat_every"])
    if he != r:
        raise ValueError("this reference counts a heartbeat a phase")
    limits = config["limits"]
    mp = config["mesh_params"]
    sc = config["score"]
    timers = config["timers"]
    nbr, nbr_ok = graph["nbr"], graph["nbr_ok"]
    nbr0 = np.clip(nbr, 0, None)
    sybil = ans["sybil"].astype(bool)
    honest = ~sybil
    sybil_nbr = sybil[nbr0] & nbr_ok                         # [N,K]
    slot_of = subs["slot_of"]
    t_end = int(rounds_run)
    out = []

    def number(name, value, limit=0):
        out.append({"name": name, "value": value, "limit": limit})

    gap = abs(int(ans["tick"]) - t_end)
    for expected, seen in summaries:
        gap = max(gap, abs(int(seen) - int(expected)))
    number("tick_gap", gap)

    want = allocate(tail["start"], tail["origin"], tail["topic"], m)
    origin, birth, topic = (ans["msg_origin"].astype(np.int64),
                            ans["msg_birth"].astype(np.int64),
                            ans["msg_topic"].astype(np.int64))
    number("msgs_mismatch", int(np.sum(
        (origin != want["origin"]) | (birth != want["birth"])
        | (topic != want["topic"]))))
    # from here on judge by the reference's table: a wrong table has
    # already failed, and the planes are read against what was published
    origin, birth, topic = want["origin"], want["birth"], want["topic"]
    live = np.flatnonzero(birth >= 0)

    fr = ans["first_round"]
    have = unpack_bits(ans["have"], m)
    bad = int(np.sum(have[:, live] != (fr[:, live] >= 0)))
    dead = np.flatnonzero(birth < 0)
    bad += int(have[:, dead].sum())
    bad += int(np.sum(fr[origin[live], live] != birth[live]))
    number("have_mismatch", bad)

    mesh = ans["mesh"]                                       # [N,S,K]
    s_idx = np.arange(mesh.shape[1])[None, :, None]
    mutual = mesh & mesh[nbr0[:, None, :], s_idx, graph["rev"][:, None, :]]
    graft = ans["graft_tick"].astype(np.int64)
    # a receiver whose validation queue overflowed in the last phase
    # (validation.go:230-244) refused what was pushed to it, and one that
    # overflowed inside the quiet period before it may have a gater that
    # drops its senders' messages by a draw (peer_gater.go:320-363)
    quiet = int(timers["gater_quiet"]["rounds"])
    pushable = honest & (ans["gater_last_throttle"].astype(np.int64)
                         < t_end - r - quiet)

    causal_bad = from_sybil = sybil_spread = 0
    push_checked = push_bad = 0
    undelivered = slowest = 0
    judged = judged_sybil = 0
    full_after = int(config["full_delivery_rounds"])
    mesh_built = int(config["mesh_build_rounds"])
    d_fmd = np.float32(sc["first_message_deliveries_decay"])
    d_mmd = np.float32(sc["mesh_message_deliveries_decay"])
    d_val = np.float32(0.01 ** (1.0 / float(config["gater"]["global_decay_s"])))
    fmd_floor = np.zeros(mesh.shape, np.float32)
    mmd_floor = np.zeros(mesh.shape, np.float32)
    validate_floor = np.zeros(nbr.shape[0], np.float32)
    fr_t = np.ascontiguousarray(fr.T)                        # [M,N]
    for s in live:
        w, b = divmod(int(s), WORD)
        o, t0, tp = int(origin[s]), int(birth[s]), int(topic[s])
        holders = np.flatnonzero(fr_t[s] >= 0)
        t_h = fr_t[s][holders].astype(np.int64)
        judged += 1
        if sybil[o]:
            # a squatter's own publish goes nowhere
            judged_sybil += 1
            sybil_spread += int(np.sum(holders != o))
        elif t0 >= mesh_built:
            if t_end - t0 >= full_after:
                undelivered += int(honest.sum() - honest[holders].sum())
            got = t_h[honest[holders]]
            if got.size:
                slowest = max(slowest, int(got.max()) - t0)
        # causality: the first copy came over one real edge from an
        # earlier holder, and never from a sybil
        recv = holders != o
        hn, ht = holders[recv], t_h[recv]
        fe = (ans["fe_words"][hn, :, w] >> np.uint32(b)) & np.uint32(1)
        one = fe.sum(axis=1) == 1
        ke = fe.argmax(axis=1)
        q = nbr[hn, ke].astype(np.int64)
        tq = fr_t[s][np.clip(q, 0, None)].astype(np.int64)
        ok = (one & nbr_ok[hn, ke] & (tq >= 0)
              & (ht > tq) & (ht > t0) & (ht < t_end))
        causal_bad += int(np.sum(~ok))
        from_sybil += int(np.sum(one & sybil_nbr[hn, ke]))
        # a first arrival credits its edge once (P2), and the mesh counter
        # too (P3) where the edge was in the mesh through the arrival's
        # phase; an accepted first receipt enters validation (the gater's
        # count). Each credit has been decayed at every heartbeat since
        decays = ((t_end - 1 - ht) // he + 1).astype(np.float32)
        sl = slot_of[hn, tp]
        np.add.at(fmd_floor, (hn, sl, ke),
                  np.where(ok, d_fmd ** decays, np.float32(0)))
        meshed = ok & mesh[hn, sl, ke] & (graft[hn, sl, ke] < ht // r * r)
        np.add.at(mmd_floor, (hn, sl, ke),
                  np.where(meshed, d_mmd ** decays, np.float32(0)))
        np.add.at(validate_floor, hn, d_val ** decays)
        # eager push over agreed mesh edges, for sends of the last phase
        t_send = t_h + 1
        sent = ((t_send >= t_end - r) & (t_send <= t_end - 1)
                & honest[holders])
        if not sent.any():
            continue
        ps, ts = holders[sent], t_send[sent]
        edges = mutual[ps, slot_of[ps, tp]] & nbr_ok[ps]     # [P,K]
        edges &= pushable[nbr0[ps]]
        pi, ki = np.nonzero(edges)
        qq = nbr[ps[pi], ki].astype(np.int64)
        tqq = fr_t[s][qq].astype(np.int64)
        got = (tqq >= 0) & (tqq <= ts[pi])
        push_checked += got.size
        push_bad += int(np.sum(~got))
    number("causality", causal_bad)
    number("sybil_sourced", from_sybil)
    number("sybil_origin_spread", sybil_spread)
    number("push_gap_share",
           push_bad / push_checked if push_checked else 1.0,
           limits["push_gap_share"])
    number("honest_undelivered", undelivered)
    number("honest_delivery_rounds_max", slowest, full_after - 1)
    number("publishes_sybil_share",
           judged_sybil / judged if judged else 0.0, 1.0)

    number("mesh_off_graph", int(np.sum(mesh & ~nbr_ok[:, None, :])))
    deg = mesh.sum(axis=2)
    backoff = ans["backoff_present"] & (ans["backoff_expire"] > t_end)
    # upstream grafts no peer with a backoff entry, expired or not, until
    # the lazy clear removes it (gossipsub.go:1360-1376, 1596 ff.), and
    # none it scores below 0
    graftable = (nbr_ok & (ans["scores"] >= 0))[:, None, :] & ~mesh \
        & ~ans["backoff_present"]
    joined = subs["my_topics"] >= 0
    number("mesh_degree_out", int(np.sum(joined & (
        over_d_hi(mesh, graph["outbound"], mp)
        | ((deg < int(mp["D_lo"])) & graftable.any(axis=2))))))
    number("backoff_in_mesh", int(np.sum(mesh & backoff)))
    number("mesh_negative", int(np.sum(mesh & (ans["scores"] < 0)[:, None, :])))
    number("ihave_mismatch", ihave_mismatch(
        ans, graph, subs, mp, fr, birth, topic, t_end, he,
        float(config["score_thresholds"]["gossip"])))
    held = mesh[honest]
    share = config["sybil_mesh_share"]
    number("sybil_mesh_share",
           float((held & sybil_nbr[honest][:, None, :]).sum()
                 / max(1, held.sum())),
           float(share["limit"]) if t_end >= int(share["after_rounds"])
           else 1.0)

    planes = score_planes(ans, graph, sc, dtype_of(config["score_dtype"]))
    finite = bool(np.isfinite(ans["scores"]).all())
    number("score_gap",
           score_gap(ans["scores"], planes) if finite else float("inf"),
           limits["score_gap"])
    number("fmd_short",
           int(np.sum(ans["fmd"] < fmd_floor * np.float32(1 - SUM_ROOM))))
    number("mmd_short",
           int(np.sum(ans["mmd"] < mmd_floor * np.float32(1 - SUM_ROOM))))
    number("imd_nonzero", int(np.sum(ans["imd"] > 0)))
    number("mesh_time_mismatch", int(np.sum(mesh & (
        (graft < 0) | (graft > t_end - 1)
        | (ans["mesh_time"] != t_end - 1 - graft)))))
    number("activation_early", int(np.sum(
        ans["mmd_active"]
        & (ans["mesh_time"] <= int(timers["p3_activation"]["rounds"])))))
    late = (ans["gater_last_throttle"].astype(np.int64)
            >= int(config["queue_settle_rounds"]))
    number("gater_throttled", int(np.sum(honest & late)),
           int(limits["gater_throttled"]))
    number("validate_short", int(np.sum(honest & (
        ans["gater_validate"] < validate_floor * np.float32(1 - SUM_ROOM)))))
    return out


def ihave_mismatch(ans, graph, subs, mp, fr, birth, topic, t_end,
                   heartbeat_every, gossip_threshold) -> int:
    """Peers, targets and mesh edges at odds with emitGossip's rules
    (gossipsub.go:1669-1723) in the IHAVE outbox the window's last
    heartbeat left: per peer and topic max(D_lazy, gossip_factor x
    candidates) of its non-mesh neighbours it scores at or above the
    gossip threshold, each told exactly the topic's messages the peer
    first saw in the last ``history_gossip`` heartbeats. A squatter
    gossips like anybody: it is its IWANT service that never comes."""
    nbr_ok = graph["nbr_ok"]
    ihave = ans["ihave_out"]                                 # [N,K,W]
    since = t_end - int(mp["history_gossip"]) * heartbeat_every
    seen = (fr >= since) & (birth >= 0)[None, :]             # [N,M]
    bad = 0
    claimed = np.zeros_like(ihave)
    for tp in range(subs["subscribed"].shape[1]):
        of_topic = (topic == tp) & (birth >= 0)
        mask = pack_bits(of_topic[None, :])[0]               # [W]
        window = pack_bits(seen & of_topic[None, :])         # [N,W]
        told = ihave & mask                                  # [N,K,W]
        claimed |= told
        target = (told != 0).any(axis=2)                     # [N,K]
        sl = subs["slot_of"][:, tp]
        member = sl >= 0
        in_mesh = ans["mesh"][np.arange(len(sl)), np.clip(sl, 0, None)]
        candidates = (nbr_ok & ~in_mesh & member[:, None]
                      & (ans["scores"] >= gossip_threshold))
        n_cand = candidates.sum(axis=1)
        want = np.minimum(n_cand, np.maximum(
            int(mp["D_lazy"]),
            np.floor(float(mp["gossip_factor"]) * n_cand).astype(np.int64)))
        want = np.where((window != 0).any(axis=1), want, 0)
        bad += int(np.sum(target.sum(axis=1) != want))
        bad += int(np.sum(target & ~candidates))
        bad += int(np.sum(target & (told != window[:, None, :]).any(axis=2)))
    bad += int(np.sum((ihave & ~claimed) != 0))
    return bad
