"""The plain reference for GossipSub cells in which a peer subscribes a
few of many topics and publishes to the others through fanout (the
Ethereum attestation subnets): the protocol's rules in numpy, applied to
the answers the timed window left behind.

This configuration's copy of ``references/gossipsub.py``: it imports
nothing of the program and takes nothing the program made except the
answers it judges. It keeps every number of that reference under its
name, with the candidates made topic-aware (a gossip target, a graftable
neighbour and a mesh edge must be a neighbour that SUBSCRIBES the topic;
the two ends of a mesh edge keep the topic in different slots), and adds
what sparse subscription makes checkable:

  off_topic_holders   holders of a message that are neither its origin nor
                      subscribers of its topic
  mesh_off_topic      mesh edges of an empty topic slot, or to a neighbour
                      that does not subscribe the slot's topic
  fanout_off_topic    fanout slots for a topic their peer subscribes, two
                      slots of one peer for one topic, slots of more than
                      D peers, and fanout peers that are no real edge to
                      a subscriber of the slot's topic (or sit in a free
                      slot)
  fanout_short        live fanout slots that do not hold min(D, eligible
                      neighbours) peers after the last heartbeat's top-up
                      (gossipsub.go:1517-1554)
  fanout_slot_mismatch  the fanout table against the schedule: each
                      origin's most recent publishes outside its topics
                      (as many distinct topics as it has slots) hold a
                      slot stamped with that publish's round; no slot is
                      stamped with a round of the schedule's tail in which
                      its peer published nothing there; none is stamped in
                      the future or outlives ``fanout_ttl_rounds``
  fanout_push_gap     the share of (publish from outside the topic in the
                      last phase, fanout peer of its slot) pairs in which
                      the peer does not hold the message one round after
                      its birth (gossipsub.go:1000-1002)
  topic_undelivered   subscribers of the topic without a ROUTABLE message
                      older than ``full_delivery_rounds`` and born once the
                      meshes were built (``mesh_build_rounds``). Routable:
                      the origin subscribes the topic or has a neighbour
                      that does, worked out from graph and subscriptions
                      alone; a publish with nobody to go to stays at its
                      origin (gossipsub.go:983-998 selects fanout peers
                      among connected peers in the topic)
  topic_delivery_rounds_max  the latest first receipt of any such message,
                      in rounds after its birth

and two shares of the judged publishes that no limit can fail (limit 1):
``publishes_routable_share`` and ``publishes_fanout_share`` (the origin
does not subscribe the topic), printed with the compared lines.
"""

from __future__ import annotations

import math

import numpy as np

WORD = 32


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


def scores_from_counters(ans: dict, graph: dict, subs: dict, sc: dict,
                         dtype) -> np.ndarray:
    """``[N, K]`` score of every neighbour slot from the score counters
    (score.go:263-335 with P3, P3b, P4, P5, P6 weightless), every product
    and sum in ``dtype``."""
    f = lambda x: np.asarray(x, dtype=dtype)
    # the time-in-mesh quantum in heartbeats (score.go:263 ff.)
    quantum = max(1.0, math.ceil(sc["time_in_mesh_quantum_s"]
                                 / float(sc.get("heartbeat_interval_s", 1.0))))
    # membership as the heartbeat's refresh saw it: the scores are taken
    # at the top of the heartbeat (gossipsub.go:1303 ff.), before it prunes.
    # The PRUNE outbox also holds the answers to GRAFTs refused at the
    # phase's head (mesh full, backoff: gossipsub.go:753-792), edges that
    # were in no mesh at the refresh: those keep the time-in-mesh of an
    # earlier membership, which the refresh did not touch, while an edge
    # the heartbeat itself pruned has the time the refresh just gave it
    last = int(ans["tick"]) - 1
    graft = ans["graft_tick"].astype(np.int64)
    fresh = (graft >= 0) & (ans["mesh_time"] == last - graft)
    in_mesh = ans["mesh"] | (ans["prune_out"] & fresh)      # [N,S,K]
    p1 = np.minimum(f(ans["mesh_time"]) / f(quantum), f(sc["time_in_mesh_cap"]))
    topic = np.where(in_mesh, p1 * f(sc["time_in_mesh_weight"]), f(0.0))
    topic = topic + f(ans["fmd"]) * f(sc["first_message_deliveries_weight"])
    score = (topic * f(sc["topic_weight"])).sum(axis=1, dtype=dtype)
    excess = f(ans["bp"]) - f(sc["behaviour_penalty_threshold"])
    p7 = np.where(excess > 0, excess * excess, f(0.0))
    score = score + p7 * f(sc["behaviour_penalty_weight"])
    return np.where(graph["nbr_ok"], score, f(0.0))


def score_gap(program: np.ndarray, reference: np.ndarray) -> float:
    """The widest gap between two score planes, against the plane's own
    scale (its largest magnitude, at least 1)."""
    ref = reference.astype(np.float64)
    scale = max(1.0, float(np.abs(ref).max()))
    return float(np.abs(program.astype(np.float64) - ref).max()) / scale


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


def neighbour_subscribes(graph: dict, subs: dict, topic: np.ndarray) -> np.ndarray:
    """``[N, K]``: neighbour k of peer n is a real edge to a subscriber of
    ``topic[n]`` (nobody where ``topic[n]`` < 0)."""
    nbr = np.clip(graph["nbr"], 0, None)
    sub = subs["subscribed"][nbr, np.clip(topic, 0, None)[:, None]]
    return sub & graph["nbr_ok"] & (topic >= 0)[:, None]


def routable(graph: dict, subs: dict) -> np.ndarray:
    """``[N, T]``: a publish of peer n on topic t has somewhere to go: n
    subscribes t, or a neighbour of n does."""
    sub = subs["subscribed"]
    near = np.zeros(sub.shape, bool)
    for k in range(graph["nbr"].shape[1]):
        ok = graph["nbr_ok"][:, k]
        near[ok] |= sub[graph["nbr"][ok, k]]
    return sub | near


def mutual_mesh(ans: dict, graph: dict, subs: dict) -> np.ndarray:
    """``[N, S, K]``: mesh edges both ends agree on. The far end keeps the
    topic in a slot of its own."""
    mesh = ans["mesh"]
    nbr = np.clip(graph["nbr"], 0, None)
    out = np.zeros(mesh.shape, bool)
    for s in range(mesh.shape[1]):
        tp = subs["my_topics"][:, s]
        far = subs["slot_of"][nbr, np.clip(tp, 0, None)[:, None]]   # [N,K]
        back = mesh[nbr, np.clip(far, 0, None), graph["rev"]]
        out[:, s] = (mesh[:, s] & back & (far >= 0) & (tp >= 0)[:, None]
                     & graph["nbr_ok"])
    return out


def fanout_table(tail: dict, subs: dict, n_slots: int) -> dict:
    """``{origin: {topic: round}}``: what each origin's fanout slots hold
    after the schedule's tail if none expires: its most recent publishes
    outside its topics, the ``n_slots`` most recent distinct topics (the
    slot of the oldest stamp is the one a new topic takes,
    gossipsub.go:983-998 with a bounded table), each with the round of
    its last publish. ``evicted`` lists the (round, origin, topic) whose
    slot a later publish of the same round took before it was pushed."""
    held: dict = {}
    evicted = set()
    rounds, p = tail["origin"].shape
    for i in range(rounds):
        t = tail["start"] + i
        for j in range(p):
            o, tp = int(tail["origin"][i, j]), int(tail["topic"][i, j])
            if subs["subscribed"][o, tp]:
                continue
            mine = held.setdefault(o, {})
            mine.pop(tp, None)
            mine[tp] = t                      # most recent last
            while len(mine) > n_slots:
                gone = next(iter(mine))
                if mine.pop(gone) == t:
                    evicted.add((t, o, gone))
    return {"held": held, "evicted": evicted}


def check(ans: dict, graph: dict, subs: dict, config: dict, tail: dict,
          rounds_run: int, summaries: list) -> list:
    """Every number compared, as ``{"name", "value", "limit"}``; the run is
    correct when no value is over its limit. ``tail`` holds the schedule's
    last rounds (``start``, ``origin``, ``topic``); ``summaries`` the
    ``(rounds so far, tick read)`` pairs of the window's segments."""
    m = int(config["msg_slots"])
    r = int(config["rounds_per_phase"])
    limits = config["limits"]
    mp = config["mesh_params"]
    nbr, nbr_ok = graph["nbr"], graph["nbr_ok"]
    nbr0 = np.clip(nbr, 0, None)
    subscribed, slot_of = subs["subscribed"], subs["slot_of"]
    my_topics = subs["my_topics"]
    n = nbr.shape[0]
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
    mutual = mutual_mesh(ans, graph, subs)
    scored = bool(config["score_enabled"])
    thresholds = config.get("score_thresholds", {})
    ft = ans["fanout_topic"].astype(np.int64)                # [N,F]
    fpeers = ans["fanout_peers"]                             # [N,F,K]
    n_fslots = ft.shape[1]
    reach = routable(graph, subs)
    table = fanout_table(tail, subs, int(config["fanout_slots"]))

    causal_bad = off_topic = 0
    push_checked = push_bad = 0
    fpush_checked = fpush_bad = 0
    undelivered = slowest = 0
    judged = judged_routable = judged_fanout = 0
    full_after = config.get("full_delivery_rounds")
    mesh_built = int(config.get("mesh_build_rounds", 0))
    if scored:
        decay = np.float32(config["score"]["first_message_deliveries_decay"])
        fmd_floor = np.zeros(mesh.shape, np.float32)
    fr_t = np.ascontiguousarray(fr.T)                        # [M,N]
    for s in live:
        w, b = divmod(int(s), WORD)
        o, t0, tp = int(origin[s]), int(birth[s]), int(topic[s])
        holders = np.flatnonzero(fr_t[s] >= 0)
        t_h = fr_t[s][holders].astype(np.int64)
        members = subscribed[:, tp]
        off_topic += int(np.sum(~members[holders] & (holders != o)))
        outside = not members[o]
        goes = bool(reach[o, tp]) and (t0, o, tp) not in table["evicted"]
        if t0 >= mesh_built:
            judged += 1
            judged_routable += goes
            judged_fanout += outside
        if full_after is not None and t0 >= mesh_built and goes:
            if t_end - t0 >= int(full_after):
                undelivered += int(members.sum() - members[holders].sum())
            got = holders[members[holders] | (holders == o)]
            if got.size:
                slowest = max(slowest, int(fr_t[s][got].max()) - t0)
        # causality: the first copy came over one real edge from an
        # earlier holder
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
        if scored:
            # a first arrival credits its edge once, in the receiver's
            # slot of the topic, and the credit has been decayed at every
            # heartbeat since
            decays = (t_end - 1 - ht) // int(config["heartbeat_every"]) + 1
            sl = slot_of[hn, tp]
            credit = ok & (sl >= 0)
            np.add.at(fmd_floor, (hn[credit], sl[credit], ke[credit]),
                      decay ** decays[credit].astype(np.float32))
        # the origin's push to its fanout peers, for publishes of the
        # last phase that had a round left to be sent in
        if outside and t_end - r <= t0 <= t_end - 2:
            slots = np.flatnonzero(ft[o] == tp)
            if slots.size:
                qq = nbr[o, fpeers[o, slots[0]]].astype(np.int64)
                tqq = fr_t[s][qq].astype(np.int64)
                fpush_checked += qq.size
                fpush_bad += int(np.sum((tqq < 0) | (tqq > t0 + 1)))
        # eager push over agreed mesh edges, for sends of the last phase
        t_send = t_h + 1
        sent = ((t_send >= t_end - r) & (t_send <= t_end - 1)
                & (slot_of[holders, tp] >= 0))
        if not sent.any():
            continue
        ps, ts = holders[sent], t_send[sent]
        edges = mutual[ps, slot_of[ps, tp]]                  # [P,K]
        pi, ki = np.nonzero(edges)
        qq = nbr[ps[pi], ki].astype(np.int64)
        tqq = fr_t[s][qq].astype(np.int64)
        got = (tqq >= 0) & (tqq <= ts[pi])
        push_checked += got.size
        push_bad += int(np.sum(~got))
    number("causality", causal_bad)
    number("push_gap_share",
           push_bad / push_checked if push_checked else 1.0,
           limits["push_gap_share"])
    number("off_topic_holders", off_topic)
    if n_fslots:
        number("fanout_push_gap",
               fpush_bad / fpush_checked if fpush_checked else 1.0,
               limits["fanout_push_gap"])
    if full_after is not None:
        number("topic_undelivered", undelivered)
        number("topic_delivery_rounds_max", slowest, int(full_after) - 1)
    number("publishes_routable_share",
           judged_routable / judged if judged else 0.0, 1.0)
    number("publishes_fanout_share",
           judged_fanout / judged if judged else 0.0, 1.0)

    number("mesh_off_graph", int(np.sum(mesh & ~nbr_ok[:, None, :])))
    joined = my_topics >= 0
    sub_of_slot = np.stack(
        [neighbour_subscribes(graph, subs, my_topics[:, s])
         for s in range(mesh.shape[1])], axis=1)             # [N,S,K]
    number("mesh_off_topic", int(np.sum(mesh & ~sub_of_slot)))
    deg = mesh.sum(axis=2)
    backoff = ans["backoff_present"] & (ans["backoff_expire"] > t_end)
    # upstream grafts no peer with a backoff entry, expired or not, until
    # the lazy clear removes it (gossipsub.go:1360-1376, 1596 ff.)
    graftable = sub_of_slot & ~mesh & ~ans["backoff_present"]
    if scored:
        graftable &= (ans["scores"] >= 0)[:, None, :]
    number("mesh_degree_out", int(np.sum(joined & (
        over_d_hi(mesh, graph["outbound"], mp)
        | ((deg < int(mp["D_lo"])) & graftable.any(axis=2))))))
    number("backoff_in_mesh", int(np.sum(mesh & backoff)))

    # the fanout table after the last heartbeat
    f_live = ft >= 0
    sub_of_fslot = np.zeros(fpeers.shape, bool)
    for f in range(n_fslots):
        sub_of_fslot[:, f] = neighbour_subscribes(graph, subs, ft[:, f])
    rows = np.arange(n)[:, None]
    fbad = int(np.sum(f_live & subscribed[rows, np.clip(ft, 0, None)]))
    fbad += int(np.sum(fpeers & ~sub_of_fslot))
    fbad += int(np.sum(fpeers.sum(axis=2) > int(mp["D"])))
    for f in range(n_fslots):
        for g in range(f + 1, n_fslots):
            fbad += int(np.sum(f_live[:, f] & (ft[:, f] == ft[:, g])))
    number("fanout_off_topic", fbad)
    eligible = sub_of_fslot
    if scored and n_fslots:
        eligible = eligible & (
            ans["scores"] >= float(thresholds["publish"]))[:, None, :]
    number("fanout_short", int(np.sum(f_live & (
        fpeers.sum(axis=2)
        != np.minimum(int(mp["D"]), eligible.sum(axis=2))))))
    number("fanout_slot_mismatch", fanout_slot_mismatch(
        ans, table, tail, t_end, int(config["fanout_ttl_rounds"])))

    number("ihave_mismatch", ihave_mismatch(
        ans, graph, subs, mp, fr, birth, topic, t_end,
        int(config["heartbeat_every"]),
        float(thresholds["gossip"]) if scored else None))

    if scored:
        dtype = dtype_of(config["score_dtype"])
        ref = scores_from_counters(ans, graph, subs, config["score"], dtype)
        finite = bool(np.isfinite(ans["scores"]).all())
        number("score_gap",
               score_gap(ans["scores"], ref) if finite else float("inf"),
               limits["score_gap"])
        number("fmd_short", int(np.sum(ans["fmd"] < fmd_floor)))
        graft = ans["graft_tick"].astype(np.int64)
        number("mesh_time_mismatch", int(np.sum(mesh & (
            (graft < 0) | (graft > t_end - 1)
            | (ans["mesh_time"] != t_end - 1 - graft)))))
    return out


def fanout_slot_mismatch(ans, table, tail, t_end, ttl_rounds) -> int:
    """Fanout slots at odds with the schedule's tail (see the module's
    docstring)."""
    ft = ans["fanout_topic"].astype(np.int64)
    stamp = ans["fanout_lastpub"].astype(np.int64)
    f_live = ft >= 0
    bad = int(np.sum(f_live & ((stamp > t_end - 1)
                               | (stamp + ttl_rounds < t_end - 1))))
    for o, mine in table["held"].items():
        for tp, t in mine.items():
            bad += int(not np.any((ft[o] == tp) & (stamp[o] == t)))
    # a stamp inside the tail needs a publish of the schedule behind it
    pn, pf = np.nonzero(f_live & (stamp >= tail["start"]))
    for o, f in zip(pn, pf):
        bad += int(table["held"].get(int(o), {}).get(int(ft[o, f]))
                   != int(stamp[o, f]))
    return bad


def ihave_mismatch(ans, graph, subs, mp, fr, birth, topic, t_end,
                   heartbeat_every, gossip_threshold=None) -> int:
    """Peers, targets and edges at odds with emitGossip's rules
    (gossipsub.go:1669-1723, and :1551-1553 for fanout topics) in the IHAVE
    outbox the window's last heartbeat left. A peer gossips once for every
    topic slot it has joined and once for every live fanout slot: to
    max(D_lazy, gossip_factor x candidates) of the neighbours that
    subscribe the slot's topic and are not in the slot's mesh (its fanout
    peers), each told exactly the topic's messages the peer first saw in
    the last ``history_gossip`` heartbeats."""
    ihave = ans["ihave_out"]                                 # [N,K,W]
    n_topics = subs["subscribed"].shape[1]
    since = t_end - int(mp["history_gossip"]) * heartbeat_every
    seen = pack_bits((fr >= since) & (birth >= 0)[None, :])  # [N,W]
    of_topic = pack_bits(
        (topic[None, :] == np.arange(n_topics)[:, None])
        & (birth >= 0)[None, :])                             # [T,W]
    slots = [(subs["my_topics"][:, s].astype(np.int64), ans["mesh"][:, s])
             for s in range(subs["my_topics"].shape[1])]
    slots += [(ans["fanout_topic"][:, f].astype(np.int64),
               ans["fanout_peers"][:, f])
              for f in range(ans["fanout_topic"].shape[1])]
    bad = 0
    claimed = np.zeros(seen.shape, np.uint32)
    for tp, inside in slots:
        mask = np.where((tp >= 0)[:, None], of_topic[np.clip(tp, 0, None)],
                        np.uint32(0))                        # [N,W]
        window = seen & mask
        told = ihave & mask[:, None, :]                      # [N,K,W]
        claimed |= mask
        target = (told != 0).any(axis=2)                     # [N,K]
        candidates = neighbour_subscribes(graph, subs, tp) & ~inside
        if gossip_threshold is not None:
            candidates &= ans["scores"] >= gossip_threshold
        n_cand = candidates.sum(axis=1)
        want = np.minimum(n_cand, np.maximum(
            int(mp["D_lazy"]),
            np.floor(float(mp["gossip_factor"]) * n_cand).astype(np.int64)))
        want = np.where((window != 0).any(axis=1), want, 0)
        bad += int(np.sum(target.sum(axis=1) != want))
        bad += int(np.sum(target & ~candidates))
        bad += int(np.sum(target & (told != window[:, None, :]).any(axis=2)))
    bad += int(np.sum((ihave & ~claimed[:, None, :]) != 0))
    return bad
