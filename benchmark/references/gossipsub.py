"""The plain reference for GossipSub cells: the protocol's rules in
numpy, applied to the answers the timed window left behind.

It imports nothing of the program and takes nothing the program made
except the answers it judges. Its inputs are the graph, subscriptions and
publish schedule the harness drew from the seed, and the configuration
file. The engine draws its random choices (mesh candidates, gossip
targets) from a device PRNG whose stream differs between backends, so no
second implementation can reproduce a run bit for bit; what the protocol
fixes, whatever the draws, is checked answer by answer:

  tick_gap        ``core.tick`` against the rounds run, at every summary
  msgs_mismatch   the message table against a ring allocator run over the
                  same schedule (origin, birth, topic of every slot)
  have_mismatch   seen-cache bits against first-receipt rounds; the origin
                  holds its message from its birth round
  causality       every holder got its first copy over a real edge, from a
                  peer that held the message in an earlier round, inside
                  the message's lifetime
  push_gap_share  eager push: a peer that takes a message in round t has
                  sent it to every mesh peer by round t+1, so a mesh peer
                  holds it by then (checked for sends of the last phase,
                  over mesh edges both ends agree on)
  mesh_off_graph  mesh membership only over real edges
  mesh_degree_out (peer, topic) pairs whose mesh degree after the window's
                  last heartbeat is over D_hi but by the heartbeat's own
                  outbound top-up (``over_d_hi``), or under D_lo while a
                  neighbour could still be grafted (on the graph, not in
                  the mesh, no backoff entry, score not negative)
  backoff_in_mesh mesh edges still under a prune backoff
  ihave_mismatch  gossip emission of the last heartbeat (emitGossip,
                  gossipsub.go:1669-1723), from the IHAVE outbox: per peer
                  and topic the number of targets is max(D_lazy,
                  gossip_factor x candidates) of its non-mesh neighbours,
                  no target is in the mesh, and each target is told every
                  message of the topic the peer first saw in the last
                  history_gossip heartbeats, and nothing else
  undelivered     (configurations that state ``full_delivery_rounds``)
                  subscribers without a message that old, of the messages
                  born once the mesh was built (``mesh_build_rounds``)
  delivery_rounds_max  (the same configurations) the latest first receipt
                  of any such message, in rounds after its birth; the limit
                  is the last round ``full_delivery_rounds`` allows
  score_gap       (scored configurations) the score plane against the
                  score recomputed from the counters, in the stated dtype
  fmd_short       (scored) edges whose first-delivery counter is under what
                  the live messages' first arrivals over that edge (the
                  first-arrival edge plane) give it after the decays since
  mesh_time_mismatch (scored) mesh edges whose time in mesh is not the
                  last heartbeat's tick less the tick of their graft
"""

from __future__ import annotations

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
    quantum = f(max(1.0, np.ceil(sc["time_in_mesh_quantum_s"])))
    p1 = np.minimum(f(ans["mesh_time"]) / quantum, f(sc["time_in_mesh_cap"]))
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


def check(ans: dict, graph: dict, subs: dict, config: dict, tail: dict,
          rounds_run: int, summaries: list) -> list:
    """Every number compared, as ``{"name", "value", "limit"}``; the run is
    correct when no value is over its limit. ``tail`` holds the schedule's
    last rounds (``start``, ``origin``, ``topic``); ``summaries`` the
    ``(rounds so far, tick read)`` pairs of the window's segments."""
    m = int(config["msg_slots"])
    r = int(config["rounds_per_phase"])
    limits = config["limits"]
    nbr, nbr_ok = graph["nbr"], graph["nbr_ok"]
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
    slot_of = subs["slot_of"]
    s_idx = np.arange(mesh.shape[1])[None, :, None]
    mutual = mesh & mesh[np.clip(nbr, 0, None)[:, None, :], s_idx,
                         graph["rev"][:, None, :]]
    causal_bad = 0
    push_checked = push_bad = 0
    undelivered = slowest = 0
    full_after = config.get("full_delivery_rounds")
    mesh_built = int(config.get("mesh_build_rounds", 0))
    scored = bool(config["score_enabled"])
    if scored:
        decay = np.float32(config["score"]["first_message_deliveries_decay"])
        fmd_floor = np.zeros(mesh.shape, np.float32)
    for s in live:
        w, b = divmod(int(s), WORD)
        o, t0, tp = int(origin[s]), int(birth[s]), int(topic[s])
        holders = np.flatnonzero(fr[:, s] >= 0)
        if (full_after is not None and t_end - t0 >= int(full_after)
                and t0 >= mesh_built):
            undelivered += int(np.sum(subs["subscribed"][:, tp])
                               - np.sum(subs["subscribed"][holders, tp]))
        t_h = fr[holders, s].astype(np.int64)
        if full_after is not None and t0 >= mesh_built and holders.size:
            slowest = max(slowest, int(t_h.max()) - t0)
        # causality: the first copy came over one real edge from an
        # earlier holder
        recv = holders != o
        hn, ht = holders[recv], t_h[recv]
        fe = (ans["fe_words"][hn, :, w] >> np.uint32(b)) & np.uint32(1)
        one = fe.sum(axis=1) == 1
        ke = fe.argmax(axis=1)
        q = nbr[hn, ke].astype(np.int64)
        tq = fr[np.clip(q, 0, None), s].astype(np.int64)
        ok = (one & nbr_ok[hn, ke] & (tq >= 0)
              & (ht > tq) & (ht > t0) & (ht < t_end))
        causal_bad += int(np.sum(~ok))
        if scored:
            # a first arrival credits its edge once, and the credit has
            # been decayed at every heartbeat since
            decays = (t_end - 1 - ht) // int(config["heartbeat_every"]) + 1
            np.add.at(fmd_floor, (hn, slot_of[hn, tp], ke),
                      np.where(ok, decay ** decays.astype(np.float32),
                               np.float32(0)))
        # eager push over agreed mesh edges, for sends of the last phase
        t_send = t_h + 1
        sent = (t_send >= t_end - r) & (t_send <= t_end - 1)
        if not sent.any():
            continue
        ps, ts = holders[sent], t_send[sent]
        sl = slot_of[ps, tp]
        edges = mutual[ps, sl] & nbr_ok[ps]                  # [P,K]
        pi, ki = np.nonzero(edges)
        qq = nbr[ps[pi], ki].astype(np.int64)
        tqq = fr[qq, s].astype(np.int64)
        got = (tqq >= 0) & (tqq <= ts[pi])
        push_checked += got.size
        push_bad += int(np.sum(~got))
    number("causality", causal_bad)
    number("push_gap_share",
           push_bad / push_checked if push_checked else 1.0,
           limits["push_gap_share"])
    if full_after is not None:
        number("undelivered", undelivered)
        number("delivery_rounds_max", slowest, int(full_after) - 1)

    number("mesh_off_graph", int(np.sum(mesh & ~nbr_ok[:, None, :])))
    mp = config["mesh_params"]
    deg = mesh.sum(axis=2)
    backoff = ans["backoff_present"] & (ans["backoff_expire"] > t_end)
    # upstream grafts no peer with a backoff entry, expired or not, until
    # the lazy clear removes it (gossipsub.go:1360-1376, 1596 ff.)
    graftable = nbr_ok[:, None, :] & ~mesh & ~ans["backoff_present"]
    if scored:
        graftable &= (ans["scores"] >= 0)[:, None, :]
    joined = subs["my_topics"] >= 0
    number("mesh_degree_out", int(np.sum(joined & (
        over_d_hi(mesh, graph["outbound"], mp)
        | ((deg < int(mp["D_lo"])) & graftable.any(axis=2))))))
    number("backoff_in_mesh", int(np.sum(mesh & backoff)))
    number("ihave_mismatch", ihave_mismatch(
        ans, graph, subs, mp, fr, birth, topic, t_end,
        int(config["heartbeat_every"])))

    if config["score_enabled"]:
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


def ihave_mismatch(ans, graph, subs, mp, fr, birth, topic, t_end,
                   heartbeat_every) -> int:
    """Peers, targets and mesh edges at odds with emitGossip's rules in
    the IHAVE outbox the window's last heartbeat left."""
    nbr_ok = graph["nbr_ok"]
    ihave = ans["ihave_out"]                                 # [N,K,W]
    m = fr.shape[1]
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
        candidates = nbr_ok & ~in_mesh & member[:, None]
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
