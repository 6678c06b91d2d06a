"""The plain reference for a GossipSub cell under peer churn: peers crash
with all their soft state, their neighbours run RemovePeer, they come back
to the same addresses with a fresh seen-cache, re-announce, are grafted
and catch up on what is still in their neighbours' gossip windows
(handleDeadPeers pubsub.go:648-689, RemovePeer gossipsub.go:545-562,
score retention score.go:604-689). The protocol's rules in numpy, applied
to the answers the timed window left.

It imports nothing of the program and takes nothing the program made
except the answers it judges. It rebuilds the whole liveness history
``[phases, N]`` from the run's seed with ``harness/churn.py`` (row p: who
is up through rounds 8p .. 8p+7; transitions land at phase heads), and
takes the bit helpers, the ring allocator and the score recomputation with
the REPAIRED membership rule from ``references/gossipsub_subnets.py``
(liveness does not enter them). Under churn meshes never settle, so the
first file's reading of the PRUNE outbox as membership would show.

Words used below. A peer's RUN is its last unbroken stretch of up rows,
the one that reaches the end of the window; ``since[n]`` is the run's
first round (0 for a peer that never left, past the end for one that is
down). Whatever a peer holds it has got inside its run: the crash took
the rest. An edge is LIVE in a round if both its ends are up in that
round's phase.

  tick_gap, msgs_mismatch, mesh_off_graph, backoff_in_mesh,
  mesh_time_mismatch   as ``references/gossipsub.py`` (a down origin's
                  slot is allocated as the ring says)
  up_mismatch     ``state.up`` against the last row of the history (and
                  the last row the builder sent against the same)
  down_holders    seen-cache, forward-set, mcache, first-receipt or
                  first-edge bits of a peer that is down
  down_origin_holders  holders of a message whose origin was down at its
                  birth (a stopped process publishes nothing)
  down_in_mesh    mesh, fanout, GRAFT / PRUNE / IHAVE / IWANT outbox,
                  promise, served or IHAVE-counter entries on an edge
                  with a down end
  stale_receipt   first receipts of an up peer stamped before its run
  stale_backoff   backoff entries a peer holds of others from before its
                  run, or while it is down (a restarted router has none)
  have_mismatch   seen-cache bits against first-receipt rounds; an origin
                  up at the birth whose run reaches back to it holds its
                  message from its birth round, and no other origin holds
                  its own (pubsub.go refuses a message from self)
  causality       every holder got its first copy over one real edge that
                  was live in that round, inside the message's lifetime;
                  where the sender's run reaches back to that round, the
                  sender held the message in an earlier round
  push_gap_share  eager push over mesh edges both ends agree on and
                  neither grafted at the last heartbeat, for sends of the
                  last phase (both ends up through it: the mesh holds no
                  dead edge)
  up_undelivered, up_delivery_rounds_max   of the messages
                  ``full_delivery_rounds`` old, born once the meshes were
                  built (``mesh_build_rounds``) by an origin that then
                  stayed up ``full_delivery_rounds``: the peers whose run
                  reaches back to the birth and who do not hold it; the
                  latest first receipt among such peers, in rounds after
                  the birth
  mesh_degree_out after the last heartbeat no up (peer, topic) is over
                  D_hi but by the heartbeat's own outbound top-up
                  (``over_d_hi``), or under D_lo while an UP neighbour could be
                  grafted (live edge, not in the mesh, no backoff entry,
                  score not negative)
  ihave_mismatch  gossip emission as ``references/gossipsub.py``, the
                  candidates and targets over live edges
  catchup_missed_share   gossip catch-up. Of the pairs (peer, message) in
                  which the peer RETURNED 3 or 4 phases before the end and
                  stayed, and the message, still in the table, was first
                  seen by a neighbour (whose run reaches back to that
                  receipt) before the return and inside that neighbour's
                  gossip window at the return's heartbeat: the share the
                  peer does NOT hold. One gossip cycle (IHAVE at the
                  heartbeat, IWANT at the next head, the answer a phase
                  later) has had time; who is told is drawn, so the limit
                  is one less ``catchup.floor``, between the sound
                  readings and the control with gossip off
  score_gap, fmd_short   as ``references/gossipsub.py``, the membership
                  as repaired; an arrival credits its edge only while the
                  edge has not died since (the sender's run reaches back)
  dead_edge_stats P1 / P2 counters or a graft time on an edge with a down
                  end (removePeer deletes the stats of a peer that leaves
                  at 0 or above and resets the first-delivery counter of
                  one it retains)

and, under limits that cannot fail, what the rows did (``peers_left``,
``peers_returned``, ``down_share_end``) beside ``state.up``'s own share
(``down_share_state``) and the sizes of the populations judged.
"""

from __future__ import annotations

import numpy as np

from benchmark.harness import churn
from benchmark.harness import manifest as mf

_subnets = mf.load_plugin("references", "gossipsub_subnets")
unpack_bits = _subnets.unpack_bits
pack_bits = _subnets.pack_bits
dtype_of = _subnets.dtype_of
allocate = _subnets.allocate
scores_from_counters = _subnets.scores_from_counters
score_gap = _subnets.score_gap
over_d_hi = _subnets.over_d_hi

WORD = 32
#: a counter that is a sum of decayed credits is compared one-sidedly
#: with this much room for a sum in another order (f32 eps 1.2e-7)
SUM_ROOM = 1e-5
#: a returning peer is judged for catch-up this many phases after its
#: return (one whole gossip cycle has run; a message of its neighbours'
#: windows at the return is still in the table)
CATCHUP_PHASES = (3, 4)


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
    nbr, nbr_ok = graph["nbr"], graph["nbr_ok"]
    nbr0 = np.clip(nbr, 0, None)
    n_peers = nbr.shape[0]
    slot_of = subs["slot_of"]
    t_end = int(rounds_run)
    phases = t_end // r
    out = []

    def number(name, value, limit=0):
        out.append({"name": name, "value": value, "limit": limit})

    # who was up when, from the seed alone
    hist = churn.liveness(int(ans["churn_seed"]), phases, n_peers,
                          config["churn"], r)
    up_end = hist[-1]
    since = churn.up_since(hist).astype(np.int64) * r        # [N] rounds
    up_at = lambda peer, rnd: hist[np.clip(rnd, 0, t_end - 1) // r, peer]
    live_end = nbr_ok & up_end[:, None] & up_end[nbr0]       # [N,K]
    dead_end = nbr_ok & ~live_end

    gap = abs(int(ans["tick"]) - t_end)
    for expected, seen in summaries:
        gap = max(gap, abs(int(seen) - int(expected)))
    number("tick_gap", gap)
    number("up_mismatch", int(np.sum(ans["up"] != up_end))
           + int(np.sum(ans["last_row"] != up_end))
           + abs(int(ans["rows_sent"]) - phases))

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
    dead = np.flatnonzero(birth < 0)
    # a publish happens if its origin is up in its round's phase
    published = np.zeros(m, bool)
    published[live] = up_at(origin[live], birth[live])

    fr = ans["first_round"].astype(np.int64)                 # [N,M]
    have = unpack_bits(ans["have"], m)
    holds = fr >= 0
    down = ~up_end
    number("down_holders", int(
        np.count_nonzero(ans["have"][down]) + np.count_nonzero(ans["fwd"][down])
        + np.count_nonzero(ans["mcache"][down]) + holds[down].sum()
        + np.count_nonzero(ans["fe_words"][down])))
    number("down_origin_holders",
           int((holds | have)[:, live[~published[live]]].sum()))
    number("stale_receipt",
           int(np.sum(holds[up_end] & (fr[up_end] < since[up_end, None]))))
    bad = int(np.sum(have[:, live] != holds[:, live]))
    bad += int(have[:, dead].sum())
    # the origin holds its message from the birth round on while its run
    # reaches back to it, and never gets it again once it lost it
    kept = published[live] & (since[origin[live]] <= birth[live])
    bad += int(np.sum(fr[origin[live], live]
                      != np.where(kept, birth[live], -1)))
    number("have_mismatch", bad)

    mesh = ans["mesh"]                                       # [N,S,K]
    s_idx = np.arange(mesh.shape[1])[None, :, None]
    graft = ans["graft_tick"].astype(np.int64)
    settled = mesh & (graft < t_end - 1)     # not the last heartbeat's
    mutual = settled & settled[nbr0[:, None, :], s_idx,
                               graph["rev"][:, None, :]]
    full_after = int(config["full_delivery_rounds"])
    mesh_built = int(config["mesh_build_rounds"])
    decay = np.float32(sc["first_message_deliveries_decay"])
    fmd_floor = np.zeros(mesh.shape, np.float32)
    causal_bad = 0
    push_checked = push_bad = 0
    undelivered = slowest = judged_pairs = 0
    fr_t = np.ascontiguousarray(fr.T)                        # [M,N]
    for s in live[published[live]]:
        w, b = divmod(int(s), WORD)
        o, t0, tp = int(origin[s]), int(birth[s]), int(topic[s])
        holders = np.flatnonzero(fr_t[s] >= 0)
        t_h = fr_t[s][holders]
        # full delivery, among the peers whose run reaches back to the
        # birth, for an origin that stayed up to see it through
        stayed = bool(hist[t0 // r:(min(t0 + full_after, t_end - 1)) // r + 1,
                           o].all())
        if t0 >= mesh_built and stayed:
            through = since <= t0
            got = through[holders]
            if t_end - t0 >= full_after:
                undelivered += int(through.sum() - got.sum())
                judged_pairs += int(through.sum())
            if got.any():
                slowest = max(slowest, int(t_h[got].max()) - t0)
        # causality: the first copy came over one real edge, live in that
        # round, from an earlier holder
        recv = holders != o
        hn, ht = holders[recv], t_h[recv]
        fe = (ans["fe_words"][hn, :, w] >> np.uint32(b)) & np.uint32(1)
        one = fe.sum(axis=1) == 1
        ke = fe.argmax(axis=1)
        q = nbr0[hn, ke]
        tq = fr_t[s][q]
        # the sender's receipt is still there to see if its run reaches
        # back to the round it sent in
        sender_kept = since[q] <= ht
        ok = (one & nbr_ok[hn, ke] & up_at(hn, ht) & up_at(q, ht)
              & (ht > t0) & (ht < t_end)
              & (~sender_kept | ((tq >= 0) & (tq < ht))))
        causal_bad += int(np.sum(~ok))
        # a first arrival credits its edge once, decayed at every heartbeat
        # since; the credit went with the edge if the sender left since
        decays = ((t_end - 1 - ht) // he + 1).astype(np.float32)
        np.add.at(fmd_floor, (hn, slot_of[hn, tp], ke),
                  np.where(ok & sender_kept, decay ** decays, np.float32(0)))
        # eager push over agreed mesh edges, for sends of the last phase
        t_send = t_h + 1
        sent = (t_send >= t_end - r) & (t_send <= t_end - 1)
        if not sent.any():
            continue
        ps, ts = holders[sent], t_send[sent]
        edges = mutual[ps, slot_of[ps, tp]] & live_end[ps]   # [P,K]
        pi, ki = np.nonzero(edges)
        tqq = fr_t[s][nbr0[ps[pi], ki]]
        got = (tqq >= 0) & (tqq <= ts[pi])
        push_checked += got.size
        push_bad += int(np.sum(~got))
    number("causality", causal_bad)
    number("push_gap_share",
           push_bad / push_checked if push_checked else 1.0,
           limits["push_gap_share"])
    number("up_undelivered", undelivered)
    number("up_delivery_rounds_max", slowest, full_after - 1)

    number("mesh_off_graph", int(np.sum(mesh & ~nbr_ok[:, None, :])))
    de3 = dead_end[:, None, :]
    number("down_in_mesh", int(
        (mesh & de3).sum() + (ans["fanout_peers"] & de3).sum()
        + (ans["graft_out"] & de3).sum() + (ans["prune_out"] & de3).sum()
        + np.count_nonzero(ans["ihave_out"][dead_end])
        + np.count_nonzero(ans["iwant_out"][dead_end])
        + np.count_nonzero(ans["served_lo"][dead_end])
        + np.count_nonzero(ans["served_hi"][dead_end])
        + (ans["promise_mid"][dead_end] >= 0).sum()
        + np.count_nonzero(ans["peerhave"][dead_end])
        + np.count_nonzero(ans["iasked"][dead_end])))
    deg = mesh.sum(axis=2)
    backoff = ans["backoff_present"] & (ans["backoff_expire"] > t_end)
    # upstream grafts no peer with a backoff entry, expired or not, until
    # the lazy clear removes it (gossipsub.go:1360-1376, 1596 ff.), none
    # it scores below 0, and nobody who is not there
    graftable = ((live_end & (ans["scores"] >= 0))[:, None, :] & ~mesh
                 & ~ans["backoff_present"])
    joined = (subs["my_topics"] >= 0) & up_end[:, None]
    number("mesh_degree_out", int(np.sum(joined & (
        over_d_hi(mesh, graph["outbound"], mp)
        | ((deg < int(mp["D_lo"])) & graftable.any(axis=2))))))
    number("backoff_in_mesh", int(np.sum(mesh & backoff)))
    # a backoff entry is stamped expire = its round + the prune backoff: a
    # restarted process starts with none, so none predates its holder's run
    stamped = (ans["backoff_expire"].astype(np.int64)
               - int(config["timers"]["prune_backoff"]["rounds"]))
    number("stale_backoff", int(np.sum(
        ans["backoff_present"] & (stamped < since[:, None, None]))))
    number("ihave_mismatch", ihave_mismatch(
        ans, live_end, subs, mp, fr, birth, topic, t_end, he))
    missed, pairs = catchup(hist, since, fr, graph, birth, published, r,
                            int(mp["history_gossip"]))
    number("catchup_missed_share", missed / pairs if pairs else 0.0,
           1.0 - float(config["catchup"]["floor"]))

    ref = scores_from_counters(ans, graph, subs, sc,
                               dtype_of(config["score_dtype"]))
    finite = bool(np.isfinite(ans["scores"]).all())
    number("score_gap",
           score_gap(ans["scores"], ref) if finite else float("inf"),
           limits["score_gap"])
    number("fmd_short",
           int(np.sum(ans["fmd"] < fmd_floor * np.float32(1 - SUM_ROOM))))
    number("mesh_time_mismatch", int(np.sum(mesh & (
        (graft < 0) | (graft > t_end - 1)
        | (ans["mesh_time"] != t_end - 1 - graft)))))
    # removePeer: a neighbour that leaves is in no mesh (graft time, time
    # in mesh) and its first-delivery counter is gone, deleted with the
    # stats or reset where they are retained (score below 0): a decaying
    # behaviour penalty is all a dead edge may keep in this configuration
    number("dead_edge_stats", int(np.sum(dead_end[:, None, :] & (
        (ans["fmd"] > 0) | (ans["mesh_time"] > 0) | (graft >= 0)))))

    # what the rows did, beside the program's own plane; cannot fail
    moved = churn.stats(hist)
    number("peers_left", moved["peers_left"], n_peers * phases)
    number("peers_returned", moved["peers_returned"], n_peers * phases)
    number("down_share_end", moved["down_share_end"], 1.0)
    number("down_share_state", float(1.0 - ans["up"].mean()), 1.0)
    number("delivery_pairs_judged", judged_pairs, n_peers * m)
    number("catchup_pairs_judged", pairs, n_peers * m)
    return out


def catchup(hist, since, fr, graph, birth, published, r, history_gossip):
    """``(missed, pairs)``: the (returned peer, message) pairs gossip had
    one whole cycle to serve, and how many of them the peer does not hold.
    ``since`` is every peer's run's first round."""
    phases = hist.shape[0]
    nbr0 = np.clip(graph["nbr"], 0, None)
    missed = pairs = 0
    for back in CATCHUP_PHASES:
        pr = phases - back                   # the row of the return
        if pr < 1:
            continue
        who = np.flatnonzero(hist[pr:].all(axis=0) & ~hist[pr - 1])
        if not who.size:
            continue
        # the neighbours' gossip window at the heartbeat that closes phase
        # pr reaches back ``history_gossip`` heartbeats; the message is
        # older than the return and still in the table
        first, last = (pr + 1 - history_gossip) * r, pr * r
        msgs = np.flatnonzero(published & (birth >= 0) & (birth < last))
        if not msgs.size:
            continue
        q = nbr0[who]                                        # [R,K]
        ok = graph["nbr_ok"][who]
        seen = fr[q][:, :, msgs]                             # [R,K,m]
        told = ((seen >= first) & (seen < last)
                & (since[q] <= first)[:, :, None] & ok[:, :, None]).any(axis=1)
        held = fr[who][:, msgs] >= 0
        pairs += int(told.sum())
        missed += int((told & ~held).sum())
    return missed, pairs


def ihave_mismatch(ans, live_end, subs, mp, fr, birth, topic, t_end,
                   heartbeat_every) -> int:
    """Peers, targets and mesh edges at odds with emitGossip's rules
    (gossipsub.go:1669-1723) in the IHAVE outbox the window's last
    heartbeat left, as ``references/gossipsub.py`` counts them, over the
    edges that are live at the end: a peer that is down emits nothing and
    is nobody's target."""
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
        candidates = live_end & ~in_mesh & member[:, None]
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
