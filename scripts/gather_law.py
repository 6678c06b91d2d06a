"""The gather's law: what the bare ``flat[perm]`` row gather of
``ops/edges.edge_permute`` costs on the chip, by rows, by table size, by
row width and by K, on the benchmark's own random graphs; what a scatter
of a short row list costs; and what a tiered gather made of them costs
whole. PR 30's first chip call, PR 32's, PR 34's and PR 36's, and the source
of the constants ``ops/edges.pick_k0`` and ``word_slices`` price a graph
and a plane with.

    python scripts/gather_law.py [--n 100000] [--d 10] [--reps 7]
        [--graph random_connect|subnet_connect] [--k0 20 24 28]
        [--widths 2 5 14] [--cases compact tierB ...]
        [--out chiprun_out/gather_law.json]

``--graph subnet_connect`` is ``eth2-100k``'s own graph (its configuration
file's draw; ``--d`` is not read); ``--cases`` runs only the cases named.

Every program is its own ``jax.jit`` over u32 tables with the index
planes as arguments (one case bakes them in, as the engine does, to show
that it is the same kernel). A time is the median over ``--reps`` calls
of the host clock around one call that ends in ``block_until_ready``;
each case also gives its least. Every line names the platform: a line
from a CPU run is a rehearsal, never a device number.

Cases (``rows_out`` gathered from a ``rows_table``-row table, W words):
  full      the cell's own ``edge_perm`` (graph seed 1)
  seed2     the same N and d on graph seed 2 (another K)
  head      ``perm_head`` of K0 over its own compact table [N*K0, W]
  headfull  the K0 head columns gathered from the FULL table
  pad48     graph seed 1 with K padded to 48
  ident, shuffle   the full shape through arange / a random permutation
  list      the tail's (and the patches') sources from the full table
  scatter   the tail's rows scattered onto the tail columns
  tierA     head from its compact table + patches + tail, joined
  tierB     head from the full table + tail, joined (PR 30's engine)
  compact   ONE gather of N*K0 + T rows out of the compact table (the
            head columns plus the tail's T present rows appended), the
            last T scattered onto the tail columns, joined, rows N-major
            (``n*K0 + k``): PR 32's engine where ``ops/edges.compact_pays``
            (``tierB`` was its other form)
  kmajor    ``ops/edges.edge_permute_tiered`` as the engine runs it since
            PR 34, compact table: the same gather with its rows K-major
            over the lane-padded peer axis (``k*Np + n``), so that no
            per-word relayout loop stands around it
  kmajorB   the same out of the full ``[K*Np]`` table (``compact=False``);
            both cross a plane of any width WHOLE (a plan with no cliff)
  kmajorS, kmajorBS   the same two with every plane wider than a tile
            crossing in tile-wide column slices (``ops/edges.word_slices``
            with the cliff at the table's own size; widths over a tile only)
  whole     ``edge_permute`` as the engine calls it ([N, K, W] in and out)
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def graph(kind: str, n: int, d: int, seed: int):
    """``(perm[N,K] full-space flat involution, nbr_ok[N,K])`` of the
    benchmark's ``random_connect`` graph, or of ``eth2-100k``'s
    ``subnet_connect`` with the configuration's own parameters."""
    from benchmark.harness import graphs, subnets
    from go_libp2p_pubsub_tpu.ops import edges

    if kind == "subnet_connect":
        with open(os.path.join(ROOT, "benchmark/configs/eth2-100k.json")) as f:
            config = json.load(f)
        config["graph"] = dict(config["graph"], seed=seed)
        g, _ = subnets.build(config, n)
    else:
        g = graphs.build_graph({"kind": kind, "d": d, "seed": seed}, n)
    return (edges.build_edge_perm(g["nbr"], g["rev"], g["nbr_ok"]),
            g["nbr_ok"])


def pad_k(perm, nbr_ok, k_new):
    """The same graph with K padded to ``k_new`` (absent slots
    self-point)."""
    n, k = perm.shape
    own = np.arange(n * k_new, dtype=np.int32).reshape(n, k_new)
    out = own.copy()
    out[:, :k] = np.where(nbr_ok, (perm // k) * k_new + perm % k, own[:, :k])
    return out


def tier_indices(perm, nbr_ok, k0):
    """Every index plane a tiered variant needs, numpy; ``kmajor`` and
    ``kmajorB`` are the engine's own plans."""
    from go_libp2p_pubsub_tpu.ops import edges

    n, k = perm.shape
    pn, pk = perm // k, perm % k
    head_ok = nbr_ok[:, :k0]
    in_head = head_ok & (pk[:, :k0] < k0)
    own = np.arange(n * k0, dtype=np.int32).reshape(n, k0)
    perm_head = np.where(in_head, pn[:, :k0] * k0 + pk[:, :k0], own)
    pr, pc = np.nonzero(head_ok & ~in_head)        # head slots, tail partner
    tr, tc = np.nonzero(nbr_ok[:, k0:])
    # PR 32's compact table, N-major: head slot (n, k) is row n*K0 + k, the
    # t-th present tail slot (row-major) row N*K0 + t
    addr = np.full((n, k), -1, np.int32)
    addr[:, :k0] = own
    addr[tr, tc + k0] = n * k0 + np.arange(tr.size, dtype=np.int32)
    compact = addr.reshape(-1)[perm]
    return {
        "perm_head": perm_head.astype(np.int32),
        "head_full": perm[:, :k0].astype(np.int32),
        "patch_src": perm[pr, pc].astype(np.int32),
        "patch_dst": (pr * k0 + pc).astype(np.int32),
        "tail_src": perm[tr, tc + k0].astype(np.int32),
        "tail_dst": (tr * (k - k0) + tc).astype(np.int32),
        "compact_head": compact[:, :k0],
        "compact_tail_src": compact[tr, tc + k0],
        **{case + form: plan.replace(cliff=cliff)
           for case, compact in (("kmajor", True), ("kmajorB", False))
           for plan in [edges.plan_tiers(perm, nbr_ok, k0, compact=compact)]
           for form, cliff in (("", 0), ("S", plan.table_rows(k)))},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--d", type=int, default=10)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--k0", type=int, nargs="*", default=[20, 24, 28])
    ap.add_argument("--widths", type=int, nargs="*", default=[2, 5, 14])
    ap.add_argument("--graph", default="random_connect",
                    choices=["random_connect", "subnet_connect"])
    ap.add_argument("--cases", nargs="*", default=None,
                    help="run only these cases (default: all)")
    ap.add_argument("--out", default="chiprun_out/gather_law.json")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from go_libp2p_pubsub_tpu.ops import edges

    dev = jax.devices()[0]
    where = {"platform": dev.platform, "device_kind": dev.device_kind,
             "graph": args.graph}
    n = args.n
    lines = []

    def timed(case, w, rows_out, rows_table, fn, *operands, expect=None,
              **extra):
        if args.cases is not None and case not in args.cases:
            return
        operands = [jnp.asarray(a) for a in operands]
        jit = jax.jit(fn)
        t0 = time.perf_counter()
        got = jax.block_until_ready(jit(*operands))
        first = time.perf_counter() - t0
        if expect is not None:
            extra["equal"] = bool(np.array_equal(np.asarray(got), expect))
        del got
        secs = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(jit(*operands))
            secs.append(time.perf_counter() - t0)
        med = statistics.median(secs)
        line = dict(where, n=n, case=case, w=w, rows_out=int(rows_out),
                    rows_table=int(rows_table), ms_median=1e3 * med,
                    ms_min=1e3 * min(secs),
                    ns_per_row=1e9 * med / max(int(rows_out), 1),
                    first_call_s=first, **extra)
        lines.append(line)
        print(json.dumps(line), flush=True)

    def table(rows, w, seed=0):
        rng = np.random.default_rng(seed)
        return rng.integers(0, 2**32, size=(rows, w), dtype=np.uint32)

    gather = lambda flat, idx: flat[idx.reshape(-1)]

    perm, ok = graph(args.graph, n, args.d, 1)
    k = perm.shape[1]
    fill = ok.sum(axis=0)
    print(json.dumps(dict(where, n=n, k=k, present=int(ok.sum()),
                          col_fill=fill.tolist())), flush=True)
    # graph seed 2 only where a case reads it
    runs = lambda *cases: args.cases is None or set(cases) & set(args.cases)
    perm2, ok2 = (graph(args.graph, n, args.d, 2) if runs("seed2", "pad48")
                  else (perm, ok))
    k2 = perm2.shape[1]
    k_pad = -(-max(k, k2) // 16) * 16
    tiers = {k0: tier_indices(perm, ok, k0) for k0 in args.k0 if k0 < k}

    for w in args.widths:
        full = table(n * k, w)
        timed("full", w, n * k, n * k, gather, full, perm, k=k)
        timed("seed2", w, n * k2, n * k2, gather, table(n * k2, w), perm2,
              k=k2)
        timed("pad48", w, n * k_pad, n * k_pad, gather, table(n * k_pad, w),
              pad_k(perm, ok, k_pad), k=k_pad)
        for k0, t in tiers.items():
            timed("head", w, n * k0, n * k0, gather, table(n * k0, w),
                  t["perm_head"], k0=k0)
            timed("headfull", w, n * k0, n * k, gather, full,
                  t["head_full"], k0=k0)

    # does the price follow the indices? and a baked-in index plane
    w = 5
    full = table(n * k, w)
    rng = np.random.default_rng(7)
    timed("ident", w, n * k, n * k, gather, full,
          np.arange(n * k, dtype=np.int32))
    timed("shuffle", w, n * k, n * k, gather, full,
          rng.permutation(n * k).astype(np.int32))
    baked = jnp.asarray(perm.reshape(-1))
    timed("full_baked", w, n * k, n * k, lambda flat: flat[baked], full, k=k)

    for w in args.widths:
        full = table(n * k, w)
        x = full.reshape(n, k, w)

        def whole(x, idx):
            return x.reshape(n * k, w)[idx.reshape(-1)].reshape(n, k, w)

        want = full[perm.reshape(-1)].reshape(n, k, w)
        timed("whole", w, n * k, n * k, whole, x, perm, k=k, expect=want)
        for k0, t in tiers.items():
            kt = k - k0
            n_t, n_p = t["tail_dst"].size, t["patch_dst"].size
            timed("list", w, n_t, n * k, gather, full, t["tail_src"], k0=k0)
            timed("list", w, n_t + n_p, n * k, gather, full,
                  np.concatenate([t["patch_src"], t["tail_src"]]), k0=k0)

            def scatter(base, dst, vals):
                return base.at[dst].set(vals, unique_indices=True,
                                        indices_are_sorted=True)

            timed("scatter", w, n_t, n * kt, scatter, table(n * kt, w),
                  t["tail_dst"], table(n_t, w, 1), k0=k0)
            timed("scatter", w, n_p, n * k0, scatter, table(n * k0, w),
                  t["patch_dst"], table(n_p, w, 1), k0=k0)

            def tier_a(x, perm_head, src, patch_dst, tail_dst, k0=k0, kt=kt):
                flat = x.reshape(n * k, w)
                moved = flat[src]
                p = patch_dst.shape[0]
                head = x[:, :k0].reshape(n * k0, w)[perm_head.reshape(-1)]
                head = head.at[patch_dst].set(
                    moved[:p], unique_indices=True, indices_are_sorted=True)
                tail = x[:, k0:].reshape(n * kt, w).at[tail_dst].set(
                    moved[p:], unique_indices=True, indices_are_sorted=True)
                return jnp.concatenate(
                    [head.reshape(n, k0, w), tail.reshape(n, kt, w)], axis=1)

            def tier_b(x, head_full, tail_src, tail_dst, k0=k0, kt=kt):
                flat = x.reshape(n * k, w)
                head = flat[head_full.reshape(-1)]
                tail = x[:, k0:].reshape(n * kt, w).at[tail_dst].set(
                    flat[tail_src], unique_indices=True,
                    indices_are_sorted=True)
                return jnp.concatenate(
                    [head.reshape(n, k0, w), tail.reshape(n, kt, w)], axis=1)

            def compact(x, head, tail_src, tail_dst, k0=k0, kt=kt):
                tail = x[:, k0:].reshape(n * kt, w)
                table = jnp.concatenate(
                    [x[:, :k0].reshape(n * k0, w), tail[tail_dst]])
                moved = table[jnp.concatenate([head.reshape(-1), tail_src])]
                tail = tail.at[tail_dst].set(
                    moved[n * k0:], unique_indices=True,
                    indices_are_sorted=True)
                return jnp.concatenate(
                    [moved[:n * k0].reshape(n, k0, w),
                     tail.reshape(n, kt, w)], axis=1)

            rows_a = n * k0 + 2 * (n_t + n_p)
            rows_b = n * k0 + 2 * n_t
            timed("tierA", w, rows_a, n * k, tier_a, x, t["perm_head"],
                  np.concatenate([t["patch_src"], t["tail_src"]]),
                  t["patch_dst"], t["tail_dst"], k0=k0, tail=n_t, patch=n_p,
                  expect=want)
            timed("tierB", w, rows_b, n * k, tier_b, x, t["head_full"],
                  t["tail_src"], t["tail_dst"], k0=k0, tail=n_t, expect=want)
            timed("compact", w, n * k0 + 3 * n_t, n * k0 + n_t, compact, x,
                  t["compact_head"], t["compact_tail_src"], t["tail_dst"],
                  k0=k0, tail=n_t, expect=want)
            for case in ("kmajor", "kmajorB", "kmajorS", "kmajorBS"):
                plan = t[case]
                slices = len(edges.word_slices(plan.table_rows(k), w,
                                               plan.cliff))
                if case.endswith("S") and slices == 1:
                    continue

                def kmajor(x, head, tail_src, tail_dst, plan=plan):
                    return edges.edge_permute_tiered(x, plan.replace(
                        head=head, tail_src=tail_src, tail_dst=tail_dst))

                timed(case, w, plan.rows, plan.table_rows(k), kmajor, x,
                      plan.head, plan.tail_src, plan.tail_dst, k0=k0,
                      tail=n_t, slices=slices, expect=want)

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
