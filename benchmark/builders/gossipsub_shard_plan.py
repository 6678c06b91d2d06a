"""Builder kind ``gossipsub_shard_plan``: ``gossipsub_phase``'s build, called
unchanged (through ``gossipsub_sharded``'s guard), for a configuration whose
graph has no locality peer-sharded over several chips, on a program that
plans its tiered edge gather per shard.

Split over chips, a tiered gather through the whole table's index planes
leaves the partitioner two ways, both of which do one chip's gather on
every chip: it gathers the table onto each chip, or it gathers on each chip
what one chip gathers. A program without the per-shard plan
(``ops/edges.plan_shards``, its exchange scoped ``gsx.exchange``) may
compile for minutes, run out of memory or run slowly there: it cannot run
the cell, and this builder refuses it before anything is built.
"""

from __future__ import annotations

import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def build(config: dict, seed: int, devices, n_peers: int | None = None,
          control: dict | None = None):
    """``gossipsub_sharded.build`` on ``devices``, once the program is
    known to plan its gather per shard."""
    from go_libp2p_pubsub_tpu.ops import edges
    from go_libp2p_pubsub_tpu.perf import stages

    from benchmark.harness import manifest as mf

    if (not hasattr(edges, "plan_shards")
            or "exchange" not in getattr(stages, "PARTS", ())):
        raise RuntimeError(
            f"{config['name']} is peer-sharded over a graph with no "
            "locality, and this program has no per-shard plan of its "
            "tiered edge gather (ops/edges.plan_shards, perf.stages part "
            "'exchange'): this program cannot run it")
    sharded = mf.load_plugin("builders", "gossipsub_sharded", ROOT)
    return sharded.build(config, seed, devices, n_peers=n_peers,
                         control=control)
