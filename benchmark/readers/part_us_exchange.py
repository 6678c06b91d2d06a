"""Device self time of the per-shard plan's exchange (``gsx.exchange``: the
gather that packs the rows the other shards read, the permutes that carry
them, and their landing after each shard's own table), in microseconds per
delivery round, the mean over the device planes (``harness/parts.py``). Its
ops are part of ``stage_us_edge_gather`` too. ``None`` on one chip, where
the program gives no collective map, and on a commit without the part."""

from benchmark.harness import halo, parts


def read(run: dict):
    if halo.sharded_window(run) is None:
        return None
    return parts.part_us_per_round(run, "exchange")
