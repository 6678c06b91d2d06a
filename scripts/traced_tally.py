"""``benchmark/tools/traced.py`` (same arguments), then what the program
counted while it traced the window: the rows its edge gathers address a
step call, the rows and tile-rows of the table they read, the calls a
step that crossed in column slices or by the explicit halo exchange and the
rows its peer gathers address (``perf.stages``'s ``edge_rows_per_dispatch``
/ ``edge_table_rows`` / ``edge_table_tile_rows`` /
``edge_sliced_calls_per_dispatch`` / ``halo_exchanges_per_dispatch`` /
``peer_rows_per_dispatch`` / ``remote_rows_per_dispatch``; ``null`` on a
commit without the counter), and
the edge gathers of the window as this machine's compiler built it, with
the memory space of each one's table, indices and output
(``window_whiles.edge_gathers``), and before them how many of at least Np
rows read their table from HBM (``edge_tables_hbm_per_dispatch``,
``window_whiles.edge_tables_hbm``: 0 where every big gather of a one-phase
window has its table in the fast space), and the collective profile of
the compiled (on four chips: partitioned) window with what one step call
runs and sends (``TracedWindow.collectives_per_dispatch`` /
``collective_bytes_per_dispatch``). One line on stderr a
window."""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "scripts")]


def main(argv=None) -> int:
    import jax

    import window_whiles
    from benchmark.harness import manifest as mf
    from benchmark.tools import traced
    from go_libp2p_pubsub_tpu.parallel import sharding
    from go_libp2p_pubsub_tpu.perf import stages

    rc = traced.main(argv)
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    manifest = mf.load_manifest(ROOT)
    cell = mf.find_cell(manifest, ap.parse_known_args(argv)[0].workload)
    n_peers = mf.load_config(manifest, cell["config"], ROOT)["n_peers"]
    for w in stages.traced_windows():
        args, kwargs = jax.tree_util.tree_unflatten(*w.signature)
        text = w.jitted.lower(*args, **kwargs).compile().as_text()
        gathers = window_whiles.edge_gathers(text)
        print(json.dumps({"window": w.module_name, **{
            name: getattr(w, name, None)
            for name in ("edge_rows_per_dispatch", "edge_table_rows",
                         "edge_table_tile_rows",
                         "edge_sliced_calls_per_dispatch",
                         "halo_exchanges_per_dispatch",
                         "peer_rows_per_dispatch",
                         "remote_rows_per_dispatch")},
            "edge_tables_hbm_per_dispatch":
                window_whiles.edge_tables_hbm(gathers, n_peers),
            "edge_gathers": gathers,
            "collectives": sharding.collective_profile(text),
            "collectives_per_dispatch": w.collectives_per_dispatch(),
            "collective_bytes_per_dispatch":
                w.collective_bytes_per_dispatch()}),
            file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
