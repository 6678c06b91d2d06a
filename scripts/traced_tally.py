"""``benchmark/tools/traced.py`` (same arguments), then what the program
counted while it traced the window: the rows its edge gathers address a
step call, the rows and tile-rows of the table they read, the calls a
step that crossed in column slices and the rows its peer gathers address
(``perf.stages``'s ``edge_rows_per_dispatch`` / ``edge_table_rows`` /
``edge_table_tile_rows`` / ``edge_sliced_calls_per_dispatch`` /
``peer_rows_per_dispatch``; ``null`` on a commit without the counter), and
the edge gathers of the window as this machine's compiler built it, with
the memory space of each one's table, indices and output
(``window_whiles.edge_gathers``). One line on stderr a window."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "scripts")]


def main(argv=None) -> int:
    import jax

    import window_whiles
    from benchmark.tools import traced
    from go_libp2p_pubsub_tpu.perf import stages

    rc = traced.main(argv)
    for w in stages.traced_windows():
        args, kwargs = jax.tree_util.tree_unflatten(*w.signature)
        text = w.jitted.lower(*args, **kwargs).compile().as_text()
        print(json.dumps({"window": w.module_name, **{
            name: getattr(w, name, None)
            for name in ("edge_rows_per_dispatch", "edge_table_rows",
                         "edge_table_tile_rows",
                         "edge_sliced_calls_per_dispatch",
                         "peer_rows_per_dispatch")},
            "edge_gathers": window_whiles.edge_gathers(text)}),
            file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
