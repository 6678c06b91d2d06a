"""``benchmark/tools/traced.py`` (same arguments), then what the program
counted while it traced the window: the rows its edge gathers address a
step call and the rows of the table they read (``perf.stages``'s
``edge_rows_per_dispatch`` / ``edge_table_rows``; ``null`` on a commit
without the counter). One line on stderr a window."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from benchmark.tools import traced
    from go_libp2p_pubsub_tpu.perf import stages

    rc = traced.main(argv)
    for w in stages.traced_windows():
        print(json.dumps({"window": w.module_name, **{
            name: getattr(w, name, None)
            for name in ("edge_rows_per_dispatch", "edge_table_rows")}}),
            file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
