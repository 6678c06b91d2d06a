"""Host time per segment to draw the seeded schedule and put it on the
device: the harness span ``xs_assembly``, median over the segments."""

from benchmark.harness import stats


def read(run: dict):
    spans = run["spans"].get("xs_assembly")
    return 1e3 * stats.median(spans) if spans else None
