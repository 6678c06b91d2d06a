"""Host time of the call of the scanned window until it returns (the
enqueue, not the completion): the harness span ``dispatch``, median over
the segments."""

from benchmark.harness import stats


def read(run: dict):
    spans = run["spans"].get("dispatch")
    return 1e3 * stats.median(spans) if spans else None
