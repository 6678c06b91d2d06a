"""The exchange's share of its roofline, in %: the bytes one chip sends in
the window's collectives per delivery round (``harness/halo.py``: the
per-shard plan's permutes, which carry all but a few bytes of them, and
GSPMD's reductions) over what the chip's inter-chip interconnect moves
(``halo_ici_peaks.json``) in the collectives' device time per round.
``None`` on one chip, where the program gives no collective map, and on a
device the peaks file does not hold."""

from benchmark.harness import halo


def read(run: dict):
    window = halo.sharded_window(run)
    seconds = halo.collective_seconds(run)
    if window is None or not seconds or not run.get("rounds_per_phase"):
        return None
    peak = halo.ici_peak(run.get("device_kind", ""))
    sent = halo.bytes_per_dispatch(window, len(run["device_trace"]["devices"]))
    if peak is None or sent is None:
        return None
    return 100.0 * (sent / run["rounds_per_phase"]) / (
        peak * seconds / run["rounds"])
