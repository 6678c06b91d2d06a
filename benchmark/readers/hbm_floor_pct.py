"""Roofline share of the whole phase step, bound by bytes: the least
time a phase can take (the carried state read once at the chip's peak
bandwidth; ``harness/costs.py``) over the device-busy time per phase."""

from benchmark.harness import costs, peaks


def read(run: dict):
    tr = run.get("trace")
    if not tr or not run.get("rounds") or tr["busy_s"] <= 0:
        return None
    phases = run["rounds"] / run["rounds_per_phase"]
    floor = costs.phase_floor_seconds(
        costs.tree_bytes(run["state_shapes"]),
        peaks.peak(run["device_kind"], "hbm_bytes_per_s"))
    return 100.0 * floor / (tr["busy_s"] / phases)
