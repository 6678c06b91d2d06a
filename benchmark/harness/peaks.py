"""Peaks of the chips the benchmark knows, keyed by ``device_kind`` as
JAX reports it. A device that is not in the table is an error, not a
default."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 16 GB of HBM at 819 GB/s per
    # chip. The step has no matrix product, so no FLOP/s peak is kept
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str, key: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks recorded for device kind {device_kind!r}; add it to "
            "benchmark/harness/peaks.py with its source")
    return PEAKS[device_kind][key]
