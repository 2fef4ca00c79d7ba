"""Published peaks of the chips the benchmark may run on, keyed by
``device_kind`` as JAX reports it. A device that is not listed is an
error, never a default, and nothing here reads the program's own table
or any environment override.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add a "
            f"row to benchmark/lib/peaks.py with its source") from None
