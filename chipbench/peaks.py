"""Published peaks of each accelerator the benchmark may run on, keyed by
the `device_kind` that JAX reports. A device that is not in the table is an
error, never a default."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture, per chip).
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str, which: str) -> float:
    """The peak `which` ("bf16_flops", "int8_ops", ...) of `device_kind`."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return PEAKS[device_kind][which]
