"""Published peaks of the chips the benchmark may run on, keyed by jax's
``device_kind``. A device that is not here is an error, not a default."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s of inter-chip links
_V5E = {
    "bf16_flops": 197e12,
    "int8_ops": 393e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "ici_bits_per_s": 1600e9,
    "source": "cloud.google.com/tpu/docs/v5e",
}
# jax calls the chip "TPU v5 lite"
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add a "
            f"row with its source to benchmarks/harness/peaks.py") from None
