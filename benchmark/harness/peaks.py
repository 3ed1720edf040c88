"""Peak rates of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports. The benchmark's own table: the program's
(obs/device.py) can be moved by LOCALAI_PEAK_* in the environment, and a
yardstick that the environment can move is not one.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s,
1,600 Gbit/s interchip interconnect. JAX reports a v5e chip as "TPU v5 lite".
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bits_s": 1600e9},
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks(device_kind: str) -> dict:
    """A device that is not in the table is an error, not a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"device kind {device_kind!r} has no entry in the benchmark's "
            f"peak table (benchmark/harness/peaks.py); have "
            f"{sorted(PEAKS)}") from None
