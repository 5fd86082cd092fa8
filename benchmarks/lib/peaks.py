"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip. JAX
calls that chip "TPU v5 lite". PR 21 measured a 4096^3 bf16 matmul chain at
about 96% of the bf16 figure on this chip, so the row is the right one.

Copied from ``dtf_tpu.telemetry.accounting.DEVICE_PEAKS`` so that no later
change to the program moves the yardstick.
"""

from __future__ import annotations

DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    """The table row of ``device_kind``. A device that is not in the table
    is an error, never a default: a utilization against a guessed peak is
    not a measurement."""
    if device_kind not in DEVICE_PEAKS:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}: the "
            f"benchmark measures only on {sorted(DEVICE_PEAKS)}")
    return DEVICE_PEAKS[device_kind]
