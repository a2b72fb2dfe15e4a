"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s in bf16,
819 GB/s of HBM bandwidth, 16 GB of HBM per chip.  A device kind that is
not in the table is an error, never a default.
"""

from __future__ import annotations

DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}
