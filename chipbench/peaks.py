"""Published peaks of each accelerator the benchmark may run on, keyed by the
``device_kind`` string JAX reports.

A device that is not in this table is an error, never a default: the
roofline and utilization metrics divide by these numbers.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
    # 197 TFLOP/s bf16, 394 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud documentation, TPU v5e",
    },
}

