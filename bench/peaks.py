"""Published peaks of each chip the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A chip that is not here is an error.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flop_per_s": 197e12,
        "int8_op_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def roofline_share(time_s: float, nbytes: float, ops: float,
                   device_kind: str, op_peak: str = "int8_op_per_s"
                   ) -> float:
    """Percent of the chip's roofline: the least time the chip could take
    for these bytes and operations, over the time measured."""
    p = peaks(device_kind)
    least = max(nbytes / p["hbm_bytes_per_s"], ops / p[op_peak])
    return 100.0 * least / time_s
