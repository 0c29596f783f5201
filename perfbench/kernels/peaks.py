"""Published peaks per chip, keyed by ``device_kind`` as JAX reports it.

Copied from ``kubetpu/utils/flops.DEVICE_PEAKS`` (the original is listed
for deletion in PERF.md, Open questions).  A device that is not in the
table is an error, never a default.
"""

from __future__ import annotations

from typing import Dict, NamedTuple


class Peak(NamedTuple):
    flops_per_s: float      # matmul peak, bf16 inputs, f32 accumulate
    bytes_per_s: float      # HBM
    source: str


DEVICE_PEAKS: Dict[str, Peak] = {
    "TPU v5 lite": Peak(
        197e12, 819e9,
        'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
        "16 GB HBM per chip at 819 GB/s"),
}


def peak(device_kind: str) -> Peak:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}; the table "
            f"has {sorted(DEVICE_PEAKS)}") from None
