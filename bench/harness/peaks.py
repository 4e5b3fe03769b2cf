"""Published peaks of the chips the benchmark runs on, keyed by JAX's
`device_kind`. A kind that is not here is an error, not a default.

TPU v5e: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s. Float32
flops are counted against the bf16 peak: a float32 product at HIGHEST
precision runs in several bf16 passes on the same units, so the peak still
bounds it.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float          # floating-point operations per second
    int8_ops: float       # int8 operations per second
    hbm_bytes: float      # HBM bytes per second
    hbm_capacity: float   # bytes


CHIP_PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, int8_ops=393e12, hbm_bytes=819e9,
                         hbm_capacity=16e9),
}


def peaks(kind: str) -> Peaks:
    if kind not in CHIP_PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}: add them "
                       f"to bench/harness/peaks.py with their source")
    return CHIP_PEAKS[kind]
