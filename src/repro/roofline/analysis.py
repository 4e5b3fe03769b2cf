"""Three-term roofline analysis from compiled XLA artifacts (no hardware).

  compute    = HLO_FLOPs_per_device / peak_FLOP/s
  memory     = HLO_bytes_per_device / HBM_bw
  collective = wire_bytes_per_device / ICI_bw

`cost_analysis()` on a compiled executable is already per-device (post-SPMD
partitioning — verified empirically: a 16-device sharded matmul reports
1/16th of the global FLOPs). Collective wire bytes are parsed from the
optimized per-device HLO with the standard ring cost model:

  all-reduce        2 x input bytes   (reduce-scatter + all-gather phases)
  all-gather        (n-1)/n x output  ~ output bytes
  reduce-scatter    (n-1)/n x input   ~ input bytes
  all-to-all        (n-1)/n x input   ~ input bytes
  collective-permute  input bytes

Hardware constants come from `chip_peaks`, one table keyed by the device
kind JAX reports. Collectives are charged one ICI link (multi-link overlap
is an upside not claimed here).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks."""

    bf16_flops: float        # dense MXU FLOP/s at bf16
    int8_ops: float          # MXU OP/s at int8
    hbm_bw: float            # HBM bytes/s
    ici_bw: float            # bytes/s of ONE inter-chip link


# Keyed by `jax.Device.device_kind`. Source: Google Cloud documentation,
# "TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s
# int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI over 4 links (50 GB/s each).
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, int8_ops=393e12,
                             hbm_bw=819e9, ici_bw=50e9),
}
# the chip the analytic models describe when no TPU is attached
MODEL_KIND = "TPU v5 lite"


def chip_peaks(kind: str | None = None) -> ChipPeaks:
    """Peaks of `kind`; by default of the attached TPU, or of `MODEL_KIND`
    when the default device is not a TPU (CPU tests, interpret mode).

    A TPU kind missing from `CHIP_PEAKS` is an error: a model of another
    chip would mis-rank kernels and mis-state roofline shares there."""
    if kind is None:
        import jax

        dev = jax.devices()[0]
        kind = dev.device_kind if dev.platform == "tpu" else MODEL_KIND
    if kind not in CHIP_PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}: add it "
                       f"to CHIP_PEAKS (repro.roofline.analysis) with its source")
    return CHIP_PEAKS[kind]

_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1,
    "s4": 0.5, "u4": 0.5,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
    re.M,
)


def _shape_bytes(shape_str: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Wire bytes per device by collective kind, from optimized HLO text."""
    out: dict[str, float] = {}
    seen_done = set()
    for m in _COLL_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        line = hlo_text[m.start() : hlo_text.find("\n", m.start())]
        if "-done(" in line:
            continue  # async pair: count the -start only
        b = _shape_bytes(shape_str)
        if kind == "all-reduce":
            b *= 2.0
        out[kind] = out.get(kind, 0.0) + b
    return out


@dataclasses.dataclass
class Roofline:
    flops: float                  # per-device HLO flops
    hbm_bytes: float              # per-device bytes accessed
    coll_bytes: float             # per-device wire bytes
    coll_by_kind: dict[str, float]
    peaks: ChipPeaks = CHIP_PEAKS[MODEL_KIND]

    @property
    def peak_flops(self) -> float:
        return self.peaks.bf16_flops

    @property
    def hbm_bw(self) -> float:
        return self.peaks.hbm_bw

    @property
    def ici_bw(self) -> float:
        return self.peaks.ici_bw

    @property
    def t_compute(self) -> float:
        return self.flops / self.peak_flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / self.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / self.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def as_dict(self) -> dict[str, Any]:
        return {
            "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.hbm_bytes,
            "collective_bytes_per_device": self.coll_bytes,
            "collective_by_kind": self.coll_by_kind,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
        }


def analyze_compiled(compiled) -> Roofline:
    """Trip-count-aware roofline terms from the optimized per-device HLO.

    NOTE: `compiled.cost_analysis()` counts while bodies once (verified:
    a scan of 8 matmuls reports the flops of 1), so all terms here come
    from repro.roofline.hlo_cost, which multiplies loop bodies by XLA's
    known_trip_count annotation. cost_analysis values are kept only as a
    cross-check lower bound.
    """
    from repro.roofline.hlo_cost import analyze_hlo_text

    cost = analyze_hlo_text(compiled.as_text())
    return Roofline(
        flops=cost.flops,
        hbm_bytes=cost.bytes,
        coll_bytes=cost.coll_bytes,
        coll_by_kind=dict(cost.coll),
        peaks=chip_peaks(),
    )


def model_flops_train(n_params: int, tokens: int) -> float:
    """Dense-equivalent useful FLOPs: 6 * N * D (fwd 2ND + bwd 4ND)."""
    return 6.0 * n_params * tokens


def model_flops_decode(n_params: int, tokens: int) -> float:
    return 2.0 * n_params * tokens


def memory_stats(compiled) -> dict[str, float]:
    ma = compiled.memory_analysis()
    keys = (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    )
    out = {k: float(getattr(ma, k, 0)) for k in keys}
    out["total_hbm_bytes"] = (
        out["argument_size_in_bytes"]
        + out["output_size_in_bytes"]
        + out["temp_size_in_bytes"]
        - out["alias_size_in_bytes"]
    )
    return out
