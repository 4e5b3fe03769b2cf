"""Functional LUT-NN approximate-matmul layer (the paper's core operator).

One entry point, `lut_linear`, with three statically-selected modes:

  DENSE      — exact x @ W (+bias): the original operator / accuracy baseline.
  LUT_TRAIN  — soft-PQ QAT forward (paper section 3): table rebuilt from the
               frozen weight each step, fake-quantized (section 3.3), encoding
               via the argmin/softmax straight-through estimator (Eq. 6) with
               the learned temperature (section 3.2).
  LUT_INFER  — deployed path: int8 table + hard argmin encode + one-hot MXU
               contraction (or the fused Pallas kernel on TPU).

Param pytrees (see repro.core.lut_layer for initializers):

  dense   : {"w": (D, M) [, "b": (M,)]}
  train   : {"centroids": (C,K,V), "log_t": ()} (+ frozen {"w", "b"})
  deploy  : {"centroids": (C,K,V), "table_q": int8 (C,K,M),
             "table_scale": (C,1,1)|(C,1,M) [, "b": (M,)]}
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Any, Mapping

import jax
import jax.numpy as jnp

from repro.core import pq, quant
from repro.core.temperature import temperature


class Mode(str, enum.Enum):
    DENSE = "dense"
    LUT_TRAIN = "lut_train"
    LUT_INFER = "lut_infer"


@dataclasses.dataclass(frozen=True)
class LUTConfig:
    """Static LUT hyper-parameters for one layer family.

    k: centroids per codebook (paper default 16 — one SIMD register there,
       one-hot lane group on the MXU here).
    v: sub-vector length (paper: 9 for 3x3 conv, 4 for 1x1, 16/32 for BERT FC;
       we default 32 for LM projections).
    bits/per_column: table scalar quantization (section 3.3).
    """

    k: int = 16
    v: int = 32
    bits: int = 8
    per_column: bool = False
    # deployed-path integer contraction: int8 one-hot x int8 table -> int32
    # with (1,1,M) scales (DESIGN.md section 2). Halves+ the decode memory
    # term by never materializing a dequantized bf16 table.
    int8_dot: bool = False
    # Pallas fused v2 kernel for LUT_INFER (int8-native MXU table read +
    # fused bias epilogue, autotuned blocks — DESIGN.md §2.3/§3); False =
    # pure-XLA one-hot path, which is what the multi-pod dry-run lowers
    # (CPU backend can't emit Mosaic).
    use_kernel: bool = False

    def codebooks(self, d: int) -> int:
        if d % self.v:
            raise ValueError(f"D={d} not divisible by V={self.v}")
        return d // self.v


def _flatten_lead(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


@jax.named_scope("linear")
def lut_linear(
    cfg: LUTConfig,
    mode: Mode,
    params: Mapping[str, Any],
    x: jax.Array,
    *,
    frozen: Mapping[str, Any] | None = None,
) -> jax.Array:
    """Apply one (possibly LUT-replaced) linear layer. x: (..., D) -> (..., M).

    Every op it emits, in every mode, sits under the named scope `linear`,
    by which the profiler's trace attributes device time."""
    if mode == Mode.DENSE:
        w = params["w"]
        y = jnp.einsum("...d,dm->...m", x, w.astype(x.dtype))
        b = params.get("b")
        return y + b.astype(y.dtype) if b is not None else y

    if mode == Mode.LUT_TRAIN:
        assert frozen is not None, "LUT_TRAIN needs the frozen dense weight"
        P = params["centroids"]
        t = temperature(params["log_t"])
        table = pq.build_table(P, frozen["w"], stop_weight_grad=True)
        table = quant.fake_quant(
            table, bits=cfg.bits, per_column=cfg.per_column, m_shared=cfg.int8_dot
        )
        xf, lead = _flatten_lead(x)
        scores = pq.encode_scores(pq.split_subvectors(xf, cfg.v), P)
        enc = pq.ste_encode(scores, t)
        y = pq.lut_contract(enc.astype(x.dtype), table.astype(x.dtype))
        b = frozen.get("b")
        y = y + b.astype(y.dtype) if b is not None else y
        return y.reshape(*lead, -1).astype(x.dtype)

    if mode == Mode.LUT_INFER:
        P = params["centroids"]
        qt = quant.QuantizedTable(params["table_q"], params["table_scale"])
        xf, lead = _flatten_lead(x)
        b = params.get("b")
        if cfg.use_kernel:
            from repro.kernels import ops  # local import: kernels are optional

            # bias rides the kernel's fused epilogue (DESIGN.md §2.3) — no
            # separate elementwise pass over the (N, M) output. The kernel
            # generation (v1 / v2 / fused-decode) is NOT pinned here:
            # ops.lut_amm consults the per-shape autotune record — measured
            # wall-clock winners when available (DESIGN.md §13.3) — so every
            # LUT site runs whichever kernel actually wins on its shape.
            y = ops.lut_amm(xf, P, qt.q, qt.scale, bias=b)
        else:
            scores = pq.encode_scores(pq.split_subvectors(xf, cfg.v), P)
            if cfg.int8_dot:
                y = pq.lut_contract_int8(pq.hard_encode(scores), qt.q, qt.scale)
            else:
                table = qt.dequant(dtype=x.dtype)
                enc = pq.hard_encode(scores).astype(x.dtype)
                y = pq.lut_contract(enc, table)
            y = y + b.astype(y.dtype) if b is not None else y
        return y.reshape(*lead, -1).astype(x.dtype)

    raise ValueError(f"unknown mode {mode}")


def lut_flops(n: int, d: int, m: int, cfg: LUTConfig) -> int:
    """Paper Table 1: N*D*K (encode) + N*M*D/V (lookup-accumulate)."""
    return n * d * cfg.k + n * m * d // cfg.v


def dense_flops(n: int, d: int, m: int) -> int:
    return n * d * m


def lut_table_bytes(d: int, m: int, cfg: LUTConfig) -> int:
    """int8 table + fp32 scales + fp32 codebook bytes (paper Table 1 size)."""
    c = d // cfg.v
    table = c * cfg.k * m                       # int8
    scales = c * 4 * (m if cfg.per_column else 1)
    codebook = c * cfg.k * cfg.v * 4
    return table + scales + codebook


def dense_bytes(d: int, m: int, dtype_bytes: int = 4) -> int:
    return d * m * dtype_bytes
