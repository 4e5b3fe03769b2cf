"""jit'd public wrappers for the LUT kernels with platform + version dispatch.

`lut_amm` runs the compiled Pallas kernels on a TPU and interpret mode
elsewhere (interpret=True executes the kernel body as XLA ops for
correctness tests on the CPU); interpret mode on a TPU is refused
(`resolve_interpret`). The XLA one-hot path in repro.core.pq is the
separate `lut_use_kernel=False` path the distributed dry-run lowers.

Kernel-version selection per shape comes from the autotune record
(`autotune.kernel_choice`, DESIGN.md §13.3) — measured wall-clock winners
when available, the analytic ranking otherwise, and a no-record fallback
rule (v1 for small-M interpret-mode shapes, else the fused v3 kernel when
its working set fits VMEM, else v2) — so callers never pin a losing
version. Pass `version=` (1 | 2 | 3) to force a generation; passing any
explicit block size keeps the historical v2 behavior unless `version` says
otherwise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.dist_argmin import encode_pallas
from repro.kernels.fused_decode import fused_decode_pallas
from repro.kernels.lut_amm import _apply_act, lut_amm_pallas, lut_amm_pallas_v1
from repro.kernels.ref import encode_ref, lut_amm_ref


def resolve_interpret(interpret: bool | None) -> bool:
    """Interpret mode off the TPU, compiled kernels on it: `None` picks by
    backend, and interpret mode on a TPU is refused — a served kernel there
    must be the compiled one."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("interpret=True on a TPU backend: the LUT kernels "
                         "run compiled there")
    return interpret


def lut_amm(
    x: jax.Array,
    centroids: jax.Array,
    table_q: jax.Array,
    scale: jax.Array,
    *,
    bias: jax.Array | None = None,
    act: str = "none",
    block_n: int | None = None,
    block_m: int | None = None,
    block_c: int | None = None,
    version: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """LUT-NN approximate matmul, autotuned dispatch: (N, D) -> (N, M)."""
    interpret = resolve_interpret(interpret)
    n, _ = x.shape
    c, k, v = centroids.shape
    m = table_q.shape[-1]
    if version is None:
        if block_n is None and block_m is None and block_c is None:
            version, cfg, _ = autotune.kernel_choice(
                n, m, c, k, v, dtype=str(x.dtype), interpret=interpret
            )
            block_n, block_m, block_c = cfg.block_n, cfg.block_m, cfg.block_c
        else:
            version = 2        # explicit blocks, no version: historical v2
    if version >= autotune.VERSION_FUSED:
        return fused_decode_pallas(
            x, centroids, table_q, scale, bias=bias, act=act,
            block_n=block_n, block_m=block_m, interpret=interpret,
        )
    if version == 2:
        return lut_amm_pallas(
            x, centroids, table_q, scale, bias=bias, act=act,
            block_n=block_n, block_m=block_m, block_c=block_c,
            interpret=interpret,
        )
    # v1 has no fused epilogue and wants (C, ...) scale layouts: broadcast
    # m-shared scales and apply bias/activation outside the kernel so the
    # three generations stay drop-in interchangeable.
    s = scale if scale.shape[0] == c else jnp.broadcast_to(scale, (c, 1, scale.shape[-1]))
    y = lut_amm_pallas_v1(
        x, centroids, table_q, s,
        block_n=block_n if block_n is not None else 256,
        block_m=block_m if block_m is not None else 512,
        block_c=block_c, interpret=interpret,
    )
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return _apply_act(y, act).astype(y.dtype)


def lut_amm_v1(
    x: jax.Array,
    centroids: jax.Array,
    table_q: jax.Array,
    scale: jax.Array,
    *,
    block_n: int = 256,
    block_m: int = 512,
    block_c: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Original fused kernel (fp32 dequant per step + o_ref accumulation)."""
    interpret = resolve_interpret(interpret)
    return lut_amm_pallas_v1(
        x,
        centroids,
        table_q,
        scale,
        block_n=block_n,
        block_m=block_m,
        block_c=block_c,
        interpret=interpret,
    )


def encode(
    x: jax.Array,
    centroids: jax.Array,
    *,
    block_n: int | None = None,
    block_c: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Closest-centroid encode: (N, D) -> int32 (N, C)."""
    interpret = resolve_interpret(interpret)
    return encode_pallas(
        x, centroids, block_n=block_n, block_c=block_c, interpret=interpret
    )


def lut_amm_fused(
    x: jax.Array,
    centroids: jax.Array,
    table_q: jax.Array,
    scale: jax.Array,
    *,
    bias: jax.Array | None = None,
    act: str = "none",
    block_n: int | None = None,
    block_m: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused encode→lookup decode kernel (v3), explicitly."""
    interpret = resolve_interpret(interpret)
    return fused_decode_pallas(
        x, centroids, table_q, scale, bias=bias, act=act,
        block_n=block_n, block_m=block_m, interpret=interpret,
    )


__all__ = [
    "lut_amm", "lut_amm_v1", "lut_amm_fused", "encode",
    "lut_amm_ref", "encode_ref",
]
