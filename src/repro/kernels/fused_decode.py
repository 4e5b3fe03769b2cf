"""Fused encode→lookup decode kernel, v3 (DESIGN.md §13).

The v1/v2 kernels tile a (N/bn, M/bm, C/bc) grid with the codebook axis
innermost — correct, but the dist-argmin encode is recomputed for EVERY
M block (the encode matmul is charged M/bm times), and the alternative
two-pass path (`encode_pallas` then a table read) round-trips the codes
through HBM between kernels. This kernel does neither:

  * grid = (N/bn, M/bm), M innermost. The whole codebook axis is
    VMEM-resident (BlockSpec index maps for x and the centroids ignore the
    M coordinate), so the per-step working set is bounded by the VMEM
    budget model's kind="fused" branch (repro.kernels.autotune).
  * the encode — squared-distance argmin per codebook subvector — runs
    exactly once per N tile, under `pl.when(m_step == 0)`, and writes the
    int8 one-hot codes into a VMEM scratch buffer. Scratch persists across
    grid steps, so every M step of the sweep reuses the same codes. The
    codes never have an output ref: they cannot touch HBM.
  * the int8 table tile's index map depends on the innermost grid axis, so
    the pipeline emitter double-buffers its DMA: while the MXU contracts
    M-tile j, tile j+1 streams in. Decode at batch = n_slots therefore
    stays table-bandwidth/MXU-bound instead of latency-bound on encode
    recomputation.
  * dequant + bias + activation ride the single write of each output tile
    (each (bn, bm) tile is visited exactly once — no accumulator, no
    read-modify-write).

Scale layouts (repro.core.quant):

  m-shared (1,1,M) / scalar (1,1,1) — the scale factors out of the codebook
  sum: codes are kept in (bn, C·K) layout and contracted against the
  (C·K, bm) int8 table in ONE int8 MXU matmul with int32 accumulation
  (exact integer arithmetic — byte-identical to the two-pass reference),
  dequantized once per output tile.

  per-codebook (C,1,1) / per-column (C,1,M) — the scale cannot factor out:
  codes are kept in (C, bn, K) layout and contracted in C-chunks sized to
  bound the (chunk, bn, bm) int32 partial, each chunk rescaled in fp32
  before the sum.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune
from repro.kernels.lut_amm import ACTIVATIONS, _apply_act


def _fused_decode_kernel(
    *refs,
    shared_scale: bool,
    has_bias: bool,
    act: str,
    chunk_c: int,
):
    if has_bias:
        x_ref, p_ref, t_ref, s_ref, b_ref, o_ref, code_ref = refs
    else:
        x_ref, p_ref, t_ref, s_ref, o_ref, code_ref = refs
        b_ref = None
    m_step = pl.program_id(1)

    # ---- encode: once per N tile, codes pinned in VMEM for the M sweep ----
    @pl.when(m_step == 0)
    def _encode():
        a = x_ref[...].astype(jnp.float32)               # (bn, C, V)
        p = p_ref[...].astype(jnp.float32)               # (C, K, V)
        cross = jax.lax.dot_general(
            a, p,
            dimension_numbers=(((2,), (2,)), ((1,), (0,))),
            precision=jax.lax.Precision.HIGHEST,         # fp32 codes, as core.pq
            preferred_element_type=jnp.float32,
        )                                                # (C, bn, K)
        # scores = squared distance less the per-row ||a||^2 (core.pq)
        p_nrm = jnp.sum(p * p, axis=-1)[:, None, :]      # (C, 1, K)
        # this operand order: `p_nrm - 2.0 * cross` takes ~5x the VMEM
        # temporaries on the v5e compiler
        dists = cross * -2.0 + p_nrm                     # (C, bn, K)
        idx = jnp.argmin(dists, axis=-1)                 # (C, bn)
        if shared_scale:
            # (bn, C, K) layout: reshapes to the (bn, C·K) single-matmul form
            shape = (idx.shape[1], idx.shape[0], dists.shape[-1])
            lanes = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
            code_ref[...] = (lanes == idx.T[:, :, None]).astype(jnp.int8)
        else:
            # (C, bn, K) layout: feeds the per-codebook chunked contraction
            lanes = jax.lax.broadcasted_iota(jnp.int32, dists.shape, 2)
            code_ref[...] = (lanes == idx[:, :, None]).astype(jnp.int8)

    # ---- lookup: int8 codes x int8 table tile, per M step ----
    codes = code_ref[...]
    t = t_ref[...]                                       # (C, K, bm) int8
    if shared_scale:
        bn_, c_, k_ = codes.shape
        acc32 = jax.lax.dot_general(
            codes.reshape(bn_, c_ * k_), t.reshape(c_ * k_, t.shape[-1]),
            dimension_numbers=(((1,), (0,)), ((), ())),
            # exact integer MXU product; pinned so a global
            # jax_default_matmul_precision cannot ask for fp32
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.int32,
        )                                                # (bn, bm) exact
        acc = acc32.astype(jnp.float32) * s_ref[...].reshape(1, -1)
    else:
        c_, bn_, _ = codes.shape
        s = s_ref[...].astype(jnp.float32)               # (C, 1, 1|bm)
        acc = jnp.zeros((bn_, t.shape[-1]), jnp.float32)
        for c0 in range(0, c_, chunk_c):
            c1 = min(c_, c0 + chunk_c)
            part = jax.lax.dot_general(
                codes[c0:c1], t[c0:c1],
                dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                # exact integer MXU product; pinned so a global
                # jax_default_matmul_precision cannot ask for fp32
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.int32,
            )                                            # (cc, bn, bm)
            acc = acc + jnp.sum(part.astype(jnp.float32) * s[c0:c1], axis=0)

    if has_bias:
        acc = acc + b_ref[...].astype(jnp.float32)
    o_ref[...] = _apply_act(acc, act)


@functools.partial(
    jax.jit,
    static_argnames=("block_n", "block_m", "act", "interpret"),
)
def _fused_decode_call(
    x_sub, centroids, table_q, scale, bias,
    *, block_n, block_m, act, interpret,
):
    np_, c, v = x_sub.shape
    k = centroids.shape[1]
    mp_ = table_q.shape[-1]
    bn, bm = block_n, block_m
    grid = (np_ // bn, mp_ // bm)
    shared_scale = scale.shape[0] == 1
    s_m = 1 if scale.shape[-1] == 1 else bm
    s_c = 1 if shared_scale else c
    # bound the (chunk, bn, bm) int32 partial of the non-shared path to ~2 MB
    chunk_c = max(1, min(c, (1 << 21) // max(1, 4 * bn * bm)))

    s_spec = pl.BlockSpec(
        (s_c, 1, s_m),
        (lambda i, j: (0, 0, j)) if s_m != 1 else (lambda i, j: (0, 0, 0)),
    )
    in_specs = [
        pl.BlockSpec((bn, c, v), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((c, k, v), lambda i, j: (0, 0, 0)),
        pl.BlockSpec((c, k, bm), lambda i, j: (0, 0, j)),
        s_spec,
    ]
    operands = [x_sub, centroids.astype(jnp.float32), table_q, scale]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bm), lambda i, j: (0, j)))
        operands.append(bias.reshape(1, -1))

    code_shape = (bn, c, k) if shared_scale else (c, bn, k)
    return pl.pallas_call(
        functools.partial(
            _fused_decode_kernel,
            shared_scale=shared_scale,
            has_bias=bias is not None,
            act=act,
            chunk_c=chunk_c,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, bm), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, mp_), jnp.float32),
        scratch_shapes=[pltpu.VMEM(code_shape, jnp.int8)],
        interpret=interpret,
        name="lut_fused_decode",
    )(*operands)


def fused_decode_pallas(
    x: jax.Array,          # (N, D)
    centroids: jax.Array,  # (C, K, V) fp32
    table_q: jax.Array,    # (C, K, M) int8
    scale: jax.Array,      # (C|1, 1, 1) or (C|1, 1, M) fp32
    *,
    bias: jax.Array | None = None,   # (M,) fused into the epilogue
    act: str = "none",               # fused epilogue activation
    block_n: int | None = None,
    block_m: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused encode→lookup decode (v3): (N, D) -> (N, M). See module docstring.

    There is no block_c: the codebook axis is entirely VMEM-resident (that is
    the point — `autotune.kernel_choice` only routes here when it fits)."""
    n, d = x.shape
    c, k, v = centroids.shape
    m = table_q.shape[-1]
    if d != c * v:
        raise ValueError(f"D={d} != C*V={c}*{v}")
    if act not in ACTIVATIONS:
        raise ValueError(f"act={act!r} not in {ACTIVATIONS}")

    if block_n is None or block_m is None:
        h = autotune.heuristic("fused", n, m, c, k, v)
        block_n = block_n if block_n is not None else h.block_n
        block_m = block_m if block_m is not None else h.block_m
    bn = max(1, min(block_n, n))
    bm = max(1, min(block_m, m))

    pad_n, pad_m = (-n) % bn, (-m) % bm
    xp = jnp.pad(x, ((0, pad_n), (0, 0))) if pad_n else x
    tp = jnp.pad(table_q, ((0, 0), (0, 0), (0, pad_m))) if pad_m else table_q
    sp = (
        jnp.pad(scale, ((0, 0), (0, 0), (0, pad_m)))
        if (pad_m and scale.shape[-1] != 1)
        else scale
    )
    bp = None
    if bias is not None:
        bp = jnp.pad(bias, (0, pad_m)) if pad_m else bias
    np_ = n + pad_n

    out = _fused_decode_call(
        xp.reshape(np_, c, v), centroids, tp, sp, bp,
        block_n=bn, block_m=bm, act=act, interpret=interpret,
    )
    return out[:n, :m].astype(x.dtype)
