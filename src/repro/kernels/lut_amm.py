"""Fused LUT-AMM Pallas TPU kernel family: encode + table read + accumulate.

TPU adaptation of the paper's section-5 inference design (DESIGN.md §2):

  * closest-centroid search  -> MXU dot(a_blk, P^T) per codebook block, with
    the codebook block pinned in VMEM across the whole N sweep
    (centroid-stationary: the BlockSpec index_map for `P` ignores the N grid
    coordinate, so the pipeline emitter keeps the same tile resident).
  * argmin                   -> VPU lane reduction (no sequential RAW hazard)
  * shuffle-instruction read -> one-hot x table matmul on the MXU
  * INT16/INT32 mixed accum  -> int8 one-hot x int8 table with int32
    accumulation (v2, DESIGN.md §2.3)

Grid = (N/bn, M/bm, C/bc) with the codebook axis innermost.

Two generations are kept side by side for benchmarking:

  `lut_amm_pallas` (v2, default) — int8-native: the table tile enters the MXU
  as int8 (`preferred_element_type=jnp.int32`), partial sums accumulate in a
  VMEM scratch buffer across codebook steps, and the output tile is written
  exactly once on the final step through a fused dequantize + bias +
  activation epilogue. With the m-shared (1,1,M) scale layout the whole tile
  is dequantized exactly once; per-codebook scale layouts rescale the int32
  partials per step into an fp32 scratch but still never materialize an fp32
  table (DESIGN.md §2.3).

  `lut_amm_pallas_v1` — the original kernel: dequantizes the int8 table to
  fp32 in VMEM on every codebook step, contracts in fp32 and read-modify-
  writes the output tile across the innermost grid axis.

Block sizes default to the shape-keyed autotuner (repro.kernels.autotune);
the VMEM budget model for legal tilings is documented in DESIGN.md §3.1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune

ACTIVATIONS = ("none", "relu", "silu", "gelu", "relu2")


def _apply_act(acc: jax.Array, act: str) -> jax.Array:
    if act == "none":
        return acc
    if act == "relu":
        return jnp.maximum(acc, 0.0)
    if act == "silu":
        return jax.nn.silu(acc)
    if act == "gelu":
        return jax.nn.gelu(acc)
    if act == "relu2":
        r = jnp.maximum(acc, 0.0)
        return r * r
    raise ValueError(f"unknown epilogue activation {act!r}")


def _encode_onehot_i8(x_ref, p_ref) -> jax.Array:
    """Distance + argmin + int8 one-hot for one (bn, bc) tile -> (bc, bn, K)."""
    a = x_ref[...].astype(jnp.float32)          # (bn, bc, V)
    p = p_ref[...].astype(jnp.float32)          # (bc, K, V)
    # squared distances: batch over codebooks on the MXU
    # (bc, bn, K) <- (bn, bc, V) x (bc, K, V)
    cross = jax.lax.dot_general(
        a, p,
        dimension_numbers=(((2,), (2,)), ((1,), (0,))),
        precision=jax.lax.Precision.HIGHEST,     # fp32 codes, as core.pq
        preferred_element_type=jnp.float32,
    )
    # scores = squared distance less the per-row ||a||^2 (core.pq)
    p_nrm = jnp.sum(p * p, axis=-1)[:, None, :]          # (bc, 1, K)
    # this operand order: `p_nrm - 2.0 * cross` takes ~5x the VMEM
    # temporaries on the v5e compiler
    dists = cross * -2.0 + p_nrm                         # (bc, bn, K)
    # vectorized argmin over the K lane axis, then one-hot re-expansion —
    # int8 so the table read below runs on the int8 MXU path.
    idx = jnp.argmin(dists, axis=-1)                     # (bc, bn)
    lanes = jax.lax.broadcasted_iota(jnp.int32, dists.shape, 2)
    return (lanes == idx[:, :, None]).astype(jnp.int8)   # (bc, bn, K)


# ---------------------------------------------------------------------------
# v2 kernel (int8-native, scratch accumulation, fused epilogue)
# ---------------------------------------------------------------------------

def _lut_amm_kernel_v2(
    *refs,
    n_c_blocks: int,
    shared_scale: bool,
    has_bias: bool,
    act: str,
):
    if has_bias:
        x_ref, p_ref, t_ref, s_ref, b_ref, o_ref, acc_ref = refs
    else:
        x_ref, p_ref, t_ref, s_ref, o_ref, acc_ref = refs
        b_ref = None
    c_step = pl.program_id(2)

    onehot = _encode_onehot_i8(x_ref, p_ref)             # (bc, bn, K) int8

    # int8 x int8 -> int32 table read on the MXU; the table tile never
    # leaves int8 (v1 materialized an fp32 copy here every step).
    # (bc, bn, bm) <- (bc, bn, K) x (bc, K, bm)
    part = jax.lax.dot_general(
        onehot, t_ref[...],
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        # exact integer MXU product; pinned so a global
        # jax_default_matmul_precision cannot ask for fp32
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.int32,
    )

    if shared_scale:
        # (1,1,M) scales factor out of the codebook sum: accumulate raw
        # int32 counts, dequantize ONCE per output tile in the epilogue.
        contrib = jnp.sum(part, axis=0)                  # (bn, bm) int32
    else:
        # per-codebook scales: rescale the int32 partials of this step, but
        # the accumulator stays in scratch and o_ref is still written once.
        s = s_ref[...].astype(jnp.float32)               # (bc, 1, 1|bm)
        contrib = jnp.sum(part.astype(jnp.float32) * s, axis=0)

    @pl.when(c_step == 0)
    def _init():
        acc_ref[...] = contrib

    @pl.when(c_step != 0)
    def _accum():
        acc_ref[...] += contrib

    @pl.when(c_step == n_c_blocks - 1)
    def _epilogue():
        acc = acc_ref[...]
        if shared_scale:
            # the single dequantize of this output tile
            acc = acc.astype(jnp.float32) * s_ref[...].reshape(1, -1)
        if has_bias:
            acc = acc + b_ref[...].astype(jnp.float32)
        o_ref[...] = _apply_act(acc, act)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_n", "block_m", "block_c", "act", "interpret",
    ),
)
def _lut_amm_call_v2(
    x_sub, centroids, table_q, scale, bias,
    *, block_n, block_m, block_c, act, interpret,
):
    np_, c, v = x_sub.shape
    k = centroids.shape[1]
    mp_ = table_q.shape[-1]
    bn, bm, bc = block_n, block_m, block_c
    grid = (np_ // bn, mp_ // bm, c // bc)
    shared_scale = scale.shape[0] == 1
    s_m = 1 if scale.shape[-1] == 1 else bm

    if shared_scale:
        s_spec = pl.BlockSpec(
            (1, 1, s_m),
            (lambda i, j, cc: (0, 0, j)) if s_m != 1 else (lambda i, j, cc: (0, 0, 0)),
        )
    else:
        s_spec = pl.BlockSpec(
            (bc, 1, s_m),
            (lambda i, j, cc: (cc, 0, j)) if s_m != 1 else (lambda i, j, cc: (cc, 0, 0)),
        )
    in_specs = [
        pl.BlockSpec((bn, bc, v), lambda i, j, cc: (i, cc, 0)),
        pl.BlockSpec((bc, k, v), lambda i, j, cc: (cc, 0, 0)),
        pl.BlockSpec((bc, k, bm), lambda i, j, cc: (cc, 0, j)),
        s_spec,
    ]
    operands = [x_sub, centroids.astype(jnp.float32), table_q, scale]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bm), lambda i, j, cc: (0, j)))
        operands.append(bias.reshape(1, -1))

    acc_dtype = jnp.int32 if shared_scale else jnp.float32
    return pl.pallas_call(
        functools.partial(
            _lut_amm_kernel_v2,
            n_c_blocks=grid[2],
            shared_scale=shared_scale,
            has_bias=bias is not None,
            act=act,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, bm), lambda i, j, cc: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, mp_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bn, bm), acc_dtype)],
        interpret=interpret,
        name="lut_amm_v2",
    )(*operands)


def lut_amm_pallas(
    x: jax.Array,          # (N, D)
    centroids: jax.Array,  # (C, K, V) fp32
    table_q: jax.Array,    # (C, K, M) int8
    scale: jax.Array,      # (C|1, 1, 1) or (C|1, 1, M) fp32
    *,
    bias: jax.Array | None = None,   # (M,) fused into the epilogue
    act: str = "none",               # fused epilogue activation
    block_n: int | None = None,
    block_m: int | None = None,
    block_c: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """v2 fused LUT-AMM: (N, D) -> (N, M). See module docstring."""
    n, d = x.shape
    c, k, v = centroids.shape
    m = table_q.shape[-1]
    if d != c * v:
        raise ValueError(f"D={d} != C*V={c}*{v}")
    if act not in ACTIVATIONS:
        raise ValueError(f"act={act!r} not in {ACTIVATIONS}")

    bn, bm, bc = autotune.resolve_blocks(
        "lut_amm", n, m, c, k, v, str(x.dtype), block_n, block_m, block_c
    )

    # pad N / M to block multiples (table M padding is cheap: int8 zeros)
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

    out = _lut_amm_call_v2(
        xp.reshape(np_, c, v), centroids, tp, sp, bp,
        block_n=bn, block_m=bm, block_c=bc, act=act, interpret=interpret,
    )
    return out[:n, :m].astype(x.dtype)


# ---------------------------------------------------------------------------
# v1 kernel (kept for dense-vs-v1-vs-v2 benchmarking)
# ---------------------------------------------------------------------------

def _lut_amm_kernel_v1(x_ref, p_ref, t_ref, s_ref, o_ref):
    c_step = pl.program_id(2)

    onehot = _encode_onehot_i8(x_ref, p_ref).astype(jnp.float32)

    # fp32 dequantized table materialized EVERY codebook step (v2 fixes this)
    table = t_ref[...].astype(jnp.float32) * s_ref[...].astype(jnp.float32)
    # (bc, bn, bm) <- (bc, bn, K) x (bc, K, bm)
    part = jax.lax.dot_general(
        onehot, table,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    acc = jnp.sum(part, axis=0)                          # (bn, bm)

    @pl.when(c_step == 0)
    def _init():
        o_ref[...] = acc

    @pl.when(c_step != 0)
    def _accum():
        o_ref[...] += acc


@functools.partial(
    jax.jit,
    static_argnames=("block_n", "block_m", "block_c", "interpret"),
)
def lut_amm_pallas_v1(
    x: jax.Array,          # (N, D)
    centroids: jax.Array,  # (C, K, V) fp32
    table_q: jax.Array,    # (C, K, M) int8
    scale: jax.Array,      # (C, 1, 1) or (C, 1, M) fp32
    *,
    block_n: int = 256,
    block_m: int = 512,
    block_c: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    n, d = x.shape
    c, k, v = centroids.shape
    m = table_q.shape[-1]
    if d != c * v:
        raise ValueError(f"D={d} != C*V={c}*{v}")

    bn = min(block_n, n)
    bm = min(block_m, m)
    bc = block_c if block_c is not None else max(1, min(c, 2048 // v))
    while c % bc:
        bc -= 1

    pad_n, pad_m = (-n) % bn, (-m) % bm
    xp = jnp.pad(x, ((0, pad_n), (0, 0))) if pad_n else x
    tp = jnp.pad(table_q, ((0, 0), (0, 0), (0, pad_m))) if pad_m else table_q
    sp = (
        jnp.pad(scale, ((0, 0), (0, 0), (0, pad_m)))
        if (pad_m and scale.shape[-1] != 1)
        else scale
    )
    np_, mp_ = n + pad_n, m + pad_m

    x_sub = xp.reshape(np_, c, v)
    grid = (np_ // bn, mp_ // bm, c // bc)
    s_m = 1 if scale.shape[-1] == 1 else bm

    out = pl.pallas_call(
        _lut_amm_kernel_v1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bc, v), lambda i, j, cc: (i, cc, 0)),
            pl.BlockSpec((bc, k, v), lambda i, j, cc: (cc, 0, 0)),
            pl.BlockSpec((bc, k, bm), lambda i, j, cc: (cc, 0, j)),
            pl.BlockSpec(
                (bc, 1, s_m),
                (lambda i, j, cc: (cc, 0, j)) if s_m != 1 else (lambda i, j, cc: (cc, 0, 0)),
            ),
        ],
        out_specs=pl.BlockSpec((bn, bm), lambda i, j, cc: (i, j)),
        out_shape=jax.ShapeDtypeStruct((np_, mp_), jnp.float32),
        interpret=interpret,
        name="lut_amm_v1",
    )(x_sub, centroids.astype(jnp.float32), tp, sp)

    return out[:n, :m].astype(x.dtype)
