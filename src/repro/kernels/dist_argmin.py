"""Encode-only Pallas kernel: closest-centroid search (paper section 5.1).

Returns int32 indices (N, C). Used where the encoding is shared across
several table reads — e.g. MoE layers encode each token once and every
expert's table consumes the same indices (DESIGN.md §4).

The codebook tile is centroid-stationary in VMEM (index_map ignores the N
grid axis), mirroring the paper's cache-resident codebook loop. Block sizes
default to the shape-keyed autotuner (repro.kernels.autotune, DESIGN.md §3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import autotune


def _encode_kernel(x_ref, p_ref, o_ref):
    a = x_ref[...].astype(jnp.float32)          # (bn, bc, V)
    p = p_ref[...].astype(jnp.float32)          # (bc, K, V)
    cross = jax.lax.dot_general(
        a, p, (((2,), (2,)), ((1,), (0,))),
        precision=jax.lax.Precision.HIGHEST,    # fp32 codes, as core.pq
        preferred_element_type=jnp.float32,
    )                                           # (bc, bn, K)
    p_nrm = jnp.sum(p * p, axis=-1)[:, None, :]
    dists = cross * -2.0 + p_nrm       # distance less the per-row ||a||^2
    o_ref[...] = jnp.argmin(dists, axis=-1).astype(jnp.int32).T   # (bn, bc)


@functools.partial(jax.jit, static_argnames=("block_n", "block_c", "interpret"))
def _encode_call(x_sub, centroids, *, block_n, block_c, interpret):
    np_, c, v = x_sub.shape
    k = centroids.shape[1]
    bn, bc = block_n, block_c
    return pl.pallas_call(
        _encode_kernel,
        grid=(np_ // bn, c // bc),
        in_specs=[
            pl.BlockSpec((bn, bc, v), lambda i, cc: (i, cc, 0)),
            pl.BlockSpec((bc, k, v), lambda i, cc: (cc, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bc), lambda i, cc: (i, cc)),
        out_shape=jax.ShapeDtypeStruct((np_, c), jnp.int32),
        interpret=interpret,
    )(x_sub, centroids.astype(jnp.float32))


def encode_pallas(
    x: jax.Array,          # (N, D)
    centroids: jax.Array,  # (C, K, V)
    *,
    block_n: int | None = None,
    block_c: int | None = None,
    interpret: bool = False,
) -> jax.Array:            # (N, C) int32
    n, d = x.shape
    c, k, v = centroids.shape
    bn, _, bc = autotune.resolve_blocks(
        "encode", n, 0, c, k, v, str(x.dtype), block_n, 0, block_c
    )
    pad_n = (-n) % bn
    xp = jnp.pad(x, ((0, pad_n), (0, 0))) if pad_n else x
    np_ = n + pad_n
    out = _encode_call(
        xp.reshape(np_, c, v), centroids,
        block_n=bn, block_c=bc, interpret=interpret,
    )
    return out[:n]
