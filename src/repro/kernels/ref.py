"""Pure-jnp oracle for the fused LUT-AMM kernel.

Semantics contract (kernels/lut_amm.py must match bit-for-bit at fp32):
  1. centroid scores ||P||^2 - 2 a.P in fp32 (the squared distance less the
     per-row ||a||^2: same argmin, no fp32 ties at large ||a||)
  2. argmin with lowest-index tie-breaking (jnp.argmin)
  3. table dequantized int8 * scale in fp32
  4. one-hot contraction accumulated in fp32, cast to x.dtype at the end
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def lut_amm_ref(
    x: jax.Array,          # (N, D)
    centroids: jax.Array,  # (C, K, V)
    table_q: jax.Array,    # (C, K, M) int8
    scale: jax.Array,      # (C, 1, 1) or (C, 1, M) fp32
) -> jax.Array:            # (N, M) in x.dtype
    n, d = x.shape
    c, k, v = centroids.shape
    assert d == c * v, (d, c, v)
    a = x.reshape(n, c, v).astype(jnp.float32)
    p = centroids.astype(jnp.float32)
    cross = jnp.einsum("ncv,ckv->nck", a, p,
                       precision=jax.lax.Precision.HIGHEST)
    dists = jnp.sum(p * p, -1)[None, :, :] - 2.0 * cross
    idx = jnp.argmin(dists, -1)                                   # (N, C)
    onehot = jax.nn.one_hot(idx, k, dtype=jnp.float32)            # (N, C, K)
    table = table_q.astype(jnp.float32) * scale.astype(jnp.float32)
    out = jnp.einsum("nck,ckm->nm", onehot, table)
    return out.astype(x.dtype)


def encode_ref(x: jax.Array, centroids: jax.Array) -> jax.Array:
    """(N, D), (C, K, V) -> int32 (N, C) nearest-centroid indices."""
    n, d = x.shape
    c, k, v = centroids.shape
    a = x.reshape(n, c, v).astype(jnp.float32)
    p = centroids.astype(jnp.float32)
    cross = jnp.einsum("ncv,ckv->nck", a, p,
                       precision=jax.lax.Precision.HIGHEST)
    dists = jnp.sum(p * p, -1)[None, :, :] - 2.0 * cross
    return jnp.argmin(dists, -1).astype(jnp.int32)
