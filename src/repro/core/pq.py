"""Product-quantization math for LUT-NN (paper Eqs. 1-6).

All functions are pure and jit-friendly. Shape conventions:

  a      : (N, D)        input activations (rows of A)
  P      : (C, K, V)     centroids / codebooks, C = D // V
  W      : (D, M)        dense weight being replaced
  T      : (C, K, M)     lookup table, T[c] = P[c] @ W[c*V:(c+1)*V, :]  (Eq. 3)
  dists  : (N, C, K)     squared Euclidean distances per codebook
  enc    : (N, C, K)     encoding (one-hot for hard, probabilities for soft)

Distances are always computed in fp32 for numerical robustness; the AMM
contraction runs in the activation dtype (bf16 on TPU) with fp32 accumulation.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def split_subvectors(a: jax.Array, V: int) -> jax.Array:
    """(..., D) -> (..., C, V) with C = D // V. D must be divisible by V."""
    *lead, D = a.shape
    if D % V:
        raise ValueError(f"feature dim {D} not divisible by sub-vector length {V}")
    return a.reshape(*lead, D // V, V)


def encode_scores(a_sub: jax.Array, P: jax.Array) -> jax.Array:
    """Centroid scores ||P||^2 - 2 a.P: the squared distance less ||a||^2.

    a_sub: (N, C, V), P: (C, K, V) -> (N, C, K) in fp32. ||a||^2 is the same
    for every centroid of a codebook, so the argmin (hard encode) and the
    softmax over -scores/t (soft encode) equal those of the distances. The
    encoders use these, not the distances: at activations of norm ~10^2 the
    ||a||^2 term rounds the distances in fp32 to steps coarser than the gaps
    between centroids, and two fp32 implementations (the TPU kernels, XLA on
    the CPU) then break the resulting ties differently.
    """
    a32 = a_sub.astype(jnp.float32)
    p32 = P.astype(jnp.float32)
    # (N, C, K) <- contract V;  batched over codebook axis C. HIGHEST: the
    # TPU's default is one bf16 pass, whose rounding flips argmin codes.
    cross = jnp.einsum("ncv,ckv->nck", a32, p32,
                       precision=jax.lax.Precision.HIGHEST)
    p_nrm = jnp.sum(p32 * p32, axis=-1)[None, :, :]          # (1, C, K)
    return p_nrm - 2.0 * cross


def pairwise_sq_dists(a_sub: jax.Array, P: jax.Array) -> jax.Array:
    """Squared Euclidean distances between sub-vectors and centroids.

    a_sub: (N, C, V), P: (C, K, V) -> (N, C, K), computed in fp32 via the
    ||a||^2 - 2 a.P + ||P||^2 expansion so the inner term maps onto the MXU.
    """
    a32 = a_sub.astype(jnp.float32)
    a_nrm = jnp.sum(a32 * a32, axis=-1)[:, :, None]          # (N, C, 1)
    return a_nrm + encode_scores(a_sub, P)


def hard_encode(dists: jax.Array) -> jax.Array:
    """onehot(argmin) encoding, Eq. 2/4.  (N, C, K) -> (N, C, K) in dists dtype."""
    K = dists.shape[-1]
    idx = jnp.argmin(dists, axis=-1)
    return jax.nn.one_hot(idx, K, dtype=dists.dtype)


def soft_encode(dists: jax.Array, t: jax.Array) -> jax.Array:
    """softmax(-dists / t), Eq. 5.  t > 0 is the (learned) temperature."""
    return jax.nn.softmax(-dists / t, axis=-1)


def ste_encode(dists: jax.Array, t: jax.Array) -> jax.Array:
    """Soft-PQ straight-through encoding, Eq. 6.

    Forward value  = hard one-hot (what inference uses).
    Backward value = softmax gradient (differentiable w.r.t. dists and t).
    """
    soft = soft_encode(dists, t)
    hard = hard_encode(dists)
    return soft + jax.lax.stop_gradient(hard - soft)


def build_table(P: jax.Array, W: jax.Array, *, stop_weight_grad: bool = True) -> jax.Array:
    """Lookup-table construction h^c(b^c) (Eq. 3):  T[c] = P[c] @ W_c.

    P: (C, K, V), W: (D, M) with D = C*V -> T: (C, K, M).
    The replaced weight is frozen during soft-PQ training (paper trains
    centroids + temperature only), so gradients through W are stopped.
    """
    C, K, V = P.shape
    D, M = W.shape
    if D != C * V:
        raise ValueError(f"weight rows {D} != C*V = {C}*{V}")
    w = jax.lax.stop_gradient(W) if stop_weight_grad else W
    w_sub = w.reshape(C, V, M)
    return jnp.einsum("ckv,cvm->ckm", P.astype(w.dtype), w_sub)


def lut_contract(enc: jax.Array, T: jax.Array) -> jax.Array:
    """AMM read+accumulate (Eq. 4): sum_c enc[n,c,:] . T[c,:,m] -> (N, M).

    enc (N, C, K) is one-hot (inference) or a probability vector (soft path).
    On TPU this is a single (N, C*K) x (C*K, M) matmul: the MXU *is* the
    parallel table-lookup unit (see DESIGN.md section 2). Accumulate fp32.
    """
    N = enc.shape[0]
    C, K, M = T.shape
    out = jax.lax.dot_general(
        enc.reshape(N, C * K),
        T.reshape(C * K, M),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return out


def lut_contract_int8(
    enc_hard: jax.Array,   # (N, C, K) one-hot (any float dtype; cast to int8)
    table_q: jax.Array,    # (C, K, M) int8
    scale_m: jax.Array,    # (1, 1, M) fp32 — m_shared quantization layout
) -> jax.Array:
    """Integer table read: int8 one-hot x int8 table -> int32, one fp32
    rescale per output column.

    This is the paper's section-5.2 mixed-precision accumulation adapted to
    the MXU: the table streams from HBM ONCE as int8 (no bf16
    dequant-materialization pass, which costs 5x the table bytes on the
    naive path: read int8 + write bf16 + read bf16). Requires the
    m_shared=(1,1,M) scale layout so the rescale factors out of the
    (C*K)-contraction; the one-hot "values" are exactly +-1 so int8 carries
    them losslessly and the int32 accumulator bounds |sum| <= C*127.
    """
    n = enc_hard.shape[0]
    c, k, m = table_q.shape
    acc = jax.lax.dot_general(
        enc_hard.reshape(n, c * k).astype(jnp.int8),
        table_q.reshape(c * k, m),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return acc.astype(jnp.float32) * scale_m.reshape(1, m)


def encode_indices(a: jax.Array, P: jax.Array) -> jax.Array:
    """Inference-side encoder g^c: (N, D) -> int32 (N, C) centroid indices."""
    a_sub = split_subvectors(a, P.shape[-1])
    return jnp.argmin(encode_scores(a_sub, P), axis=-1).astype(jnp.int32)


def gather_lut(idx: jax.Array, T: jax.Array) -> jax.Array:
    """Reference gather-based table read: (N, C) idx, (C, K, M) -> (N, M).

    The dynamic-gather formulation of Eq. 4 (what the CPU shuffle instruction
    does). Kept as an oracle / alternative path; the deployed TPU path is the
    one-hot matmul in :func:`lut_contract`.
    """
    # T[c, idx[n, c], :] summed over c.
    idx_cn = idx.T[:, :, None].astype(jnp.int32)            # (C, N, 1)
    gathered = jnp.take_along_axis(T, idx_cn, axis=1)       # (C, N, M)
    return jnp.sum(gathered, axis=0)


def pq_reconstruct(a: jax.Array, P: jax.Array) -> jax.Array:
    """Quantize-dequantize a through its nearest centroids (analysis util)."""
    a_sub = split_subvectors(a, P.shape[-1])
    enc = hard_encode(encode_scores(a_sub, P))              # (N, C, K)
    rec = jnp.einsum("nck,ckv->ncv", enc.astype(P.dtype), P)
    return rec.reshape(a.shape)
