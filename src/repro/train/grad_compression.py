"""int8 error-feedback gradient compression for the data-parallel reduce.

Classic 2-phase compressed all-reduce (1-bit Adam family, here 8-bit):

  1. quantize local grads to int8 with a per-tensor fp32 scale, carrying the
     quantization residual into the next step (error feedback preserves
     convergence),
  2. all_to_all int8 chunks across the data axis (wire: 1 byte/elem),
  3. local dequant + fp32 mean of the received chunks,
  4. re-quantize the reduced chunk, all_gather int8 (wire: 1 byte/elem).

Wire bytes: 2 x 1B/elem vs 2 x 2B/elem for a bf16 ring all-reduce -> 2x
collective-term reduction (4x vs fp32 grads).

`compressed_mean_tree` is the inside-shard_map primitive;
`make_compressed_grad_fn` builds the full data-parallel gradient step
(shard_map over the dp axis: local grads -> compressed mean), which is the
trainer's opt-in replacement for GSPMD's implicit bf16 all-reduce.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def _quant(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def compressed_mean_1d(vec: jax.Array, *, axis: str, n: int) -> jax.Array:
    """Mean over the mesh axis of a flat fp32 vector (len divisible by n).

    Must be called inside shard_map; `vec` differs per shard. Both wire
    phases move int8.
    """
    chunks = vec.reshape(n, -1)
    q, s = _quant(chunks)                                    # s: per-device scalar
    # row p of recv = peer p's chunk destined for my slot
    recv = jax.lax.all_to_all(q, axis, split_axis=0, concat_axis=0, tiled=True)
    scales = jax.lax.all_gather(s, axis, tiled=False)        # (n,) per-peer scales
    local_sum = jnp.einsum("nc,n->c", recv.astype(jnp.float32), scales) / n
    q2, s2 = _quant(local_sum)
    all_q = jax.lax.all_gather(q2, axis, tiled=False)        # (n, chunk) int8 wire
    all_s = jax.lax.all_gather(s2, axis, tiled=False)
    return (all_q.astype(jnp.float32) * all_s.reshape(n, 1)).reshape(-1)


def compressed_mean_tree(grads: Any, *, axis: str, n: int) -> Any:
    flat, treedef = jax.tree_util.tree_flatten(grads)
    sizes = [x.size for x in flat]
    vec = jnp.concatenate([x.astype(jnp.float32).reshape(-1) for x in flat])
    pad = (-vec.size) % n
    out = compressed_mean_1d(jnp.pad(vec, (0, pad)), axis=axis, n=n)[: vec.size]
    outs, off = [], 0
    for x, sz in zip(flat, sizes):
        outs.append(out[off : off + sz].reshape(x.shape).astype(x.dtype))
        off += sz
    return jax.tree_util.tree_unflatten(treedef, outs)


def residual_correct(grads: Any, residual: Any) -> tuple[Any, Any]:
    """Error feedback: add carried residual; return (corrected, new_residual)."""
    corrected = jax.tree.map(
        lambda g, r: g.astype(jnp.float32) + r, grads, residual
    )

    def res(c):
        q, s = _quant(c)
        return c - q.astype(jnp.float32) * s

    new_residual = jax.tree.map(res, corrected)
    return corrected, new_residual


def init_residual(params: Any) -> Any:
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def make_compressed_grad_fn(
    loss_fn: Callable[[Any, Any], jax.Array],
    mesh: Mesh,
    *,
    axis: str = "data",
) -> Callable[[Any, Any, Any], tuple[jax.Array, Any, Any]]:
    """Data-parallel value_and_grad with int8 compressed reduce.

    Returns step(params, residual, batch) -> (mean loss, mean grads,
    new residual). params replicated; batch sharded on dim0 over `axis`.
    """
    n = mesh.shape[axis]

    def local(params, residual, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        corrected, new_residual = residual_correct(grads, residual)
        reduced = compressed_mean_tree(corrected, axis=axis, n=n)
        loss = jax.lax.pmean(loss, axis)
        return loss, reduced, new_residual

    batch_spec = P(axis)
    rep = P()
    return jax.shard_map(
        local, mesh=mesh, check_vma=False,
        in_specs=(rep, rep, batch_spec), out_specs=(rep, rep, rep),
    )
