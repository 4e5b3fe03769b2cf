"""Zamba2-style hybrid: Mamba2 backbone + one *shared* attention block.

The shared transformer block (attention + MLP, one set of weights) is
invoked every `attn_every` mamba layers; its input is a learned fusion of
the current hidden state with the original embeddings (concat -> linear),
and its output is projected back into the residual stream — following
Zamba2 (arXiv:2411.15242). Each invocation has its own KV cache but reuses
the same weights, so in LUT mode the block's tables are amortized across
all invocations (DESIGN.md section 4).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import mamba2 as mamba_mod
from repro.models import mlp as mlp_mod
from repro.models.common import (
    Params,
    SiteCfg,
    embed,
    embed_init,
    linear,
    linear_init,
    rmsnorm,
    rmsnorm_init,
)
from repro.models.transformer import BlockCfg, block_init, block_apply


@dataclasses.dataclass(frozen=True)
class HybridCfg:
    vocab: int
    d_model: int
    n_layers: int                     # mamba layers
    attn_every: int                   # shared block before layers k, 2k, ...
    mamba_block: BlockCfg             # kind == "mamba"
    shared_attn: attn_mod.AttnCfg
    shared_mlp: mlp_mod.MLPCfg
    fuse: SiteCfg                     # 2*d_model -> d_model (dense)
    out: SiteCfg                      # d_model -> d_model
    remat: bool = True
    unroll: bool = False              # python-loop layers (activation capture)

    @property
    def invocation_points(self) -> tuple[int, ...]:
        return tuple(range(self.attn_every, self.n_layers + 1, self.attn_every))

    @property
    def segment_bounds(self) -> tuple[tuple[int, int], ...]:
        pts = (0, *self.invocation_points)
        segs = [(pts[i], pts[i + 1]) for i in range(len(pts) - 1)]
        if pts[-1] < self.n_layers:
            segs.append((pts[-1], self.n_layers))
        return tuple(segs)


def hybrid_init(key: jax.Array, cfg: HybridCfg, *, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 8)
    layer_keys = jax.random.split(ks[0], cfg.n_layers)
    mamba_stack = jax.vmap(lambda k: block_init(k, cfg.mamba_block, dtype=dtype))(layer_keys)
    return {
        "embed": embed_init(ks[1], cfg.vocab, cfg.d_model, dtype),
        "mamba_stack": mamba_stack,
        "shared": {
            "fuse": linear_init(ks[2], cfg.fuse, dtype=dtype),
            "norm1": rmsnorm_init(cfg.d_model, dtype),
            "attn": attn_mod.attn_init(ks[3], cfg.shared_attn, dtype=dtype),
            "norm2": rmsnorm_init(cfg.d_model, dtype),
            "mlp": mlp_mod.mlp_init(ks[4], cfg.shared_mlp, dtype=dtype),
            "out": linear_init(ks[5], cfg.out, dtype=dtype),
        },
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }


def hybrid_caches(cfg: HybridCfg, b: int, s_max: int, dtype=jnp.bfloat16, abstract: bool = False,
                  paged: attn_mod.PagedSpec | None = None):
    n_inv = len(cfg.invocation_points)
    if abstract:
        one_m = mamba_mod.mamba2_cache_specs(b, cfg.mamba_block.mamba, dtype)
        mstack = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((cfg.n_layers, *s.shape), s.dtype), one_m
        )
        one_a = (attn_mod.paged_cache_specs(paged, cfg.shared_attn, dtype) if paged is not None
                 else attn_mod.cache_specs(b, s_max, cfg.shared_attn, dtype))
        astack = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n_inv, *s.shape), s.dtype), one_a
        )
    else:
        one_m = mamba_mod.mamba2_init_cache(b, cfg.mamba_block.mamba, dtype)
        mstack = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (cfg.n_layers, *a.shape)).copy(), one_m
        )
        one_a = (attn_mod.paged_init_cache(paged, cfg.shared_attn, dtype) if paged is not None
                 else attn_mod.init_cache(b, s_max, cfg.shared_attn, dtype))
        astack = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n_inv, *a.shape)).copy(), one_a
        )
    return {"mamba": mstack, "attn": astack}


def _shared_block(
    cfg: HybridCfg, p: Params, x: jax.Array, x0: jax.Array, *,
    pos, cache, cache_len, block_tables=None, write_len=None, slot_mask=None,
) -> tuple[jax.Array, Params | None]:
    h = linear(cfg.fuse, p["fuse"], jnp.concatenate([x, x0], axis=-1))
    a, new_cache = attn_mod.attention(
        cfg.shared_attn, p["attn"], rmsnorm(p["norm1"], h),
        pos=pos, cache=cache, cache_len=cache_len,
        block_tables=block_tables, write_len=write_len, slot_mask=slot_mask,
    )
    h = h + a
    h = h + mlp_mod.mlp(cfg.shared_mlp, p["mlp"], rmsnorm(p["norm2"], h))
    return x + linear(cfg.out, p["out"], h), new_cache


def hybrid_apply(
    cfg: HybridCfg,
    params: Params,
    *,
    tokens: jax.Array,
    pos: jax.Array,
    caches: Params | None = None,
    cache_len: jax.Array | None = None,
    compute_dtype=jnp.float32,
    block_tables: jax.Array | None = None,
    write_len: jax.Array | None = None,
    slot_mask: jax.Array | None = None,
) -> tuple[jax.Array, Params | None, jax.Array]:
    x = embed(params["embed"], tokens).astype(compute_dtype)
    x0 = x

    def mamba_seg(x, lo, hi, cstack):
        if cfg.unroll:
            # eager layer loop so the conversion tape sees concrete arrays,
            # keyed by the registry's mamba_stack/<layer> prefixes
            from repro.models.common import set_tape_prefix

            new_c = [] if cstack is not None else None
            for j in range(hi - lo):
                set_tape_prefix(f"mamba_stack/{lo + j}")
                pl_ = jax.tree.map(lambda a: a[lo + j], params["mamba_stack"])
                cl_ = None if cstack is None else jax.tree.map(lambda a: a[lo + j], cstack)
                x, nc, _ = block_apply(cfg.mamba_block, pl_, x, pos=pos, cache=cl_,
                                       slot_mask=slot_mask)
                if cstack is not None:
                    new_c.append(nc)
            if cstack is not None:
                new_c = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *new_c)
            return x, new_c

        seg_p = jax.tree.map(lambda a: a[lo:hi], params["mamba_stack"])

        def body(xc, layer_in):
            if cstack is None:
                y, _, _ = block_apply(cfg.mamba_block, layer_in, xc, pos=pos)
                return y, None
            pl_, cl_ = layer_in
            y, nc, _ = block_apply(cfg.mamba_block, pl_, xc, pos=pos, cache=cl_,
                                   slot_mask=slot_mask)
            return y, nc

        fn = jax.checkpoint(body) if (cfg.remat and cstack is None) else body
        xs = seg_p if cstack is None else (seg_p, jax.tree.map(lambda a: a[lo:hi], cstack))
        return jax.lax.scan(fn, x, xs)

    new_m, new_a = [], []
    inv = 0
    for lo, hi in cfg.segment_bounds:
        x, nc = mamba_seg(x, lo, hi, None if caches is None else caches["mamba"])
        if caches is not None:
            new_m.append(nc)
        if hi in cfg.invocation_points:
            from repro.models.common import set_tape_prefix

            # the shared block is weight-shared across invocation points:
            # one registry path, activations pooled across invocations
            set_tape_prefix("shared")
            a_cache = (
                None if caches is None
                else jax.tree.map(lambda a: a[inv], caches["attn"])
            )
            x, nac = _shared_block(
                cfg, params["shared"], x, x0,
                pos=pos, cache=a_cache, cache_len=cache_len,
                block_tables=block_tables, write_len=write_len, slot_mask=slot_mask,
            )
            if caches is not None:
                new_a.append(nac)
            inv += 1

    x = rmsnorm(params["final_norm"], x)
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"]["table"].astype(x.dtype))
    new_caches = None
    if caches is not None:
        mstack = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *new_m)
        astack = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *new_a)
        new_caches = {"mamba": mstack, "attn": astack}
    return logits, new_caches, jnp.zeros((), jnp.float32)
