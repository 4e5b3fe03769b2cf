"""Decoder-only LM assembly: blocks, scan-over-layers segments, heads.

Layer stacking uses jax.lax.scan over stacked per-layer params so the HLO
stays O(1) in depth (compile-time critical for the 40-cell dry-run). Layers
are grouped into *segments* of identical block structure; the paper's
"don't replace the first layer" rule (and BERT's "last 6 layers only",
Fig. 13) fall out naturally: segment 0 = 1 dense-mode block, segment 1 =
L-1 LUT-mode blocks.

Covers families: dense (llama3/minitron/qwen3/command-r), moe
(llama4/arctic incl. dense-residual), ssm (mamba2), vlm (qwen2-vl via
embeds input + M-RoPE). Hybrid (zamba2) and enc-dec (whisper) assemble
these same blocks in hybrid.py / encdec.py.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import mamba2 as mamba_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models.common import (
    Params,
    SiteCfg,
    cross_entropy,
    embed,
    embed_init,
    linear,
    linear_init,
    rmsnorm,
    rmsnorm_init,
)


@dataclasses.dataclass(frozen=True)
class BlockCfg:
    kind: str                                  # "dense" | "moe" | "mamba"
    d_model: int
    attn: attn_mod.AttnCfg | None = None
    mlp: mlp_mod.MLPCfg | None = None
    moe: moe_mod.MoECfg | None = None
    mamba: mamba_mod.Mamba2Cfg | None = None
    residual_mlp: mlp_mod.MLPCfg | None = None  # arctic parallel dense branch


def block_init(key: jax.Array, cfg: BlockCfg, *, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    if cfg.kind == "mamba":
        return {
            "norm": rmsnorm_init(cfg.d_model, dtype),
            "mamba": mamba_mod.mamba2_init(ks[0], cfg.mamba, dtype=dtype),
        }
    p: Params = {
        "norm1": rmsnorm_init(cfg.d_model, dtype),
        "norm2": rmsnorm_init(cfg.d_model, dtype),
        "attn": attn_mod.attn_init(ks[0], cfg.attn, dtype=dtype),
    }
    if cfg.kind == "dense":
        p["mlp"] = mlp_mod.mlp_init(ks[1], cfg.mlp, dtype=dtype)
    elif cfg.kind == "moe":
        p["moe"] = moe_mod.moe_init(ks[1], cfg.moe, dtype=dtype)
        if cfg.residual_mlp is not None:
            p["residual_mlp"] = mlp_mod.mlp_init(ks[2], cfg.residual_mlp, dtype=dtype)
    else:
        raise ValueError(cfg.kind)
    return p


def block_cache_specs(cfg: BlockCfg, b: int, s_max: int, dtype=jnp.bfloat16,
                      paged: attn_mod.PagedSpec | None = None) -> Params:
    if cfg.kind == "mamba":
        return mamba_mod.mamba2_cache_specs(b, cfg.mamba, dtype)
    if paged is not None:
        return attn_mod.paged_cache_specs(paged, cfg.attn, dtype)
    return attn_mod.cache_specs(b, s_max, cfg.attn, dtype)


def block_init_cache(cfg: BlockCfg, b: int, s_max: int, dtype=jnp.bfloat16,
                     paged: attn_mod.PagedSpec | None = None) -> Params:
    if cfg.kind == "mamba":
        return mamba_mod.mamba2_init_cache(b, cfg.mamba, dtype)
    if paged is not None:
        return attn_mod.paged_init_cache(paged, cfg.attn, dtype)
    return attn_mod.init_cache(b, s_max, cfg.attn, dtype)


def block_apply(
    cfg: BlockCfg,
    p: Params,
    x: jax.Array,
    *,
    pos: jax.Array,
    cache: Params | None = None,
    cache_len: jax.Array | None = None,
    defer_cache_write: bool = False,
    block_tables: jax.Array | None = None,
    write_len: jax.Array | None = None,
    slot_mask: jax.Array | None = None,
    layer: jax.Array | None = None,
) -> tuple[jax.Array, Params | None, jax.Array]:
    """Returns (x, new_cache, aux_loss).

    Cache rows outside `slot_mask` (when given) come back unchanged;
    `layer` is attention's: its dense cache leaves are layer-stacked."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.kind == "mamba":
        h, new_cache = mamba_mod.mamba2(cfg.mamba, p["mamba"], rmsnorm(p["norm"], x), cache=cache)
        if cache is not None and slot_mask is not None:
            new_cache = jax.tree.map(
                lambda new, old: jnp.where(
                    slot_mask.reshape((-1,) + (1,) * (old.ndim - 1)), new, old),
                new_cache, cache)
        return x + h, new_cache, aux

    a, new_cache = attn_mod.attention(
        cfg.attn, p["attn"], rmsnorm(p["norm1"], x), pos=pos, cache=cache,
        cache_len=cache_len, defer_cache_write=defer_cache_write,
        block_tables=block_tables, write_len=write_len,
        slot_mask=slot_mask, layer=layer,
    )
    x = x + a
    h = rmsnorm(p["norm2"], x)
    if cfg.kind == "dense":
        f = mlp_mod.mlp(cfg.mlp, p["mlp"], h)
    else:
        f, aux = moe_mod.moe(cfg.moe, p["moe"], h)
        if cfg.residual_mlp is not None:
            f = f + mlp_mod.mlp(cfg.residual_mlp, p["residual_mlp"], h)
    return x + f, new_cache, aux


# ---------------------------------------------------------------------------
# full LM
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LMCfg:
    vocab: int
    d_model: int
    segments: tuple[tuple[int, BlockCfg], ...]   # (n_layers, block cfg) runs
    lm_head: SiteCfg | None = None               # None -> tied to embedding
    remat: bool = True
    takes_embeds: bool = False                   # vlm/audio stub frontends
    unroll: bool = False                         # python-loop layers (capture)

    @property
    def n_layers(self) -> int:
        return sum(n for n, _ in self.segments)


def lm_init(key: jax.Array, cfg: LMCfg, *, dtype=jnp.float32) -> Params:
    keys = jax.random.split(key, len(cfg.segments) + 3)
    segs = []
    for i, (count, bcfg) in enumerate(cfg.segments):
        seg_keys = jax.random.split(keys[i], count)
        segs.append(jax.vmap(lambda k: block_init(k, bcfg, dtype=dtype))(seg_keys))
    p: Params = {
        "embed": embed_init(keys[-3], cfg.vocab, cfg.d_model, dtype),
        "segments": segs,
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }
    if cfg.lm_head is not None:
        p["lm_head"] = linear_init(keys[-2], cfg.lm_head, dtype=dtype)
    return p


def init_caches(cfg: LMCfg, b: int, s_max: int, dtype=jnp.bfloat16, abstract: bool = False,
                paged: attn_mod.PagedSpec | None = None) -> list:
    mk = block_cache_specs if abstract else block_init_cache
    out = []
    for count, bcfg in cfg.segments:
        one = mk(bcfg, b, s_max, dtype, paged=paged)
        if abstract:
            stacked = jax.tree.map(
                lambda sds: jax.ShapeDtypeStruct((count, *sds.shape), sds.dtype), one
            )
        else:
            stacked = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (count, *a.shape)).copy(), one)
        out.append(stacked)
    return out


def _seg_apply(
    bcfg: BlockCfg,
    seg_params: Params,
    x: jax.Array,
    *,
    pos: jax.Array,
    caches: Params | None,
    cache_len: jax.Array | None,
    remat: bool,
    unroll: bool = False,
    prefix: str = "",
    block_tables: jax.Array | None = None,
    write_len: jax.Array | None = None,
    slot_mask: jax.Array | None = None,
) -> tuple[jax.Array, Params | None, jax.Array]:
    """scan one segment of stacked layers."""
    if unroll:
        # eager python loop over per-layer param slices: used by the
        # dense->LUT conversion pass so the activation tape sees concrete
        # arrays (jax.lax.scan would only show it tracers).
        n_layers = jax.tree.leaves(seg_params)[0].shape[0]
        aux = jnp.zeros((), jnp.float32)
        new_caches = [] if caches is not None else None
        from repro.models.common import set_tape_prefix

        for j in range(n_layers):
            set_tape_prefix(f"{prefix}/{j}")
            pl_ = jax.tree.map(lambda a: a[j], seg_params)
            cl_ = None if caches is None else jax.tree.map(lambda a: a[j], caches)
            x, nc, a = block_apply(bcfg, pl_, x, pos=pos, cache=cl_, cache_len=cache_len,
                                   slot_mask=slot_mask)
            aux = aux + a
            if caches is not None:
                new_caches.append(nc)
        if caches is not None:
            new_caches = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *new_caches)
        return x, new_caches, aux

    def body(carry, layer_in):
        xc, aux = carry
        if caches is None:
            pl_ = layer_in
            y, _, a = block_apply(bcfg, pl_, xc, pos=pos, cache=None, cache_len=None)
            return (y, aux + a), None
        pl_, cl_ = layer_in
        y, new_c, a = block_apply(bcfg, pl_, xc, pos=pos, cache=cl_,
                                  cache_len=cache_len, defer_cache_write=defer,
                                  block_tables=block_tables, write_len=write_len,
                                  slot_mask=slot_mask)
        return (y, aux + a), new_c

    # decode fast path: attention attends over the stale cache plus the
    # fresh K/V slab (flash-decoding)
    defer = (
        caches is not None
        and bcfg.kind != "mamba"
        and x.shape[1] == 1
    )
    aux0 = jnp.zeros((), jnp.float32)
    if caches is not None and "k" in caches:
        # a dense attention cache rides the scan's carry: each layer writes
        # its new rows into it in place, then reads them back, so the scan
        # never builds a whole cache as its output
        def carry_body(carry, layer_in):
            xc, aux, kv = carry
            pl_, j = layer_in
            y, kv, a = block_apply(bcfg, pl_, xc, pos=pos, cache=kv, cache_len=cache_len,
                                   defer_cache_write=defer, slot_mask=slot_mask, layer=j)
            return (y, aux + a, kv), None

        n_layers = jax.tree.leaves(seg_params)[0].shape[0]
        (x, aux, new_caches), _ = jax.lax.scan(
            carry_body, (x, aux0, caches),
            (seg_params, jnp.arange(n_layers, dtype=jnp.int32)))
        return x, new_caches, aux
    fn = jax.checkpoint(body) if (remat and caches is None) else body
    xs = seg_params if caches is None else (seg_params, caches)
    (x, aux), new_caches = jax.lax.scan(fn, (x, aux0), xs)
    if defer and new_caches is not None:
        # paged: one O(L*B*s_new) scatter of every layer's slab into the
        # page-flattened pool; masked rows route to the garbage page
        with jax.named_scope("kv_write"):
            b = x.shape[0]
            s_new = new_caches["k_slab"].shape[2]
            n_pages, page_size = caches["k_pool"].shape[1:3]
            wl = write_len
            if wl is None:
                wl = jnp.full((b,), s_new, jnp.int32)
            flat = attn_mod.paged_write_flat(
                block_tables, cache_len, s_new, page_size, wl)          # (B, s)

            def scatter(pool, slab):
                fp = pool.reshape(pool.shape[0], n_pages * page_size, *pool.shape[3:])
                return fp.at[:, flat].set(slab).reshape(pool.shape)

            new_caches = {
                "k_pool": scatter(caches["k_pool"], new_caches["k_slab"]),
                "v_pool": scatter(caches["v_pool"], new_caches["v_slab"]),
            }
    return x, new_caches, aux


def lm_apply(
    cfg: LMCfg,
    params: Params,
    *,
    tokens: jax.Array | None = None,      # (B, S) int32
    embeds: jax.Array | None = None,      # (B, S, D) stub-frontend input
    pos: jax.Array,                       # (B, S) or (3, B, S)
    caches: list | None = None,
    cache_len: jax.Array | None = None,
    compute_dtype=jnp.float32,
    block_tables: jax.Array | None = None,
    write_len: jax.Array | None = None,
    slot_mask: jax.Array | None = None,
) -> tuple[jax.Array, list | None, jax.Array]:
    """Returns (logits (B, S, vocab), new caches, aux loss). Cache rows
    outside `slot_mask` (B,), when given, come back bit-identical."""
    if cfg.takes_embeds:
        x = embeds.astype(compute_dtype)
    else:
        x = embed(params["embed"], tokens).astype(compute_dtype)

    new_caches = [] if caches is not None else None
    aux_total = jnp.zeros((), jnp.float32)
    for i, (count, bcfg) in enumerate(cfg.segments):
        c_i = caches[i] if caches is not None else None
        x, nc, aux = _seg_apply(
            bcfg, params["segments"][i], x,
            pos=pos, caches=c_i, cache_len=cache_len, remat=cfg.remat,
            unroll=cfg.unroll, prefix=f"segments/{i}",
            block_tables=block_tables, write_len=write_len, slot_mask=slot_mask,
        )
        if caches is not None:
            new_caches.append(nc)
        aux_total = aux_total + aux

    x = rmsnorm(params["final_norm"], x)
    with jax.named_scope("lm_head"):
        if cfg.lm_head is not None:
            from repro.models.common import set_tape_prefix

            set_tape_prefix("")                 # registry key: bare "lm_head"
            logits = linear(cfg.lm_head, params["lm_head"], x)
        else:
            logits = jnp.einsum(
                "bsd,vd->bsv", x, params["embed"]["table"].astype(x.dtype)
            )
    return logits, new_caches, aux_total


# MoE load-balance penalty weight — the ONE definition shared by lm_loss,
# ModelBundle.loss_from_logits, and the distillation CE term
LM_AUX_WEIGHT = 0.01


def lm_loss(
    cfg: LMCfg,
    params: Params,
    batch: dict[str, jax.Array],
    *,
    compute_dtype=jnp.float32,
    aux_weight: float = LM_AUX_WEIGHT,
) -> jax.Array:
    pos = batch.get("pos")
    if pos is None:
        b, s = batch["labels"].shape[:2]
        pos = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
    logits, _, aux = lm_apply(
        cfg,
        params,
        tokens=batch.get("tokens"),
        embeds=batch.get("embeds"),
        pos=pos,
        compute_dtype=compute_dtype,
    )
    return cross_entropy(logits, batch["labels"]) + aux_weight * aux
