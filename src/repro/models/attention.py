"""GQA attention with flash-style chunked softmax, KV cache, qk-norm, M-RoPE.

Covers every attention-bearing assigned arch: llama3/minitron/command-r
(GQA), qwen3 (GQA + qk_norm), llama4/arctic (GQA inside MoE stacks), qwen2-vl
(M-RoPE), whisper (self + cross), zamba2 (shared MHA block).

Score/AV contractions are NOT LUT-replaced (paper section 8: no weights);
the Q/K/V/O projections are LUT sites.

Attention over long sequences is computed blockwise with an online softmax
(lax.scan over KV chunks) so the 32k-prefill dry-run never materializes an
S x S score matrix.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import common
from repro.models.common import Params, SiteCfg, linear, linear_init, rmsnorm, rmsnorm_init


@dataclasses.dataclass(frozen=True)
class AttnCfg:
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_head: int
    q: SiteCfg
    k: SiteCfg
    v: SiteCfg
    o: SiteCfg
    qk_norm: bool = False
    rope_theta: float = 1_000_000.0
    mrope_sections: tuple[int, ...] = ()
    causal: bool = True
    use_rope: bool = True


def attn_init(key: jax.Array, cfg: AttnCfg, *, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {
        "q": linear_init(ks[0], cfg.q, dtype=dtype),
        "k": linear_init(ks[1], cfg.k, dtype=dtype),
        "v": linear_init(ks[2], cfg.v, dtype=dtype),
        "o": linear_init(ks[3], cfg.o, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(cfg.d_head, dtype)
        p["k_norm"] = rmsnorm_init(cfg.d_head, dtype)
    return p


def _rope(cfg: AttnCfg, x: jax.Array, pos: jax.Array) -> jax.Array:
    if not cfg.use_rope:
        return x
    if cfg.mrope_sections:
        return common.apply_mrope(x, pos, cfg.rope_theta, cfg.mrope_sections)
    return common.apply_rope(x, pos, cfg.rope_theta)


def _attend(
    qc: jax.Array,      # (B, Sq, KV, G, Dh)
    k: jax.Array,       # (B, T, KV, Dh)
    v: jax.Array,       # (B, T, KV, Dh)
    *,
    q_pos: jax.Array,   # (B, Sq)
    kv_pos: jax.Array,  # (B, T)
    causal: bool,
    kv_valid: jax.Array | None,
) -> jax.Array:
    """One q-block against the FULL KV extent.

    The KV sequence axis may be sharded over the "model" mesh axis
    (flash-decoding-style SP): the max/sum softmax reductions and the AV
    contraction over T then lower to small (B,S,H)-sized all-reduces, which
    GSPMD emits automatically — this is why we never lax.scan over the KV
    axis (scanning a sharded axis forces SPMD full rematerialization).
    """
    pv, m, l = _attend_stats(
        qc, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal, kv_valid=kv_valid
    )
    return pv / jnp.maximum(l, 1e-30)[..., None]            # (B, Sq, KV, G, Dh) f32


def _attend_stats(
    qc: jax.Array, k: jax.Array, v: jax.Array, *,
    q_pos, kv_pos, causal, kv_valid,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Unnormalized flash stats: (sum p*V, running max m, denom l)."""
    b, sq, kvh, g, dh = qc.shape
    sm = 1.0 / (dh ** 0.5)
    # cached K/V may be stored sub-bf16 (fp8 KV cache, section Perf) —
    # upcast at use; the convert fuses into the dot on TPU
    k = k.astype(qc.dtype)
    v = v.astype(qc.dtype)
    sc = jnp.einsum(
        "bskgd,btkd->bskgt", qc, k,
        preferred_element_type=jnp.float32,
    ) * sm                                                  # (B, Sq, KV, G, T)
    mask = jnp.ones((b, 1, 1, 1, k.shape[1]), bool)
    if causal:
        mask = mask & (kv_pos[:, None, :] <= q_pos[:, :, None])[:, :, None, None, :]
    if kv_valid is not None:
        mask = mask & kv_valid[:, None, None, None, :]
    neg = jnp.asarray(-1e30, sc.dtype)
    sc = jnp.where(mask, sc, neg)
    m = jnp.max(sc, axis=-1)                                # (B, Sq, KV, G)
    p = jnp.exp(sc - m[..., None])
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)
    pv = jnp.einsum(
        "bskgt,btkd->bskgd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return pv, m, l


def _merge_stats(parts: list[tuple[jax.Array, jax.Array, jax.Array]]) -> jax.Array:
    """Combine flash partials from disjoint KV sources (flash-decoding)."""
    m = parts[0][1]
    for _, mi, _ in parts[1:]:
        m = jnp.maximum(m, mi)
    acc = jnp.zeros_like(parts[0][0])
    l = jnp.zeros_like(parts[0][2])
    for pv, mi, li in parts:
        corr = jnp.exp(mi - m)
        acc = acc + pv * corr[..., None]
        l = l + li * corr
    return acc / jnp.maximum(l, 1e-30)[..., None]


def flash_attention(
    q: jax.Array,       # (B, S, Hq, Dh)
    k: jax.Array,       # (B, T, KV, Dh)
    v: jax.Array,       # (B, T, KV, Dh)
    *,
    q_pos: jax.Array,   # (B, S) int32 absolute positions
    kv_pos: jax.Array,  # (B, T) int32 (entries > q_pos are masked when causal)
    causal: bool,
    kv_valid: jax.Array | None = None,  # (B, T) bool extra mask (cache fill)
    q_chunk: int = 512,
) -> jax.Array:
    """Grouped-query attention, blocked over the *query* axis.

    Scanning over Q (never KV) keeps every scanned axis unsharded; the score
    matrix peak is B x q_chunk x H x T per step instead of B x S x H x T.
    """
    b, s, hq, dh = q.shape
    kvh = k.shape[2]
    g = hq // kvh
    qg = q.reshape(b, s, kvh, g, dh)

    nq = max(1, s // q_chunk)
    while s % nq:
        nq -= 1
    qc_len = s // nq
    if nq == 1:
        out = _attend(qg, k, v, q_pos=q_pos, kv_pos=kv_pos, causal=causal, kv_valid=kv_valid)
        return out.reshape(b, s, hq, dh).astype(q.dtype)

    q_blocks = qg.reshape(b, nq, qc_len, kvh, g, dh).swapaxes(0, 1)
    pos_blocks = q_pos.reshape(b, nq, qc_len).swapaxes(0, 1)

    def step(_, inp):
        qb, pb = inp
        return None, _attend(
            qb, k, v, q_pos=pb, kv_pos=kv_pos, causal=causal, kv_valid=kv_valid
        )

    _, out = jax.lax.scan(step, None, (q_blocks, pos_blocks))
    out = out.swapaxes(0, 1).reshape(b, s, hq, dh)
    return out.astype(q.dtype)


def write_rows(
    cache: jax.Array,            # (B, S, KV, Dh), or (L, B, S, KV, Dh) with `layer`
    rows: jax.Array,             # (B, s, KV, Dh) new positions of each row
    start: jax.Array,            # (B,) position of each row's first new one
    keep: jax.Array | None,      # (B,) bool rows written; None: all
    layer: jax.Array | None = None,
) -> jax.Array:
    """Scatter each row's new positions into `cache` (slice `layer` of a
    layer-stacked one). Rows `keep` leaves out are routed past the end, and
    they and every position at or past S are dropped: those cache rows stay
    bit-identical. Only the new positions are written, so a donated cache
    is updated in place."""
    b, s = rows.shape[:2]
    pos = start[:, None] + jnp.arange(s, dtype=start.dtype)[None, :]     # (B, s)
    if keep is not None:
        pos = jnp.where(keep[:, None], pos, cache.shape[-3])
    idx = (jnp.arange(b, dtype=start.dtype)[:, None], pos)
    if layer is not None:
        idx = (layer,) + idx
    return cache.at[idx].set(rows.astype(cache.dtype), mode="drop")


def init_cache(b: int, s_max: int, cfg: AttnCfg, dtype=jnp.bfloat16) -> Params:
    return {
        "k": jnp.zeros((b, s_max, cfg.n_kv_heads, cfg.d_head), dtype),
        "v": jnp.zeros((b, s_max, cfg.n_kv_heads, cfg.d_head), dtype),
    }


def cache_specs(b: int, s_max: int, cfg: AttnCfg, dtype=jnp.bfloat16) -> Params:
    return {
        "k": jax.ShapeDtypeStruct((b, s_max, cfg.n_kv_heads, cfg.d_head), dtype),
        "v": jax.ShapeDtypeStruct((b, s_max, cfg.n_kv_heads, cfg.d_head), dtype),
    }


# ---------------------------------------------------------------------------
# paged KV cache (DESIGN.md §12)
# ---------------------------------------------------------------------------
#
# A paged cache is a pool of `n_pages` physical pages of `page_size` token
# positions each, shared by every sequence in the batch; each sequence maps
# its logical positions through a per-row block table (B, P) of page ids.
# Page 0 is the reserved *garbage* page: masked / out-of-range writes are
# routed there instead of being merged away with a select, so the jitted
# step function needs no per-slot write mask over pool leaves. Allocators
# must never hand out page 0. Pool content stays finite (zeros at init,
# activation values after), so gathered-then-masked garbage contributes
# exactly 0 to the flash softmax.

GARBAGE_PAGE = 0


@dataclasses.dataclass(frozen=True)
class PagedSpec:
    """Layout of a paged KV pool."""
    n_pages: int
    page_size: int


def paged_init_cache(spec: PagedSpec, cfg: AttnCfg, dtype=jnp.bfloat16) -> Params:
    shape = (spec.n_pages, spec.page_size, cfg.n_kv_heads, cfg.d_head)
    return {"k_pool": jnp.zeros(shape, dtype), "v_pool": jnp.zeros(shape, dtype)}


def paged_cache_specs(spec: PagedSpec, cfg: AttnCfg, dtype=jnp.bfloat16) -> Params:
    shape = (spec.n_pages, spec.page_size, cfg.n_kv_heads, cfg.d_head)
    return {
        "k_pool": jax.ShapeDtypeStruct(shape, dtype),
        "v_pool": jax.ShapeDtypeStruct(shape, dtype),
    }


def paged_write_flat(
    block_tables: jax.Array,   # (B, P) int32 page ids
    cache_len: jax.Array,      # (B,) logical write cursor
    s: int,                    # fresh positions per row
    page_size: int,
    write_len: jax.Array,      # (B,) valid count; offsets >= write_len -> garbage
) -> jax.Array:
    """(B, s) indices into the page-flattened pool axis (n_pages*page_size)
    for the `s` fresh positions starting at cache_len. Invalid positions
    (padding rows, chunk tail past write_len, or past the table width) all
    land in GARBAGE_PAGE."""
    n_tables = block_tables.shape[1]
    off = jnp.arange(s, dtype=jnp.int32)[None, :]
    write_idx = cache_len[:, None].astype(jnp.int32) + off     # (B, s) logical
    p_idx = write_idx // page_size
    ok = (off < write_len[:, None]) & (p_idx < n_tables)
    pages = jnp.take_along_axis(block_tables, jnp.minimum(p_idx, n_tables - 1), axis=1)
    pages = jnp.where(ok, pages, GARBAGE_PAGE)
    return pages * page_size + jnp.where(ok, write_idx % page_size, 0)


def paged_gather(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Materialize each row's logical KV extent: (B, P*page_size, KV, Dh).

    The gathered layout is *identical* to the dense (B, s_max, KV, Dh) cache
    when P*page_size == s_max, which is what makes paged serving token
    parity with the dense engine exact rather than approximate."""
    b, p = block_tables.shape
    g = pool[block_tables]                     # (B, P, page_size, KV, Dh)
    return g.reshape(b, p * pool.shape[1], *pool.shape[2:])


@jax.named_scope("attention")
def attention(
    cfg: AttnCfg,
    p: Params,
    x: jax.Array,                 # (B, S, D)
    *,
    pos: jax.Array,               # (B, S) or (3, B, S) for M-RoPE
    cache: Params | None = None,
    cache_len: jax.Array | None = None,  # (B,) tokens already in cache
    x_kv: jax.Array | None = None,       # cross-attention memory (B, T, D)
    kv_pos: jax.Array | None = None,
    defer_cache_write: bool = False,
    block_tables: jax.Array | None = None,  # (B, P) page ids (paged cache)
    write_len: jax.Array | None = None,     # (B,) valid fresh tokens per row
    slot_mask: jax.Array | None = None,     # (B,) rows whose dense cache is written
    layer: jax.Array | None = None,         # dense cache leaves are layer-stacked
) -> tuple[jax.Array, Params | None]:
    """Returns (output (B, S, D), updated cache).

    defer_cache_write (decode fast path, section Perf): attend over the
    STALE cache and the fresh K/V slab as two flash partials. A paged cache
    returns {"k_slab", "v_slab"} instead of a rewritten pool — the caller
    scatters all layers' slabs into the pool in one O(tokens) write after
    its scan; a dense cache is written in place first, as in every forward.

    Paged caches ({"k_pool", "v_pool"}, DESIGN.md §12) route through the
    same entry point: writes scatter into the page-flattened pool via the
    block table (invalid positions land in the garbage page), reads gather
    the row's pages back into the dense logical layout and reuse the exact
    dense masks, so outputs are bit-identical to the dense cache path.

    A dense cache is written only in the rows `slot_mask` keeps (all rows
    when it is None); every other row stays bit-identical. With `layer`
    given, the dense cache leaves are (L, B, S_max, KV, Dh): this layer's
    new rows go into slice `layer` in place, and attention reads that
    slice. Paged writes are masked by `write_len` instead.

    Its ops sit under the named scope `attention`, its cache writes under
    `attention/kv_write`, and its projections under `attention/linear`.
    """
    b, s, _ = x.shape
    src = x if x_kv is None else x_kv
    q = linear(cfg.q, p["q"], x).reshape(b, s, cfg.n_heads, cfg.d_head)
    k = linear(cfg.k, p["k"], src).reshape(b, src.shape[1], cfg.n_kv_heads, cfg.d_head)
    v = linear(cfg.v, p["v"], src).reshape(b, src.shape[1], cfg.n_kv_heads, cfg.d_head)

    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q)
        k = rmsnorm(p["k_norm"], k)

    flat_pos = pos if pos.ndim == 2 else pos[0]   # (B, S) scalar stream for masks
    q = _rope(cfg, q, pos)
    if x_kv is None:
        k = _rope(cfg, k, pos if kv_pos is None else kv_pos)

    paged = cache is not None and "k_pool" in cache
    if paged:
        if block_tables is None:
            raise ValueError("paged cache requires block_tables")
        if write_len is None:
            write_len = jnp.full((b,), s, jnp.int32)

    if cache is None:
        out = flash_attention(
            q, k, v,
            q_pos=flat_pos,
            kv_pos=flat_pos if kv_pos is None or kv_pos.ndim != 2 else kv_pos,
            causal=cfg.causal,
        )
        new_cache = None
    elif paged and defer_cache_write:
        # flash-decoding over (stale gathered pages) + (fresh slab); the
        # segment-level scatter writes the slab into the pool afterwards
        page_size = cache["k_pool"].shape[1]
        s_logical = block_tables.shape[1] * page_size
        kvh, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
        qg = q.reshape(b, s, kvh, g, cfg.d_head)
        all_pos = jnp.arange(s_logical, dtype=jnp.int32)[None, :].repeat(b, 0)
        stale_valid = all_pos < cache_len[:, None]
        part_cache = _attend_stats(
            qg,
            paged_gather(cache["k_pool"], block_tables),
            paged_gather(cache["v_pool"], block_tables),
            q_pos=flat_pos, kv_pos=all_pos, causal=cfg.causal, kv_valid=stale_valid,
        )
        slab_pos = (cache_len[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :])
        part_slab = _attend_stats(
            qg, k, v, q_pos=flat_pos, kv_pos=slab_pos, causal=cfg.causal, kv_valid=None,
        )
        out = _merge_stats([part_cache, part_slab]).reshape(b, s, cfg.n_heads, cfg.d_head)
        out = out.astype(q.dtype)
        new_cache = {
            "k_slab": k.astype(cache["k_pool"].dtype),
            "v_slab": v.astype(cache["v_pool"].dtype),
        }
    elif paged:
        # scatter fresh K/V into the page-flattened pool (garbage-routed
        # masking), then gather this row's pages and attend densely
        n_pages, page_size = cache["k_pool"].shape[:2]
        flat = paged_write_flat(block_tables, cache_len, s, page_size, write_len)
        flat_shape = (n_pages * page_size, cfg.n_kv_heads, cfg.d_head)
        with jax.named_scope("kv_write"):
            ck = (cache["k_pool"].reshape(flat_shape)
                  .at[flat].set(k.astype(cache["k_pool"].dtype))
                  .reshape(cache["k_pool"].shape))
            cv = (cache["v_pool"].reshape(flat_shape)
                  .at[flat].set(v.astype(cache["v_pool"].dtype))
                  .reshape(cache["v_pool"].shape))
        new_cache = {"k_pool": ck, "v_pool": cv}
        s_logical = block_tables.shape[1] * page_size
        all_pos = jnp.arange(s_logical, dtype=jnp.int32)[None, :].repeat(b, 0)
        valid = all_pos < (cache_len + s)[:, None]
        out = flash_attention(
            q,
            paged_gather(ck, block_tables),
            paged_gather(cv, block_tables),
            q_pos=flat_pos,
            kv_pos=all_pos,
            causal=cfg.causal,
            kv_valid=valid,
        )
    else:
        # write new K/V at per-sequence cursors in place, then read the
        # cache back and attend over it
        with jax.named_scope("kv_write"):
            new_cache = {
                name: write_rows(cache[name], new, cache_len, slot_mask, layer)
                for name, new in (("k", k), ("v", v))
            }
        ck, cv = new_cache["k"], new_cache["v"]
        if layer is not None:
            ck = jax.lax.dynamic_index_in_dim(ck, layer, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(cv, layer, keepdims=False)
        s_max = ck.shape[1]
        all_pos = jnp.arange(s_max, dtype=jnp.int32)[None, :].repeat(b, 0)
        if defer_cache_write:
            # flash-decoding over (stale cache) + (fresh slab): the stale
            # partial masks every position from cache_len on, the new
            # rows included
            kvh, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
            qg = q.reshape(b, s, kvh, g, cfg.d_head)
            part_cache = _attend_stats(
                qg, ck, cv, q_pos=flat_pos, kv_pos=all_pos, causal=cfg.causal,
                kv_valid=all_pos < cache_len[:, None],
            )
            slab_pos = (cache_len[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :])
            part_slab = _attend_stats(
                qg, k, v, q_pos=flat_pos, kv_pos=slab_pos, causal=cfg.causal, kv_valid=None,
            )
            out = _merge_stats([part_cache, part_slab]).reshape(b, s, cfg.n_heads, cfg.d_head)
            out = out.astype(q.dtype)
        else:
            out = flash_attention(
                q, ck, cv,
                q_pos=flat_pos,
                kv_pos=all_pos,
                causal=cfg.causal,
                kv_valid=all_pos < (cache_len + s)[:, None],
            )

    y = linear(cfg.o, p["o"], out.reshape(b, s, cfg.n_heads * cfg.d_head))
    return y, new_cache
