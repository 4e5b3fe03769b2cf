"""Mixture-of-Experts FFN with GShard-style capacity dispatch.

Covers llama4-maverick (128e top-1 + shared expert) and arctic (128e top-2 +
dense residual branch — the residual lives at the block level, see
transformer.py).

LUT-NN integration (DESIGN.md section 4): the router stays exact (dense) —
approximating routing logits destabilizes top-k selection; expert
projections are LUT sites with **per-expert tables sharing per-layer
codebooks** (the layer input distribution is expert-independent, so one
codebook serves all experts; table memory scales with E, encode cost does
not have to — the encode-once-dispatch-codes variant is a §Perf lever).

Tokens are grouped by the batch axis (G = B groups of S tokens), which is
also the data-sharded axis, so dispatch/combine einsums stay local until the
expert contraction itself — GSPMD then emits the all-to-all across the
expert-sharded axis.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import pq, quant
from repro.core.amm import LUTConfig, Mode
from repro.core.temperature import init_log_temperature, temperature
from repro.models.common import Params, SiteCfg, activation, linear, linear_init


@dataclasses.dataclass(frozen=True)
class ExpertSiteCfg:
    """Expert-stacked linear site: (E, Cap, d_in) -> (E, Cap, d_out)."""

    n_experts: int
    d_in: int
    d_out: int
    mode: Mode
    lut: LUTConfig


def expert_linear_init(key: jax.Array, s: ExpertSiteCfg, *, dtype=jnp.float32) -> Params:
    kw, kc = jax.random.split(key)
    scale = 1.0 / (s.d_in ** 0.5)
    w = (jax.random.normal(kw, (s.n_experts, s.d_in, s.d_out), jnp.float32) * scale).astype(dtype)
    if s.mode == Mode.DENSE:
        return {"w": w}
    c = s.lut.codebooks(s.d_in)
    centroids = jax.random.normal(kc, (c, s.lut.k, s.lut.v), jnp.float32) * 0.02
    if s.mode == Mode.LUT_TRAIN:
        return {"w": w, "centroids": centroids, "log_t": init_log_temperature()}
    # LUT_INFER: int8 tables per expert, shared codebooks; scale layout
    # mirrors quant.table_scale per the site's policy (deploy writes the
    # same shapes — core.convert._build_quantize_tables)
    if s.lut.int8_dot or s.lut.use_kernel:
        s_shape = (s.n_experts, 1, 1, s.d_out)
    elif s.lut.per_column:
        s_shape = (s.n_experts, c, 1, s.d_out)
    else:
        s_shape = (s.n_experts, c, 1, 1)
    return {
        "centroids": centroids,
        "table_q": jax.random.randint(kc, (s.n_experts, c, s.lut.k, s.d_out), -127, 127, jnp.int8),
        "table_scale": jnp.full(s_shape, 0.02, jnp.float32),
    }


def _expert_tables_train(p: Params, s: ExpertSiteCfg) -> jax.Array:
    """(E, C, K, F) fake-quantized tables rebuilt from frozen expert weights."""
    c = s.lut.codebooks(s.d_in)
    w = jax.lax.stop_gradient(p["w"]).reshape(s.n_experts, c, s.lut.v, s.d_out)
    t = jnp.einsum("ckv,ecvf->eckf", p["centroids"].astype(w.dtype), w)
    # per-(expert, codebook) symmetric scale — same policy as quant.fake_quant
    scale = jnp.maximum(
        jnp.max(jnp.abs(t), axis=(2, 3), keepdims=True).astype(jnp.float32), 1e-8
    ) / (2 ** (s.lut.bits - 1) - 1)
    t32 = t.astype(jnp.float32)
    qdq = jnp.clip(jnp.round(t32 / scale), -(2 ** (s.lut.bits - 1) - 1), 2 ** (s.lut.bits - 1) - 1) * scale
    return (t32 + jax.lax.stop_gradient(qdq - t32)).astype(t.dtype)


def expert_linear(s: ExpertSiteCfg, p: Params, x: jax.Array) -> jax.Array:
    """x: (E, Cap*, d_in) -> (E, Cap*, d_out). Cap* may have extra leading dims
    folded in by the caller (we use (E, G*Cap, d_in))."""
    if s.mode == Mode.DENSE:
        return jnp.einsum("ecd,edf->ecf", x, p["w"].astype(x.dtype))

    P = p["centroids"]
    e, cap, _ = x.shape
    xf = x.reshape(e * cap, s.d_in)
    scores = pq.encode_scores(pq.split_subvectors(xf, s.lut.v), P)
    if s.mode == Mode.LUT_TRAIN:
        enc = pq.ste_encode(scores, temperature(p["log_t"]))
        tables = _expert_tables_train(p, s)
    elif s.lut.int8_dot:
        # integer batched contraction: tables stream once as int8
        enc8 = pq.hard_encode(scores).reshape(e, cap, -1).astype(jnp.int8)
        tq = p["table_q"].reshape(e, -1, s.d_out)
        acc = jax.lax.dot_general(
            enc8, tq, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.int32,
        )
        return (acc.astype(jnp.float32) * p["table_scale"].reshape(e, 1, s.d_out)).astype(x.dtype)
    else:
        enc = pq.hard_encode(scores)
        tables = (p["table_q"].astype(jnp.float32) * p["table_scale"]).astype(x.dtype)
    enc = enc.reshape(e, cap, -1).astype(x.dtype)             # (E, Cap, C*K)
    tbl = tables.reshape(e, tables.shape[1] * tables.shape[2], s.d_out)
    return jnp.einsum("ecx,exf->ecf", enc, tbl.astype(x.dtype))


@dataclasses.dataclass(frozen=True)
class MoECfg:
    d_model: int
    d_ff: int
    n_experts: int
    top_k: int
    router: SiteCfg                      # always DENSE
    gate: ExpertSiteCfg
    up: ExpertSiteCfg
    down: ExpertSiteCfg
    shared: object | None = None         # optional MLPCfg for a shared expert
    act: str = "silu"
    capacity_factor: float = 1.25
    # tokens per routing group: dispatch/combine tensors scale LINEARLY with
    # the group size (total = tokens * cf * k * G elems), so long-sequence
    # prefill/train must not use the whole sequence as one group
    # (section Perf, MoE iteration 1)
    group_tokens: int = 1024


def moe_init(key: jax.Array, cfg: MoECfg, *, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 5)
    p: Params = {
        "router": linear_init(ks[0], cfg.router, dtype=jnp.float32),
        "gate": expert_linear_init(ks[1], cfg.gate, dtype=dtype),
        "up": expert_linear_init(ks[2], cfg.up, dtype=dtype),
        "down": expert_linear_init(ks[3], cfg.down, dtype=dtype),
    }
    if cfg.shared is not None:
        from repro.models.mlp import mlp_init

        p["shared"] = mlp_init(ks[4], cfg.shared, dtype=dtype)
    return p


def moe(cfg: MoECfg, p: Params, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux_loss). Routing groups = `group_tokens` chunks
    of the (batch-major) token stream, so per-group capacity stays bounded
    at long sequence lengths."""
    b0, s0, d = x.shape
    g_tok = max(1, min(cfg.group_tokens, s0))
    while s0 % g_tok:
        g_tok //= 2
    x = x.reshape(b0 * (s0 // g_tok), g_tok, d)
    b, s, _ = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = max(k, int(cfg.capacity_factor * k * s / e) + 1)

    logits = linear(cfg.router, p["router"], x.astype(jnp.float32))   # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)

    # top-k routing with per-group capacity (GShard)
    combine = jnp.zeros((b, s, e, cap), x.dtype)
    dispatch = jnp.zeros((b, s, e, cap), bool)
    remaining = probs
    fill = jnp.zeros((b, e), jnp.int32)                                # slots used
    for _ in range(k):
        gate, idx = jnp.max(remaining, -1), jnp.argmax(remaining, -1)  # (B, S)
        onehot_e = jax.nn.one_hot(idx, e, dtype=jnp.int32)             # (B, S, E)
        pos = fill[:, None, :] + jnp.cumsum(onehot_e, axis=1) - onehot_e  # (B, S, E)
        slot = jnp.sum(onehot_e * pos, -1)                             # (B, S)
        keep = slot < cap
        oh_slot = jax.nn.one_hot(slot, cap, dtype=x.dtype) * keep[..., None]
        d_k = onehot_e.astype(x.dtype)[..., None] * oh_slot[:, :, None, :]
        dispatch |= d_k.astype(bool)
        combine = combine + gate.astype(x.dtype)[..., None, None] * d_k
        fill = fill + jnp.sum(onehot_e * keep[..., None].astype(jnp.int32), axis=1)
        remaining = remaining * (1.0 - jax.nn.one_hot(idx, e, dtype=probs.dtype))

    # load-balance aux loss (Switch): E * sum_e f_e * P_e
    frac_tokens = jnp.mean(jnp.sum(dispatch, axis=-1).astype(jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * frac_probs) / k

    xin = jnp.einsum("bsec,bsd->ebcd", dispatch.astype(x.dtype), x)    # (E, B, Cap, D)
    xin = xin.reshape(e, b * cap, d)
    g = activation(cfg.act, expert_linear(cfg.gate, p["gate"], xin))
    u = expert_linear(cfg.up, p["up"], xin)
    h = expert_linear(cfg.down, p["down"], g * u)                      # (E, B*Cap, D)
    h = h.reshape(e, b, cap, d)
    y = jnp.einsum("bsec,ebcd->bsd", combine, h)

    if cfg.shared is not None:
        from repro.models.mlp import mlp as mlp_apply

        y = y + mlp_apply(cfg.shared, p["shared"], x)
    return y.reshape(b0, s0, d), aux
