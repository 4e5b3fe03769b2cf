"""Plain float32 reference of the test family's block (attention, then a
softmax-routed mixture of SwiGLU experts with a shared expert), with
LUT-NN linear sites (arXiv:2302.03213, Eqs. 1-4).

Straight `jax.numpy`: no kernels, no cache, no capacity, no batching. One
causal forward over a whole token sequence, layer by layer:

    per layer:  a = rmsnorm(h) ; q, k, v = site(a) ; q, k = rope(q, k)
                h += o(softmax(q k^T / sqrt(dh), causal, GQA) v)
                m = rmsnorm(h) ; p = softmax(m @ router)
                h += sum over each token's top-k experts e of
                     p_e * down_e(silu(gate_e(m)) * up_e(m))
                     + down_s(silu(gate_s(m)) * up_s(m))     (shared)
    logits = rmsnorm(h) @ embed^T

An expert LUT site encodes its input once with the centroids its experts
share and sums the rows of expert e's table that the codes pick.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def dot(spec: str, a, b, precision):
    return jnp.einsum(spec, a, b, precision=precision)


def rmsnorm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _codes(cent, x):
    c, k, v = cent.shape
    sub = x.reshape(x.shape[0], c, 1, v)
    idx = jnp.argmin(jnp.sum((sub - cent[None]) ** 2, axis=-1), axis=-1)      # (S, C)
    return jax.nn.one_hot(idx, k, dtype=jnp.float32).reshape(x.shape[0], c * k)


def site(p: dict[str, Any], x, precision, expert=None):
    """One linear site on rows x (S, d_in); of expert `expert` where the
    site is stacked over experts."""
    if "w" in p:
        w = p["w"] if expert is None else p["w"][expert]
        return dot("sd,dm->sm", x, w, precision)
    tq, ts = p["table_q"], p["table_scale"]
    if expert is not None:
        tq, ts = tq[expert], ts[expert]
    table = tq.astype(jnp.float32) * ts                                      # (C, K, M)
    return dot("sj,jm->sm", _codes(p["centroids"], x), table.reshape(-1, table.shape[-1]),
               precision)


def _swiglu(w, x, precision, prefix="", expert=None):
    g = site(w[prefix + "gate"], x, precision, expert)
    u = site(w[prefix + "up"], x, precision, expert)
    return site(w[prefix + "down"], jax.nn.silu(g) * u, precision, expert)


def layer(cfg: dict[str, Any], w: dict[str, Any], h, precision):
    """One layer over a whole sequence h (S, d): the new h and the cached
    leaves {"k", "v"}, (S, kv, dh) each."""
    s = h.shape[0]
    eps = cfg["rms_norm_eps"]
    nh, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    pos = jnp.arange(s)
    a = rmsnorm(h, w["norm1"], eps)
    q = rope(site(w["q"], a, precision).reshape(s, nh, dh), pos, cfg["rope_theta"])
    k = rope(site(w["k"], a, precision).reshape(s, nkv, dh), pos, cfg["rope_theta"])
    v = site(w["v"], a, precision).reshape(s, nkv, dh)
    rep = nh // nkv
    scores = dot("qhd,khd->hqk", q, jnp.repeat(k, rep, axis=1), precision) / dh ** 0.5
    scores = jnp.where(pos[None, :, None] >= pos[None, None, :], scores, -jnp.inf)
    attn = dot("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), jnp.repeat(v, rep, axis=1),
               precision)
    h = h + site(w["o"], attn.reshape(s, nh * dh), precision)
    m = rmsnorm(h, w["norm2"], eps)
    probs = jax.nn.softmax(site(w["router"], m, precision), axis=-1)         # (S, E)
    n_exp, top_k = cfg["num_experts"], cfg["num_experts_per_tok"]
    top, idx = jax.lax.top_k(probs, top_k)
    gates = jnp.sum(jax.nn.one_hot(idx, n_exp) * top[..., None], axis=-2)    # (S, E)
    y = sum(gates[:, e:e + 1] * _swiglu(w, m, precision, expert=e) for e in range(n_exp))
    if cfg["n_shared_experts"]:
        y = y + _swiglu(w, m, precision, prefix="shared_")
    return h + y, {"k": k, "v": v}


def embed(g: dict[str, Any], tokens):
    return g["embed"][tokens]


def final_hidden(cfg, g, h):
    return rmsnorm(h, g["final_norm"], cfg["rms_norm_eps"])


def logits(g, hf, precision):
    return dot("sd,vd->sv", hf, g["embed"], precision)
