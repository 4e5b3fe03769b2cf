"""Plain float32 reference of Qwen3 (huggingface.co/Qwen/Qwen3-1.7B) with
LUT-NN linear sites (arXiv:2302.03213, Eqs. 1-4).

Straight `jax.numpy`: no kernels, no cache, no batching across sequences.
One causal forward over a whole token sequence, layer by layer:

    h = embed[tokens]
    per layer:  a = rmsnorm(h) ; q, k, v = site(a) ; q, k = rmsnorm per head
                q, k = rope(q, k) (rotate-half, theta from the config)
                h += o(softmax(q k^T / sqrt(dh), causal, GQA) v)
                h += down(silu(gate(rmsnorm(h))) * up(rmsnorm(h)))
    logits = rmsnorm(h) @ embed^T       (tied embeddings)

A dense site is x @ W. A LUT site splits x into C = d_in / v sub-vectors,
encodes each as the index of its nearest centroid by squared Euclidean
distance (Eq. 2, computed as the sum of squared differences), and sums the
table rows those indices pick, dequantised by their scale (Eqs. 3-4).

Every matrix product takes the `precision` it is given: HIGHEST for the
reference, HIGH (three bfloat16 passes on a TPU) for the control.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


def dot(spec: str, a, b, precision):
    """einsum in float32 at `precision`."""
    return jnp.einsum(spec, a, b, precision=precision)


def rmsnorm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x: (S, H, dh); rotate-half RoPE at positions `pos` (S,)."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def site(p: dict[str, Any], x, precision):
    """One linear site on rows x (S, d_in)."""
    if "w" in p:
        return dot("sd,dm->sm", x, p["w"], precision)
    cent = p["centroids"]                              # (C, K, v)
    c, k, v = cent.shape
    sub = x.reshape(x.shape[0], c, 1, v)
    idx = jnp.argmin(jnp.sum((sub - cent[None]) ** 2, axis=-1), axis=-1)  # (S, C)
    table = p["table_q"].astype(jnp.float32) * p["table_scale"]          # (C, K, M)
    onehot = jax.nn.one_hot(idx, k, dtype=jnp.float32).reshape(x.shape[0], c * k)
    return dot("sj,jm->sm", onehot, table.reshape(c * k, -1), precision)


def layer(cfg: dict[str, Any], w: dict[str, Any], h, precision):
    """One decoder layer over a whole sequence h (S, d). Returns the new h
    and the layer's cached leaves as a KV cache holds them: {"k": keys
    (after norm and RoPE), "v": values}, (S, kv, dh) each."""
    s = h.shape[0]
    eps = cfg["rms_norm_eps"]
    nh, nkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    pos = jnp.arange(s)
    a = rmsnorm(h, w["norm1"], eps)
    q = site(w["q"], a, precision).reshape(s, nh, dh)
    k = site(w["k"], a, precision).reshape(s, nkv, dh)
    v = site(w["v"], a, precision).reshape(s, nkv, dh)
    q = rope(rmsnorm(q, w["q_norm"], eps), pos, cfg["rope_theta"])
    k = rope(rmsnorm(k, w["k_norm"], eps), pos, cfg["rope_theta"])
    cache_k, cache_v = k, v
    rep = nh // nkv                                    # query heads per KV head
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = dot("qhd,khd->hqk", q, k, precision) / dh ** 0.5
    causal = pos[None, :, None] >= pos[None, None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = dot("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v, precision)
    h = h + site(w["o"], attn.reshape(s, nh * dh), precision)
    m = rmsnorm(h, w["norm2"], eps)
    gate = site(w["gate"], m, precision)
    up = site(w["up"], m, precision)
    return (h + site(w["down"], jax.nn.silu(gate) * up, precision),
            {"k": cache_k, "v": cache_v})


def embed(g: dict[str, Any], tokens):
    return g["embed"][tokens]


def final_hidden(cfg, g, h):
    return rmsnorm(h, g["final_norm"], cfg["rms_norm_eps"])


def logits(g, hf, precision):
    """(S, vocab) logits of final hidden states hf (S, d), tied embedding."""
    return dot("sd,vd->sv", hf, g["embed"], precision)
