"""A test family (model_type "tiny_moe"): the program's sparse-expert block
(`repro.models.moe`), wired to the benchmark by this file alone.

Grouped-query attention with RoPE and no q or k norm, then a softmax
router over `num_experts` SwiGLU experts of width `moe_intermediate_size`,
each token sent to its `num_experts_per_tok` best and weighted by their
router probabilities (not renormalised), plus `n_shared_experts` (0 or 1)
shared SwiGLU expert of the same width. The router stays dense; the
expert sites are stacked over experts and share one set of centroids
per site. With `num_experts_per_tok == num_experts` no token is dropped
by the program's capacity dispatch, so what is served does not depend on
chunking.

The cached state `correct` compares is each layer's keys and values.
"""

from __future__ import annotations

from typing import Any

from harness import weights, work

_ATTN = ("q", "k", "v", "o")
_MLP = ("gate", "up", "down")


def arch(cfg: dict[str, Any]):
    from repro.configs import ArchSpec

    if cfg["num_experts_per_tok"] != cfg["num_experts"] or cfg["norm_topk_prob"]:
        raise ValueError("the program's capacity dispatch drops tokens unless every "
                         "expert takes every token, and it does not renormalise")
    lut = cfg.get("lut") or {}
    return ArchSpec(
        name=cfg["name"], family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        d_head=cfg["head_dim"], d_ff=cfg["moe_intermediate_size"],
        vocab=cfg["vocab_size"], act=cfg["hidden_act"], qk_norm=False,
        rope_theta=float(cfg["rope_theta"]), tie_embeddings=cfg["tie_word_embeddings"],
        n_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        moe_shared_expert=bool(cfg["n_shared_experts"]),
        lut_k=lut.get("k", 16), lut_v=lut.get("v", 32), lut_bits=lut.get("bits", 8),
        lut_use_kernel=lut.get("use_kernel", False),
        lut_policy=lut.get("plan", "all_but_first"),
        param_dtype=cfg["compute"]["dtype"],
    )


def layer(cfg: dict[str, Any], i: int) -> weights.Layer:
    d, ff, e = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_experts"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    Site = weights.Site
    sites = [Site("q", d, hq, ("attn", "q")), Site("k", d, hkv, ("attn", "k")),
             Site("v", d, hkv, ("attn", "v")),
             Site("o", hq, d, ("attn", "o"), residual=True, centroid_scale=0.5),
             Site("router", d, e, ("moe", "router"), lut=False)]
    expert = dict(experts=e, top_k=cfg["num_experts_per_tok"])
    sites += [Site("gate", d, ff, ("moe", "gate"), **expert),
              Site("up", d, ff, ("moe", "up"), **expert),
              Site("down", ff, d, ("moe", "down"), residual=True, centroid_scale=0.6,
                   **expert)]
    if cfg["n_shared_experts"]:
        sites += [Site("shared_gate", d, ff, ("moe", "shared", "gate")),
                  Site("shared_up", d, ff, ("moe", "shared", "up")),
                  Site("shared_down", ff, d, ("moe", "shared", "down"), residual=True,
                       centroid_scale=0.6)]
    return weights.Layer((("norm1", d), ("norm2", d)), tuple(sites))


def program_layer(w: dict[str, Any]) -> dict[str, Any]:
    moe = {s: w[s] for s in ("router",) + _MLP}
    if "shared_gate" in w:
        moe["shared"] = {s: w[f"shared_{s}"] for s in _MLP}
    return {"norm1": {"scale": w["norm1"]}, "norm2": {"scale": w["norm2"]},
            "attn": {s: w[s] for s in _ATTN}, "moe": moe}


def cache(segment: dict[str, Any]) -> dict[str, Any]:
    return {"k": segment["k"], "v": segment["v"]}


def attention(cfg: dict[str, Any]) -> work.Attention:
    dh = cfg["head_dim"]
    return work.Attention(cfg["num_attention_heads"], dh, dh,
                          2 * cfg["num_key_value_heads"] * dh * work.F32)
