"""The Qwen3 family (model_type "qwen3", huggingface.co/Qwen/Qwen3-1.7B):
the program's dense decoder block with grouped-query attention, per-head
q and k norms, RoPE, a SwiGLU MLP and tied embeddings.

Every layer holds the same seven linear sites, all of them LUT sites
where the plan replaces the layer:

    {"norm1": (d,), "norm2": (d,), "q_norm": (dh,), "k_norm": (dh,),
     "q": site, "k": site, "v": site, "o": site,
     "gate": site, "up": site, "down": site}

The cached state `correct` compares is each layer's keys (after norm and
RoPE) and values, (layers, slots, max_seq, kv heads, head dim) each.
"""

from __future__ import annotations

import inspect
from typing import Any

from harness import weights, work

_ATTN = ("q", "k", "v", "o")
# the scale of the activations each site encodes: normalised inputs 1,
# attention outputs 0.5, the MLP's hidden 0.6
CENTROID_SCALE = {"o": 0.5, "down": 0.6}


def arch(cfg: dict[str, Any]):
    """The program's ArchSpec for a configuration file; refuses a file the
    program cannot serve as stated."""
    from repro.configs import ArchSpec
    from repro.models import common

    eps = inspect.signature(common.rmsnorm).parameters["eps"].default
    if cfg["rms_norm_eps"] != eps:
        raise ValueError(f"rms_norm_eps={cfg['rms_norm_eps']}: the program "
                         f"normalises with eps={eps}")
    if cfg.get("attention_bias") or cfg.get("use_sliding_window") or cfg.get("rope_scaling"):
        raise ValueError("attention bias, sliding windows and RoPE scaling are "
                         "not wired for this family")
    lut = cfg.get("lut") or {}
    return ArchSpec(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        d_head=cfg["head_dim"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], act=cfg["hidden_act"], mlp_gated=True,
        qk_norm=True, use_bias=False, rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        lut_k=lut.get("k", 16), lut_v=lut.get("v", 32), lut_bits=lut.get("bits", 8),
        lut_use_kernel=lut.get("use_kernel", False),
        lut_policy=lut.get("plan", "all_but_first"),
        param_dtype=cfg["compute"]["dtype"],
    )


def site_dims(cfg: dict[str, Any]) -> dict[str, tuple[int, int]]:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {"q": (d, hq), "k": (d, hkv), "v": (d, hkv), "o": (hq, d),
            "gate": (d, ff), "up": (d, ff), "down": (ff, d)}


def layer(cfg: dict[str, Any], i: int) -> weights.Layer:
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    sites = tuple(
        weights.Site(name, d_in, d_out, ("attn" if name in _ATTN else "mlp", name),
                     residual=name in ("o", "down"),
                     centroid_scale=CENTROID_SCALE.get(name, 1.0))
        for name, (d_in, d_out) in site_dims(cfg).items())
    return weights.Layer((("norm1", d), ("norm2", d), ("q_norm", dh), ("k_norm", dh)), sites)


def program_layer(w: dict[str, Any]) -> dict[str, Any]:
    attn = {s: w[s] for s in _ATTN}
    attn["q_norm"] = {"scale": w["q_norm"]}
    attn["k_norm"] = {"scale": w["k_norm"]}
    return {"norm1": {"scale": w["norm1"]}, "norm2": {"scale": w["norm2"]},
            "attn": attn, "mlp": {s: w[s] for s in ("gate", "up", "down")}}


def cache(segment: dict[str, Any]) -> dict[str, Any]:
    return {"k": segment["k"], "v": segment["v"]}


def attention(cfg: dict[str, Any]) -> work.Attention:
    dh = cfg["head_dim"]
    return work.Attention(cfg["num_attention_heads"], dh, dh,
                          2 * cfg["num_key_value_heads"] * dh * work.F32)
