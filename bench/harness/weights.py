"""The benchmark's own weights for a configuration, made from the seed.

The values come from here and not from the program: the reference
(`bench/reference/`) makes the same values again, layer by layer, from the
same seed, and so takes nothing the program made. `program_params` packs
them into the program's parameter tree in one jitted call on the device.

Layout of one layer, as the reference reads it:

    {"norm1": (d,), "norm2": (d,), "q_norm": (dh,), "k_norm": (dh,),
     "q": site, "k": site, "v": site, "o": site,
     "gate": site, "up": site, "down": site}

A dense site is {"w": (d_in, d_out)}; a LUT site is {"centroids": (C, K, v),
"table_q": int8 (C, K, M), "table_scale": the scale layout the program
serves}. Scales: dense weights N(0, 1/d_in); LUT tables uniform int8 with a
scale that gives each output about unit variance; centroids N(0, s) with s
the scale of the activations they encode (normalised inputs 1, attention
outputs 0.5, the MLP's hidden 0.6).

The output sites of each residual branch (`o`, `down`) after the first
layer are scaled by BRANCH_SCALE, as initialisations of deep residual
networks scale them down. A random LUT model is chaotic: a centroid code
that float32 rounding decides differently in two correct implementations
(an encode that lands within rounding of a tie) changes its site's output
by a tenth or so, which moves codes in the next layers, and the change
spreads to every later position. On one v5e at published width, with
unscaled branches, the served tokens of the kernel path lay up to 3.8
logits below the reference's best; at 0.003 none did (PERF.md, PR 12).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

SITES = ("q", "k", "v", "o", "gate", "up", "down")
_ATTN = ("q", "k", "v", "o")
_INT8_STD = 73.6          # std of a uniform integer on [-127, 127]
BRANCH_SCALE = 0.003
CENTROID_SCALE = {"o": 0.5, "down": 0.6}      # the rest: 1.0


def base_key(seed: int) -> jax.Array:
    """A key from any whole seed up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def site_dims(cfg: dict[str, Any]) -> dict[str, tuple[int, int]]:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {"q": (d, hq), "k": (d, hkv), "v": (d, hkv), "o": (hq, d),
            "gate": (d, ff), "up": (d, ff), "down": (ff, d)}


def lut_layers(cfg: dict[str, Any]) -> list[bool]:
    """Which layers serve their linear sites as LUTs, by the configuration's
    plan."""
    n = cfg["num_hidden_layers"]
    lut = cfg.get("lut")
    if lut is None:
        return [False] * n
    plan = lut["plan"]
    if plan == "all":
        return [True] * n
    if plan == "all_but_first":
        return [False] + [True] * (n - 1)
    raise ValueError(f"unknown LUT plan {plan!r}")


def _norm(key, n):
    return 1.0 + 0.05 * jax.random.normal(key, (n,), jnp.float32)


def _site(key, name, d_in, d_out, lut, scale_shape, out_scale):
    if lut is None:
        w = jax.random.normal(key, (d_in, d_out), jnp.float32)
        return {"w": w * (out_scale / d_in ** 0.5)}
    k, v = lut["k"], lut["v"]
    c = d_in // v
    kc, kt, ks = (jax.random.fold_in(key, i) for i in range(3))
    s = out_scale / (_INT8_STD * c ** 0.5)
    return {
        "centroids": CENTROID_SCALE.get(name, 1.0)
        * jax.random.normal(kc, (c, k, v), jnp.float32),
        "table_q": jax.random.randint(kt, (c, k, d_out), -127, 128, jnp.int8),
        "table_scale": s * (1.0 + 0.05 * jax.random.normal(ks, scale_shape, jnp.float32)),
    }


def gen_layer(cfg, seed_key, i, lut: bool, scale_shapes) -> dict[str, Any]:
    """Layer `i`'s weights (reference layout). `i` may be traced."""
    key = jax.random.fold_in(seed_key, 1000 + i)
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    out = {
        "norm1": _norm(jax.random.fold_in(key, 0), d),
        "norm2": _norm(jax.random.fold_in(key, 1), d),
        "q_norm": _norm(jax.random.fold_in(key, 2), dh),
        "k_norm": _norm(jax.random.fold_in(key, 3), dh),
    }
    branch = jnp.where(i >= 1, BRANCH_SCALE, 1.0)
    for j, (name, (d_in, d_out)) in enumerate(site_dims(cfg).items()):
        out[name] = _site(jax.random.fold_in(key, 10 + j), name, d_in, d_out,
                          cfg["lut"] if lut else None,
                          scale_shapes.get(name) if lut else None,
                          branch if name in ("o", "down") else 1.0)
    return out


def gen_globals(cfg, seed_key) -> dict[str, Any]:
    d = cfg["hidden_size"]
    emb = jax.random.normal(jax.random.fold_in(seed_key, 1),
                            (cfg["vocab_size"], d), jnp.float32)
    return {"embed": 0.02 * emb, "final_norm": _norm(jax.random.fold_in(seed_key, 2), d)}


# ---------------------------------------------------------------------------
# the program's layout
# ---------------------------------------------------------------------------

def _to_program_layer(w: dict[str, Any]) -> dict[str, Any]:
    attn = {s: w[s] for s in _ATTN}
    attn["q_norm"] = {"scale": w["q_norm"]}
    attn["k_norm"] = {"scale": w["k_norm"]}
    return {"norm1": {"scale": w["norm1"]}, "norm2": {"scale": w["norm2"]},
            "attn": attn, "mlp": {s: w[s] for s in ("gate", "up", "down")}}


def program_layout(cfg, param_specs) -> tuple[list[tuple[int, int, bool]], dict]:
    """Map the program's parameter tree onto the configuration: per segment
    (first layer, layer count, LUT?) and the table-scale shape of each LUT
    site. Raises where the program's tree and the configuration's plan
    disagree."""
    lut = lut_layers(cfg)
    segs, scale_shapes, first = [], {}, 0
    for seg in param_specs["segments"]:
        n = jax.tree.leaves(seg)[0].shape[0]
        is_lut = "table_q" in seg["attn"]["q"]
        if any(x != is_lut for x in lut[first:first + n]):
            raise ValueError(f"layers {first}..{first + n - 1}: the program "
                             f"serves them {'as LUTs' if is_lut else 'dense'}, "
                             f"the configuration's plan does not")
        if is_lut:
            for s in SITES:
                part = seg["attn"] if s in _ATTN else seg["mlp"]
                scale_shapes[s] = tuple(part[s]["table_scale"].shape[1:])
        segs.append((first, n, is_lut))
        first += n
    if first != cfg["num_hidden_layers"]:
        raise ValueError(f"the program has {first} layers, the configuration "
                         f"{cfg['num_hidden_layers']}")
    return segs, scale_shapes


def program_params(cfg, seed: int, param_specs) -> Any:
    """The program's parameter tree, made on the device in one jitted call."""
    segs, scale_shapes = program_layout(cfg, param_specs)

    def make(key):
        segments = []
        for first, n, is_lut in segs:
            layer = jax.vmap(lambda i: _to_program_layer(
                gen_layer(cfg, key, i, is_lut, scale_shapes)))
            segments.append(layer(jnp.arange(first, first + n)))
        g = gen_globals(cfg, key)
        return {"embed": {"table": g["embed"]}, "segments": segments,
                "final_norm": {"scale": g["final_norm"]}}

    want = jax.tree.map(lambda s: (s.shape, s.dtype), param_specs)
    got = jax.tree.map(lambda s: (s.shape, s.dtype),
                       jax.eval_shape(make, jax.ShapeDtypeStruct((2,), jnp.uint32)))
    if jax.tree.structure(want) != jax.tree.structure(got) or want != got:
        raise ValueError("the program's parameter tree is not the one this "
                         "benchmark packs: update bench/harness/weights.py")
    return jax.jit(make)(base_key(seed))
