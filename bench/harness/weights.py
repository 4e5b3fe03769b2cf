"""The benchmark's own weights for a configuration, made from the seed.

The values come from here and not from the program: the reference
(`bench/reference/`) makes the same values again, layer by layer, from the
same seed, and so takes nothing the program made. `program_params` packs
them into the program's parameter tree in one jitted call on the device.

What a layer holds is its family's (`bench/families/<model_type>.py`,
`Layer`): named norms and named linear sites, each site with its widths,
where the program's layer tree keeps it, and whether it is stacked over
experts. A layer's weights, as the reference reads them, are
{norm name: (n,), site name: site}.

A dense site is {"w": (d_in, d_out)}; a LUT site is {"centroids": (C, K, v),
"table_q": int8 (C, K, M), "table_scale": the scale layout the program
serves}. An expert-stacked site puts the experts first: {"w": (E, d_in,
d_out)}, or one set of centroids (C, K, v) that every expert shares with
tables (E, C, K, M), as the program serves them. Scales: dense weights
N(0, 1/d_in); LUT tables uniform int8 with a scale that gives each output
about unit variance; centroids N(0, s) with s the scale of the activations
they encode (the site's `centroid_scale`).

The output sites of each residual branch (`residual`) after the first
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

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

_INT8_STD = 73.6          # std of a uniform integer on [-127, 127]
BRANCH_SCALE = 0.003


@dataclasses.dataclass(frozen=True)
class Site:
    """One linear site of a layer."""

    name: str
    d_in: int
    d_out: int
    path: tuple[str, ...]          # where the program's layer tree holds it
    lut: bool = True               # replaced by a LUT in a layer the plan replaces
    residual: bool = False         # writes the residual stream
    centroid_scale: float = 1.0    # scale of the activations it encodes
    experts: int = 0               # stacked over this many experts (0: not)
    top_k: int = 0                 # experts each token visits


@dataclasses.dataclass(frozen=True)
class Layer:
    """What one layer holds: (name, size) of each norm, and its sites."""

    norms: tuple[tuple[str, int], ...]
    sites: tuple[Site, ...]


def base_key(seed: int) -> jax.Array:
    """A key from any whole seed up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


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


def norm(key, n):
    return 1.0 + 0.05 * jax.random.normal(key, (n,), jnp.float32)


def site(key, s: Site, lut, scale_shape, out_scale):
    """A site's weights: dense where `lut` is None, else a LUT whose table
    scales have `scale_shape`."""
    experts = (s.experts,) if s.experts else ()
    if lut is None:
        w = jax.random.normal(key, experts + (s.d_in, s.d_out), jnp.float32)
        return {"w": w * (out_scale / s.d_in ** 0.5)}
    k, v = lut["k"], lut["v"]
    c = s.d_in // v
    kc, kt, ks = (jax.random.fold_in(key, i) for i in range(3))
    scale = out_scale / (_INT8_STD * c ** 0.5)
    return {
        "centroids": s.centroid_scale * jax.random.normal(kc, (c, k, v), jnp.float32),
        "table_q": jax.random.randint(kt, experts + (c, k, s.d_out), -127, 128, jnp.int8),
        "table_scale": scale * (1.0 + 0.05 * jax.random.normal(ks, scale_shape, jnp.float32)),
    }


def gen_globals(cfg, seed_key) -> dict[str, Any]:
    """The weights outside the layers, as the reference reads them: a tied
    embedding and the final norm (a family whose model holds more gives its
    own `gen_globals`)."""
    d = cfg["hidden_size"]
    emb = jax.random.normal(jax.random.fold_in(seed_key, 1),
                            (cfg["vocab_size"], d), jnp.float32)
    return {"embed": 0.02 * emb, "final_norm": norm(jax.random.fold_in(seed_key, 2), d)}


def program_globals(g: dict[str, Any]) -> dict[str, Any]:
    """`gen_globals`'s weights in the program's tree."""
    return {"embed": {"table": g["embed"]}, "final_norm": {"scale": g["final_norm"]}}


def gen_layer(spec: Layer, cfg, seed_key, i, lut: bool, scale_shapes) -> dict[str, Any]:
    """Layer `i`'s weights (reference layout), `spec` its structure. `i`
    may be traced."""
    key = jax.random.fold_in(seed_key, 1000 + i)
    out = {name: norm(jax.random.fold_in(key, j), n) for j, (name, n) in enumerate(spec.norms)}
    branch = jnp.where(i >= 1, BRANCH_SCALE, 1.0)
    for j, s in enumerate(spec.sites):
        here = lut and s.lut
        out[s.name] = site(jax.random.fold_in(key, 10 + j), s,
                           cfg["lut"] if here else None,
                           scale_shapes.get(s.name) if here else None,
                           branch if s.residual else 1.0)
    return out


# ---------------------------------------------------------------------------
# the program's layout
# ---------------------------------------------------------------------------

def at(tree, path: tuple[str, ...]):
    for k in path:
        tree = tree[k]
    return tree


def replace(tree, path: tuple[str, ...], value):
    """A copy of nested dicts `tree` with `value` at `path`."""
    if not path:
        return value
    return dict(tree, **{path[0]: replace(tree[path[0]], path[1:], value)})


def program_layout(model, param_specs) -> tuple[list[tuple[int, int, bool]], dict]:
    """Map the program's parameter tree onto the configuration: per segment
    (first layer, layer count, LUT?) and the table-scale shape of each LUT
    site. Raises where the program's tree and the configuration's plan
    disagree, or where one segment holds layers of different structure."""
    cfg, fam = model.cfg, model.family
    lut = lut_layers(cfg)
    segs, scale_shapes, first = [], {}, 0
    for seg in param_specs["segments"]:
        n = jax.tree.leaves(seg)[0].shape[0]
        spec = fam.layer(cfg, first)
        if any(fam.layer(cfg, i) != spec for i in range(first + 1, first + n)):
            raise ValueError(f"layers {first}..{first + n - 1} share one program "
                             f"segment but differ in the family's layout")
        is_lut = any("table_q" in at(seg, s.path) for s in spec.sites)
        if any(x != is_lut for x in lut[first:first + n]):
            raise ValueError(f"layers {first}..{first + n - 1}: the program "
                             f"serves them {'as LUTs' if is_lut else 'dense'}, "
                             f"the configuration's plan does not")
        for s in spec.sites:
            leaf = at(seg, s.path)
            if is_lut and "table_q" in leaf:
                scale_shapes[s.name] = tuple(leaf["table_scale"].shape[1:])
        segs.append((first, n, is_lut))
        first += n
    if first != cfg["num_hidden_layers"]:
        raise ValueError(f"the program has {first} layers, the configuration "
                         f"{cfg['num_hidden_layers']}")
    return segs, scale_shapes


def program_params(model, seed: int, param_specs) -> Any:
    """The program's parameter tree, made on the device in one jitted call."""
    cfg, fam = model.cfg, model.family
    segs, scale_shapes = program_layout(model, param_specs)

    def make(key):
        segments = []
        for first, n, is_lut in segs:
            spec = fam.layer(cfg, first)
            layer = jax.vmap(lambda i: fam.program_layer(
                gen_layer(spec, cfg, key, i, is_lut, scale_shapes)))
            segments.append(layer(jnp.arange(first, first + n)))
        return dict(model.program_globals(model.gen_globals(key)), segments=segments)

    want = jax.tree.map(lambda s: (s.shape, s.dtype), param_specs)
    got = jax.tree.map(lambda s: (s.shape, s.dtype),
                       jax.eval_shape(make, jax.ShapeDtypeStruct((2,), jnp.uint32)))
    if jax.tree.structure(want) != jax.tree.structure(got) or want != got:
        raise ValueError("the program's parameter tree is not the one this "
                         "benchmark packs: update the family's module")
    return jax.jit(make)(base_key(seed))
