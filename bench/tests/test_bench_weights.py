"""The weights and the reference of the Qwen3 configurations, pinned: the
program's parameter tree and the reference's weights, cached keys and
values and final hidden states at a seed hash to what they did before the
family modules took the model's layout out of the harness. A change here
changes what the benchmark reads on every cell."""

import hashlib
import json

import bench_paths  # noqa: F401
import jax
import numpy as np
import pytest
from bench_paths import BENCH, FIXTURES
from harness import check, family, weights

HIGHEST = jax.lax.Precision.HIGHEST
SEED = 2**31 + 11
PINNED = {
    "tiny-lut": {
        "params": "da8fa9c2873c661dfdae5afe455fa1db001c28b185307da8c0c7058ab3449268",
        "ref_weights": "5542f072fd6a9b359942069b0ff11a9be97569b90db605daf6a9c730a18a18a2",
        "ref_kv": "25e045d6efe9175fabf7d168875842ca8b395d51f2108f4b415c443b30f79ad5",
        "ref_final": "0c7b3c4c4a54553432473e27dbb006192c18a2435aa8d1caae5b31ad5281b6ee"},
    "tiny-dense": {
        "params": "f6d6a99b8143b9a53f94d15e5923d9fdc49f1b7475ef7bfe60105033bbcf8e6e",
        "ref_weights": "a0731eb55a66787f00482827a11378010c45a75feb3bbaf31b1440b54332cb56",
        "ref_kv": "705ed433ed6056ad3787b48bb5f4f17e67164d6276207dfa8ae64392eddf31cb",
        "ref_final": "5cd1e9d7ea60a8f39243d3f20078bfbecf23b1179de1b8c16b0bd03aa522fdba"},
}


def digest(tree) -> str:
    """sha256 over each leaf's path, dtype, shape and bytes, in tree order."""
    m = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            m.update(part.encode())
        m.update(a.tobytes())
    return m.hexdigest()


@pytest.mark.parametrize("name", sorted(PINNED))
def test_weights_and_reference_are_pinned(name):
    from repro.configs import build_model
    from repro.core.amm import Mode

    cfg = json.loads((FIXTURES / "configs" / f"{name}.json").read_text())
    model = family.load(cfg, BENCH)
    bundle = build_model(model.family.arch(cfg), Mode.LUT_INFER if cfg.get("lut") else Mode.DENSE)
    specs = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    _, shapes = weights.program_layout(model, specs)
    ref = check.Reference(model, SEED, shapes, pad_len=64)
    layers = [ref._gen[key](ref.key, i) for i, key in enumerate(zip(ref.specs, ref.lut))]
    rng = np.random.default_rng(5)
    seqs = [rng.integers(1, 256, 40).tolist(), rng.integers(1, 256, 17).tolist()]
    kv = {}

    def on_layer(i, j, cached):
        kv[f"{i}/{j}"] = (cached[HIGHEST]["k"], cached[HIGHEST]["v"])
    with jax.default_matmul_precision("highest"):
        final = ref.forward(seqs, (HIGHEST,), on_layer)[HIGHEST]
        params = weights.program_params(model, SEED, specs)
    got = {"params": digest(params),
           "ref_weights": digest({"layers": layers, "globals": ref.g}),
           "ref_kv": digest(kv), "ref_final": digest(final)}
    assert got == PINNED[name]
