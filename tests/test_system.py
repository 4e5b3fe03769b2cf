"""End-to-end behaviour tests for the LUT-NN system (paper claims in
miniature — the full-size counterparts live in benchmarks/)."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pq, quant
from repro.core.amm import LUTConfig, Mode, dense_flops, lut_flops, lut_linear
from repro.core.lut_layer import deploy_params, init_dense, lut_train_params_from_dense


def test_flops_reduction_matches_table1(key):
    """Paper Table 1/section 6.2: reduction = M / (K + M/V)."""
    n, d, m = 1024, 768, 3072                      # BERT FFN up-projection
    cfg = LUTConfig(k=16, v=32)
    red = dense_flops(n, d, m) / lut_flops(n, d, m, cfg)
    expect = m / (cfg.k + m / cfg.v)
    assert abs(red - expect) < 1e-9
    assert red > 26                                # paper: up to 16x e2e, more per-op


def test_lut_approximates_clustered_activations(key):
    """On inputs with cluster structure (the paper's premise), LUT-AMM with
    k-means centroids approximates the dense op well; on the same data with
    random centroids it does not."""
    k1, k2, k3 = jax.random.split(key, 3)
    d, m, n_clusters = 64, 96, 16
    centers = jax.random.normal(k1, (n_clusters, d))
    x = centers[jax.random.randint(k2, (512,), 0, n_clusters)]
    x = x + 0.05 * jax.random.normal(k2, (512, d))
    dense = init_dense(k3, d, m)
    cfg = LUTConfig(k=16, v=8)
    y_ref = lut_linear(cfg, Mode.DENSE, dense, x)

    trainable, frozen = lut_train_params_from_dense(k3, dense, x, cfg)
    dep = deploy_params(trainable, frozen, cfg)
    y_lut = lut_linear(cfg, Mode.LUT_INFER, dep, x)
    rel = float(jnp.linalg.norm(y_lut - y_ref) / jnp.linalg.norm(y_ref))
    assert rel < 0.30, rel

    rnd = dict(dep, centroids=jax.random.normal(k1, dep["centroids"].shape))
    tbl = pq.build_table(rnd["centroids"], frozen["w"], stop_weight_grad=False)
    qt = quant.quantize_table(tbl)
    rnd.update(table_q=qt.q, table_scale=qt.scale)
    y_rnd = lut_linear(cfg, Mode.LUT_INFER, rnd, x)
    rel_rnd = float(jnp.linalg.norm(y_rnd - y_ref) / jnp.linalg.norm(y_ref))
    assert rel < 0.5 * rel_rnd, (rel, rel_rnd)


def test_int8_table_accuracy_claim(key):
    """Section 6.3: INT8 table ~ FP32 table accuracy (0.04% drop there)."""
    k1, k2, k3 = jax.random.split(key, 3)
    d, m = 64, 128
    centers = jax.random.normal(k1, (16, d))
    x = centers[jax.random.randint(k2, (256,), 0, 16)] + 0.05 * jax.random.normal(k2, (256, d))
    dense = init_dense(k3, d, m)
    cfg8 = LUTConfig(k=16, v=8, bits=8)
    trainable, frozen = lut_train_params_from_dense(k3, dense, x, cfg8)
    y_ref = lut_linear(cfg8, Mode.DENSE, dense, x)

    tbl = pq.build_table(trainable["centroids"], frozen["w"], stop_weight_grad=False)
    enc = pq.hard_encode(
        pq.pairwise_sq_dists(pq.split_subvectors(x, cfg8.v), trainable["centroids"])
    )
    y_fp32 = pq.lut_contract(enc, tbl)
    dep = deploy_params(trainable, frozen, cfg8)
    y_int8 = lut_linear(cfg8, Mode.LUT_INFER, dep, x)

    e_fp = float(jnp.linalg.norm(y_fp32 - y_ref))
    e_i8 = float(jnp.linalg.norm(y_int8 - y_ref))
    assert e_i8 < 1.05 * e_fp + 1e-3               # int8 adds <5% extra error


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: it stands and nothing else is set.
    Unset: the cache goes to the fixed, git-ignored <checkout>/.jax_cache."""
    import pathlib

    from repro.launch import compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.init_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.init_compile_cache()
        root = pathlib.Path(__file__).resolve().parents[1]
        assert path == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_serve_random_init_is_published_arch_unless_cut():
    """`launch/serve.py --arch X` serves the published width; only
    --layers/--d-model/--vocab cut it down."""
    import argparse

    from repro.configs import get_arch
    from repro.launch.serve import _random_init_arch

    def args(**kw):
        base = dict(arch="qwen3_1p7b", use_kernel=True, layers=None,
                    d_model=None, vocab=None)
        return argparse.Namespace(**{**base, **kw})

    published = get_arch("qwen3_1p7b")
    arch = _random_init_arch(args())
    assert (arch.n_layers, arch.d_model, arch.vocab, arch.lut_v) == (
        published.n_layers, published.d_model, published.vocab, published.lut_v)
    assert arch.lut_use_kernel
    small = _random_init_arch(args(layers=2, d_model=64, vocab=128))
    assert (small.n_layers, small.d_model, small.vocab) == (2, 64, 128)
