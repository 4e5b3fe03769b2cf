"""Pallas kernels vs the pure-jnp oracle: shape/dtype sweep, interpret mode.

`lut_amm_pallas` is the v2 kernel (int8-native MXU table read, VMEM scratch
accumulation, fused epilogue — DESIGN.md §2.3); `lut_amm_pallas_v1` is the
original generation kept for benchmarking. Both must match the oracle.
"""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.kernels.dist_argmin import encode_pallas
from repro.kernels.lut_amm import (
    _lut_amm_kernel_v2,
    lut_amm_pallas,
    lut_amm_pallas_v1,
)
from repro.kernels.ref import encode_ref, lut_amm_ref

SHAPES = [
    # (N, D, M, K, V, block_n, block_m, block_c)
    (32, 32, 64, 16, 4, 16, 64, 4),
    (64, 64, 128, 16, 8, 32, 128, 8),
    (100, 64, 130, 16, 32, 32, 128, None),      # padding on N and M
    (17, 96, 48, 8, 32, 8, 128, 1),             # tiny blocks, K=8
    (128, 256, 512, 16, 32, 128, 256, None),    # production-ish tile
    (8, 128, 384, 16, 16, 8, 128, 2),
]

# ragged cases for the v2 acceptance sweep: N/M not multiples of the blocks,
# block_c not dividing C (the wrapper shrinks it to the next divisor)
RAGGED = [
    # (N, D, M, K, V, block_n, block_m, block_c)
    (33, 64, 70, 16, 8, 16, 64, 3),             # bc=3, C=8 -> shrinks to 2
    (100, 64, 130, 16, 32, 32, 128, None),
    (7, 96, 130, 8, 16, 8, 128, 4),             # bc=4, C=6 -> shrinks to 3
    (65, 160, 48, 16, 32, 64, 128, 5),
]


def _mk(n, d, m, k, v, seed=None, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed if seed is not None else n * d), 3)
    x = jax.random.normal(k1, (n, d), dtype)
    P = jax.random.normal(k2, (d // v, k, v), jnp.float32)
    T = jax.random.normal(k3, (d // v, k, m), jnp.float32)
    return x, P, T


@pytest.mark.parametrize("shape", SHAPES, ids=[str(s[:5]) for s in SHAPES])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lut_amm_matches_ref(shape, dtype):
    n, d, m, k, v, bn, bm, bc = shape
    x, P, T = _mk(n, d, m, k, v, dtype=dtype)
    qt = quant.quantize_table(T, bits=8)
    ref = lut_amm_ref(x, P, qt.q, qt.scale)
    out = lut_amm_pallas(
        x, P, qt.q, qt.scale, block_n=bn, block_m=bm, block_c=bc, interpret=True
    )
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("shape", SHAPES[:4], ids=[str(s[:5]) for s in SHAPES[:4]])
def test_per_column_scale_variant(shape):
    n, d, m, k, v, bn, bm, bc = shape
    x, P, T = _mk(n, d, m, k, v, seed=1 + n)
    qt = quant.quantize_table(T, bits=8, per_column=True)
    ref = lut_amm_ref(x, P, qt.q, qt.scale)
    out = lut_amm_pallas(
        x, P, qt.q, qt.scale, block_n=bn, block_m=bm, block_c=bc, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", RAGGED, ids=[str(s[:5]) for s in RAGGED])
@pytest.mark.parametrize("layout", ["per_codebook", "per_column", "m_shared"])
def test_v2_ragged_shapes_all_scale_layouts(shape, layout):
    """Acceptance sweep: v2 matches the fp32 dequantize reference within
    1e-4 on ragged shapes across every scale layout (per-codebook (C,1,1),
    per-column (C,1,M), m-shared (1,1,M) — the single-dequantize path)."""
    n, d, m, k, v, bn, bm, bc = shape
    x, P, T = _mk(n, d, m, k, v)
    kw = {"per_column": layout == "per_column", "m_shared": layout == "m_shared"}
    qt = quant.quantize_table(T, bits=8, **kw)
    ref = lut_amm_ref(x, P, qt.q, qt.scale)
    out = lut_amm_pallas(
        x, P, qt.q, qt.scale, block_n=bn, block_m=bm, block_c=bc, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("shape", SHAPES[:3], ids=[str(s[:5]) for s in SHAPES[:3]])
def test_v1_matches_ref(shape):
    n, d, m, k, v, bn, bm, bc = shape
    x, P, T = _mk(n, d, m, k, v)
    qt = quant.quantize_table(T, bits=8)
    ref = lut_amm_ref(x, P, qt.q, qt.scale)
    out = lut_amm_pallas_v1(
        x, P, qt.q, qt.scale, block_n=bn, block_m=bm, block_c=bc, interpret=True
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("act", ["none", "relu", "silu", "gelu", "relu2"])
def test_fused_bias_activation_epilogue(act):
    """bias + activation fused into the final-step epilogue == applying them
    to the oracle output."""
    import repro.models.common as common

    n, d, m, k, v = 40, 64, 100, 16, 8
    x, P, T = _mk(n, d, m, k, v, seed=7)
    b = jax.random.normal(jax.random.PRNGKey(9), (m,))
    qt = quant.quantize_table(T, m_shared=True)
    ref = lut_amm_ref(x, P, qt.q, qt.scale) + b
    if act != "none":
        ref = common.activation(act, ref)
    out = lut_amm_pallas(
        x, P, qt.q, qt.scale, bias=b, act=act,
        block_n=16, block_m=128, block_c=2, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_autotuned_default_blocks():
    """With no explicit blocks the wrapper consults the autotuner (cache miss
    -> heuristic) and still matches the oracle."""
    n, d, m, k, v = 50, 96, 75, 16, 16
    x, P, T = _mk(n, d, m, k, v, seed=3)
    qt = quant.quantize_table(T)
    ref = lut_amm_ref(x, P, qt.q, qt.scale)
    out = lut_amm_pallas(x, P, qt.q, qt.scale, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_v2_structure_no_output_rmw_single_dequant():
    """Acceptance: the v2 kernel never read-modify-writes o_ref (accumulation
    lives in the VMEM scratch) and dequantizes exactly once per output tile
    on the shared-scale path (one scale multiply, in the final epilogue)."""
    src = inspect.getsource(_lut_amm_kernel_v2)
    assert "o_ref[...] +=" not in src and "o_ref[...]+=" not in src
    # o_ref is stored exactly once (epilogue) and never read
    assert src.count("o_ref[...] =") == 1
    assert "= o_ref" not in src and "o_ref[...])" not in src
    # scratch accumulator carries the running sum instead
    assert "acc_ref[...] +=" in src


def test_v2_no_fp32_table_materialization():
    """The int8 table tile must enter the MXU contraction directly — no
    `t_ref[...].astype` dequant materialization anywhere in v2."""
    src = inspect.getsource(_lut_amm_kernel_v2)
    assert "t_ref[...].astype" not in src
    assert "preferred_element_type=jnp.int32" in src


@pytest.mark.parametrize(
    "n,d,k,v", [(32, 32, 16, 4), (100, 256, 16, 32), (7, 64, 8, 8)]
)
def test_encode_kernel_matches_ref(n, d, k, v):
    k1, k2 = jax.random.split(jax.random.PRNGKey(n))
    x = jax.random.normal(k1, (n, d))
    P = jax.random.normal(k2, (d // v, k, v))
    out = encode_pallas(x, P, block_n=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(encode_ref(x, P)))


def test_encode_autotuned_default_blocks():
    x = jax.random.normal(jax.random.PRNGKey(0), (23, 96))
    P = jax.random.normal(jax.random.PRNGKey(1), (6, 16, 16))
    out = encode_pallas(x, P, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(encode_ref(x, P)))


def test_kernel_argmin_tie_break(key):
    """Duplicate centroids: kernel must pick the lowest index like jnp."""
    P = jnp.zeros((1, 4, 4)).at[0, 1].set(1.0)      # rows 0,2,3 identical
    x = jnp.zeros((8, 4))
    out = encode_pallas(x, P, interpret=True)
    assert int(jnp.max(out)) == 0


def test_resolve_interpret_refuses_interpret_mode_on_tpu(monkeypatch):
    """Off the TPU `None` means interpret mode; on a TPU the kernels run
    compiled, and an explicit interpret=True is refused."""
    from repro.kernels import ops

    assert ops.resolve_interpret(None) is True          # CPU test process
    assert ops.resolve_interpret(False) is False
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops.resolve_interpret(None) is False
    with pytest.raises(ValueError, match="compiled"):
        ops.resolve_interpret(True)
