"""Compile the LUT kernels for a TPU v5e without one attached.

The TPU compiler is installed with JAX and compiles for a chip that is only
described (`jax.experimental.topologies`). What interpret mode cannot show —
a tiling that overflows the kernel's scoped VMEM, a block shape Mosaic does
not accept — fails here as it would on the chip.

The cases are the LUT sites of qwen3_1p7b as the serving engine runs them:
decode N = slots, prefill N = slots * prefill_chunk (launch/serve.py's
defaults, 4 and 32), and the four (D -> M) site widths. The compiled kernel
is the one `autotune.kernel_choice` picks for the TPU, with no record and
after the analytic `tune`.

The topology is described inside a fixture and never at import: only one
process may load the TPU library, and each test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels import autotune, ops

ARCH = get_arch("qwen3_1p7b")
SLOTS, PREFILL_CHUNK = 4, 32
SITE_WIDTHS = [
    (ARCH.d_model, ARCH.n_heads * ARCH.d_head),       # q, o: 2048 -> 2048
    (ARCH.d_model, ARCH.n_kv_heads * ARCH.d_head),    # k, v: 2048 -> 1024
    (ARCH.d_model, ARCH.d_ff),                        # gate, up: 2048 -> 6144
    (ARCH.d_ff, ARCH.d_model),                        # down: 6144 -> 2048
]
TOKENS = {"decode": SLOTS, "prefill": SLOTS * PREFILL_CHUNK}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: cannot describe here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(one_chip, n, d, m, version, cfg):
    c, k, v = d // ARCH.lut_v, ARCH.lut_k, ARCH.lut_v

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def f(x, p, t, s):
        return ops.lut_amm(x, p, t, s, version=version, block_n=cfg.block_n,
                           block_m=cfg.block_m, block_c=cfg.block_c,
                           interpret=False)

    lowered = jax.jit(f).lower(
        spec((n, d), jnp.float32), spec((c, k, v), jnp.float32),
        spec((c, k, m), jnp.int8), spec((1, 1, m), jnp.float32),
    )
    assert "tpu_custom_call" in lowered.as_text()
    return lowered.compile()


@pytest.mark.parametrize("phase", sorted(TOKENS))
@pytest.mark.parametrize("d,m", SITE_WIDTHS, ids=lambda w: str(w))
@pytest.mark.parametrize("source", ["no_record", "tuned"])
def test_qwen3_site_kernel_compiles_for_v5e(one_chip, tmp_path, source, phase, d, m):
    n = TOKENS[phase]
    c, k, v = d // ARCH.lut_v, ARCH.lut_k, ARCH.lut_v
    cache = autotune.AutotuneCache(tmp_path / "autotune.json")
    if source == "tuned":
        autotune.tune("lut_amm", n, m, c, k, v, backend="tpu", cache=cache,
                      save=False)
    version, cfg, from_record = autotune.kernel_choice(
        n, m, c, k, v, backend="tpu", interpret=False, cache=cache)
    assert from_record == (source == "tuned")
    _compile(one_chip, n, d, m, version, cfg)


@pytest.mark.parametrize("version", [2, autotune.VERSION_FUSED])
def test_kernels_compile_under_highest_default_precision(one_chip, version):
    """chip_smoke.py serves under JAX_DEFAULT_MATMUL_PRECISION=highest; the
    kernels' int8 table contraction must not inherit the fp32 precision,
    which Mosaic refuses for int8 operands."""
    d, m = ARCH.d_model, ARCH.d_model
    cfg = autotune.BlockConfig(SLOTS, 512, d // ARCH.lut_v)
    with jax.default_matmul_precision("highest"):
        _compile(one_chip, SLOTS, d, m, version, cfg)


@pytest.mark.parametrize("version,name", [(1, "lut_amm_v1"), (2, "lut_amm_v2"),
                                          (autotune.VERSION_FUSED, "lut_fused_decode")])
def test_kernel_calls_carry_their_stable_names(one_chip, version, name):
    """A trace names each kernel call by its instruction: the kernel's own
    name, whatever function or module wraps it."""
    d, m = ARCH.d_model, ARCH.d_model
    cfg = autotune.BlockConfig(SLOTS, 512, d // ARCH.lut_v)
    calls = _kernel_calls(_compile(one_chip, SLOTS, d, m, version, cfg).as_text())
    assert calls and all(c.split(".")[0] == name for c in calls), calls


def test_the_encode_kernel_carries_its_stable_name(one_chip):
    from repro.kernels import dist_argmin

    c, k, v = ARCH.d_model // ARCH.lut_v, ARCH.lut_k, ARCH.lut_v
    x = jax.ShapeDtypeStruct((SLOTS, ARCH.d_model), jnp.float32, sharding=one_chip)
    p = jax.ShapeDtypeStruct((c, k, v), jnp.float32, sharding=one_chip)
    text = jax.jit(lambda a, b: dist_argmin.encode_pallas(a, b)).lower(x, p).compile().as_text()
    calls = _kernel_calls(text)
    assert calls and all(c.split(".")[0] == "dist_argmin" for c in calls), calls


def _kernel_calls(text: str) -> list[str]:
    """Instruction names of the Pallas kernel calls in compiled HLO text."""
    return [line.split("=", 1)[0].split()[-1].lstrip("%") for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]
