"""Roofline analyzer unit tests: HLO collective parser + term math."""

import jax
import jax.numpy as jnp
import pytest

from repro.roofline.analysis import Roofline, collective_bytes, _shape_bytes


def test_shape_bytes():
    assert _shape_bytes("f32[8,16]") == 8 * 16 * 4
    assert _shape_bytes("bf16[2,3,4]{2,1,0}") == 24 * 2
    assert _shape_bytes("(s8[10], f32[2])") == 10 + 8
    assert _shape_bytes("pred[]") == 1


def test_collective_parser_on_synthetic_hlo():
    hlo = """
  %ar = f32[1024]{0} all-reduce(%x), replica_groups={}
  %ag.1 = bf16[2048]{0} all-gather(%y), dimensions={0}
  ROOT %rs = f32[512]{0} reduce-scatter(%z), dimensions={0}
  %cp = u8[100]{0} collective-permute(%w)
  %a2a = (f32[64]{0}, f32[64]{0}) all-to-all(%p, %q)
  %done = f32[1024]{0} all-reduce-done(%ar2)
"""
    c = collective_bytes(hlo)
    assert c["all-reduce"] == 1024 * 4 * 2          # 2x ring model
    assert c["all-gather"] == 2048 * 2
    assert c["reduce-scatter"] == 512 * 4
    assert c["collective-permute"] == 100
    assert c["all-to-all"] == 2 * 64 * 4


def test_terms_and_bottleneck():
    r = Roofline(flops=197e12, hbm_bytes=0, coll_bytes=0, coll_by_kind={})
    assert abs(r.t_compute - 1.0) < 1e-9
    assert r.bottleneck == "compute"
    r2 = Roofline(flops=0, hbm_bytes=819e9, coll_bytes=100e9, coll_by_kind={})
    assert r2.bottleneck == "collective"            # 2.0s vs 1.0s


def test_real_compiled_module_collectives():
    """An actually-sharded matmul must show a nonzero collective term."""
    from tests._subproc import run_with_devices
    import textwrap

    out = run_with_devices(
        textwrap.dedent(
            """
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P, NamedSharding
            from repro.roofline.analysis import analyze_compiled
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4,), ("model",))
            def f(x, w):
                return x @ w          # contraction dim sharded -> psum
            xs = jax.ShapeDtypeStruct((64, 128), jnp.float32)
            ws = jax.ShapeDtypeStruct((128, 64), jnp.float32)
            with mesh:
                c = jax.jit(
                    f,
                    in_shardings=(NamedSharding(mesh, P(None, "model")),
                                  NamedSharding(mesh, P("model", None))),
                    out_shardings=NamedSharding(mesh, P(None, None)),
                ).lower(xs, ws).compile()
            r = analyze_compiled(c)
            assert r.coll_bytes > 0, c.as_text()[:2000]
            print("COLL_BYTES", r.coll_bytes)
            """
        ),
        n_devices=4,
    )
    assert "COLL_BYTES" in out


def test_chip_peaks_table_by_device_kind():
    """Peaks are looked up by the device kind JAX reports; off the TPU the
    analytic models describe MODEL_KIND; an unknown TPU kind is an error."""
    from repro.roofline.analysis import CHIP_PEAKS, MODEL_KIND, chip_peaks

    v5e = chip_peaks("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bw) == (197e12, 819e9)
    assert chip_peaks() is CHIP_PEAKS[MODEL_KIND]       # CPU test process
    with pytest.raises(KeyError, match="TPU v99"):
        chip_peaks("TPU v99")
