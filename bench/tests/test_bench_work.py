"""Work counts: the algorithm's work, the same whatever implements it, and
never more than an implementation must do."""

import json

import bench_paths  # noqa: F401
import jax
import jax.numpy as jnp
import pytest
from bench_paths import BENCH, FIXTURES, ROOT
from harness import family, tracing, work
from harness.peaks import peaks

QWEN3 = family.module(BENCH / "families" / "qwen3.py")
site_dims = QWEN3.site_dims
CFG = json.loads((ROOT / "bench/configs/qwen3_1p7b-lut.json").read_text())
DENSE = json.loads((ROOT / "bench/configs/qwen3_1p7b-dense.json").read_text())
LUT_MODEL, DENSE_MODEL = family.load(CFG, BENCH), family.load(DENSE, BENCH)
V5E = peaks("TPU v5 lite")
SITES = sorted(set(site_dims(CFG).values()))


def _blocks():
    """The fixture's compiled kernel calls, one generation each: v1, v2 and
    the fused v3, as the TPU compiler emits them."""
    text = (FIXTURES / "lut_kernel_calls.hlo.txt").read_text()
    out = []
    for block in text.split("# ")[1:]:
        head, line = block.split("\n", 1)
        out.append((head, tracing.lut_calls_in_hlo(line)))
    return out


def test_every_kernel_generation_reads_as_its_site():
    blocks = _blocks()
    assert [h.split()[0] for h, _ in blocks] == ["v1", "v2", "v3", "v2", "v3"]
    for head, calls in blocks:
        (shape,) = calls.values()
        n = int(head.split()[1][2:])
        d_in, d_out = (int(x) for x in head.split()[2].split("->"))
        assert shape == (n, d_in, d_out, 16, 32)


def test_lut_site_work_is_the_same_for_every_implementation():
    # v1 (broadcast per-codebook scales), v2, v3 and the XLA path all give
    # the same (rows, site) — and the work depends on nothing else
    per_gen = {}
    for head, calls in _blocks():
        (n, d_in, d_out, k, v), = calls.values()
        w = work.lut_site_work(512, d_in, d_out, k, v, scale_elems=d_out)
        per_gen.setdefault((d_in, d_out), set()).add(w)
    xla = {(d_in, d_out): work.lut_site_work(512, d_in, d_out, 16, 32, d_out)
           for d_in, d_out in per_gen}
    for key, ws in per_gen.items():
        assert ws == {xla[key]}


def test_lut_site_work_counts():
    w = work.lut_site_work(8, 2048, 6144, 16, 32, scale_elems=6144)
    c = 2048 // 32
    assert w.flops == 2 * 8 * c * 16 * 32
    assert w.int8_ops == 8 * c * 6144
    assert w.bytes == c * 16 * 6144 + c * 16 * 32 * 4 + 6144 * 4 + 8 * (2048 + 6144) * 4


@pytest.mark.parametrize("d_in,d_out", SITES)
@pytest.mark.parametrize("n", [8, 512, 1024])
def test_lut_work_never_above_an_implementation(n, d_in, d_out):
    """The least time of the counted work is at most that of the XLA
    one-hot implementation's own operations and bytes (its cost analysis,
    at the configuration's real site shapes): a share of the roofline
    counted from it cannot pass 100% for lack of work counted."""
    from repro.core import pq

    c = d_in // 32

    def xla_site(x, cent, table, scale):
        enc = pq.hard_encode(pq.encode_scores(pq.split_subvectors(x, 32), cent))
        return pq.lut_contract_int8(enc, table, scale)

    cost = jax.jit(xla_site).lower(
        jax.ShapeDtypeStruct((n, d_in), jnp.float32),
        jax.ShapeDtypeStruct((c, 16, 32), jnp.float32),
        jax.ShapeDtypeStruct((c, 16, d_out), jnp.int8),
        jax.ShapeDtypeStruct((1, 1, d_out), jnp.float32)).cost_analysis()
    w = work.lut_site_work(n, d_in, d_out, 16, 32, scale_elems=d_out)
    assert w.flops + w.int8_ops <= cost["flops"]
    assert w.bytes <= cost["bytes accessed"]


def test_padding_rows_never_count():
    rows = [work.Row(100, 64, False), work.Row(0, 30, True)]
    padded = rows + [work.Row(0, 0, False)] * 6
    for model in (LUT_MODEL, DENSE_MODEL):
        assert work.forward_work(model, rows) == work.forward_work(model, padded)
        assert work.forward_work(model, []) == work.Work()


def test_forward_work_of_the_lut_and_dense_models_differ_only_at_sites():
    rows = [work.Row(37, 1, True)] * 3
    lut, dense = work.forward_work(LUT_MODEL, rows), work.forward_work(DENSE_MODEL, rows)
    d_sites = sum(2.0 * 3 * di * do for di, do in site_dims(CFG).values()) * 27
    l_sites = sum(2.0 * 3 * (di // 32) * 16 * 32 for di, _ in site_dims(CFG).values()) * 27
    assert dense.flops - lut.flops == pytest.approx(d_sites - l_sites)
    assert lut.int8_ops == 27 * 3 * sum((di // 32) * do for di, do in site_dims(CFG).values())


def test_kv_counts_to_the_valid_length_and_lm_head_only_where_sampled():
    a = work.forward_work(DENSE_MODEL, [work.Row(10, 1, True)])
    b = work.forward_work(DENSE_MODEL, [work.Row(20, 1, True)])
    per_key = 28 * 2 * 8 * 128 * 4
    assert b.bytes - a.bytes == pytest.approx(10 * per_key)
    no_sample = work.forward_work(DENSE_MODEL, [work.Row(0, 64, False)])
    sample = work.forward_work(DENSE_MODEL, [work.Row(0, 64, True)])
    assert sample.flops - no_sample.flops == 2.0 * 2048 * 151936


def test_least_time_picks_the_larger_bound():
    w = work.Work(flops=197e12, bytes=0.0)
    assert w.least_s(V5E) == pytest.approx(1.0) and w.bound(V5E) == "compute"
    w = work.Work(int8_ops=0.0, bytes=819e9)
    assert w.least_s(V5E) == pytest.approx(1.0) and w.bound(V5E) == "memory"


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        peaks("TPU v99")
