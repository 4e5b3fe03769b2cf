"""A model family is added by files alone: a second family, defined under
this test's own data directory (`fixtures/moe/`: a configuration, a family
module, a reference, a traffic mix and cells), runs through `run.main` on
the CPU with no edit to the harness, reads correct, and reads not correct
under planted faults. Its block is the program's sparse-expert block with
every expert taking every token, so nothing is dropped."""

import json

import bench_paths  # noqa: F401
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_paths import FIXTURES, ROOT
from harness import check, faults, spec, tracing, weights, work
from harness.peaks import peaks

import run

MOE = FIXTURES / "moe"
CELLS = {"tiny-moe.chat": "tiny-moe", "tiny-moe-dense.chat": "tiny-moe-dense"}


def _benchmark():
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    data["configs"] = [{"name": n, "source": "test", "file": str(MOE / "configs" / f"{n}.json"),
                        "reduced": [], "why": "test"} for n in sorted(set(CELLS.values()))]
    data["workloads"] = [{"name": w, "config": c, "traffic": "tiny-chat", "chips": 1,
                          "why": "test"} for w, c in CELLS.items()]
    data["per_layer"] = [{k: v for k, v in m.items() if k != "workloads"}
                         for m in data["per_layer"]]
    return spec.Benchmark(ROOT, data=data, data_dir=MOE)


BENCH = _benchmark()


@pytest.fixture(autouse=True)
def _autotune_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))


def _run(capsys, workload, hook=None, trace=0):
    rc = run.main(["--workload", workload, "--seed", str(2**33 + 17), "--seconds", "2",
                   "--trace", str(trace)], require_chip=False, engine_hook=hook, bench=BENCH)
    out = capsys.readouterr()
    assert rc == 0, out.err
    return json.loads(out.out.strip().splitlines()[-1]), out


def test_the_family_and_reference_come_from_the_data_directory():
    model = BENCH.cell("tiny-moe.chat").model
    assert model.family.__file__ == str(MOE / "families" / "tiny_moe.py")
    assert model.reference.__file__ == str(MOE / "reference" / "tiny_moe.py")
    names = [s.name for s in model.layers()[1].sites]
    assert names[:5] == ["q", "k", "v", "o", "router"] and "shared_down" in names
    assert set(faults.for_model(model)) >= {"drop_gate", "drop_shared_up", "deep_shared_down"}
    assert "drop_router" not in faults.for_model(model)       # the router stays dense


def test_expert_stacked_sites_pack_into_the_programs_tree():
    from repro.configs import build_model
    from repro.core.amm import Mode

    model = BENCH.cell("tiny-moe.chat").model
    bundle = build_model(model.family.arch(model.cfg), Mode.LUT_INFER)
    specs = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    segs, scales = weights.program_layout(model, specs)
    assert segs == [(0, 1, False), (1, 2, True)]
    assert scales["gate"] == (2, 1, 1, 64) and scales["shared_gate"] == (1, 1, 64)
    params = weights.program_params(model, 5, specs)
    moe = params["segments"][1]["moe"]
    assert moe["gate"]["table_q"].shape == (2, 2, 2, 16, 64)
    # the experts share centroids and differ in their tables
    assert not np.array_equal(moe["gate"]["table_q"][:, 0], moe["gate"]["table_q"][:, 1])
    assert params["segments"][0]["moe"]["down"]["w"].shape == (1, 2, 64, 64)


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_a_sound_run_of_a_second_family_is_correct(capsys, workload):
    res, out = _run(capsys, workload)
    assert res["correct"] is True, out.err
    assert all(c["value"] <= c["limit"] for c in res["check"].values())
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("workload,fault", [
    ("tiny-moe.chat", "state_unchanged"), ("tiny-moe.chat", "token_altered"),
    ("tiny-moe-dense.chat", "drop_gate"), ("tiny-moe-dense.chat", "deep_shared_down")])
def test_a_broken_run_of_a_second_family_is_not_correct(capsys, workload, fault):
    hook = faults.for_model(BENCH.cell(workload).model)[fault]
    res, out = _run(capsys, workload, hook=hook)
    assert res["correct"] is False, out.err
    assert any(c["value"] is not None and c["value"] > c["limit"]
               for c in res["check"].values())


def _site(experts=0, top_k=0):
    return weights.Layer((), (weights.Site("s", 64, 96, ("s",), experts=experts, top_k=top_k),))


@pytest.mark.parametrize("lut", [None, {"k": 16, "v": 32}])
def test_expert_sites_count_each_token_once_per_expert_it_visits(lut):
    plain = work.sites_work(_site(), lut, 5)
    # one expert taking every token is a plain site
    assert work.sites_work(_site(1, 1), lut, 5) == plain
    both = work.sites_work(_site(2, 2), lut, 5)
    one = work.sites_work(_site(4, 1), lut, 1)          # one token reads one expert
    if lut is None:
        assert both == work.Work(2 * plain.flops, 0.0, 2 * plain.bytes)
        assert one.bytes == 64 * 96 * work.F32
    else:
        c = 64 // 32
        assert both.flops == plain.flops                 # one encode: shared centroids
        assert both.int8_ops == 2 * plain.int8_ops
        assert both.bytes == c * 16 * 32 * 4 + 2 * (c * 16 * 96 + 96 * 4)
        assert one.bytes == c * 16 * 32 * 4 + c * 16 * 96 + 96 * 4


def test_the_familys_work_reaches_the_step_share():
    cell = BENCH.cell("tiny-moe.chat")
    fwd = [tracing.Forward("decode", [work.Row(10, 1, True)] * 3, 1e-3)]
    ctx = tracing.Context(cell=cell, cfg=cell.config, settings=cell.settings, stats={},
                          sent=[], t0=0.0, t_end=0.0, forwards=fwd, trace=None,
                          peaks=peaks("TPU v5 lite"), scale_shapes={})
    least = work.forward_work(cell.model, fwd[0].rows).least_s(ctx.peaks)
    assert tracing.step_share(ctx, "decode") == pytest.approx(100.0 * least / 1e-3)


class _LatentReference:
    """A reference whose layers cache one latent leaf, (S, 1, 576)."""

    lut = [False, True, True]

    def __init__(self, rows):
        self.rows = rows

    def forward(self, seqs, precisions, on_layer):
        for i in range(len(self.lut)):
            for j in range(len(seqs)):
                on_layer(i, j, {p: {"latent": self.rows[i, j]} for p in precisions})
        return {p: [] for p in precisions}


def test_a_latent_cache_leaf_is_compared_by_its_own_name():
    rng = np.random.default_rng(3)
    rows = jnp.asarray(rng.normal(size=(3, 2, 40, 1, 576)), jnp.float32)
    seqs = [[1] * 40, [2] * 25]
    ref = _LatentReference(rows)
    nums, stats = check.check_served(ref, [], seqs, {"latent": rows})
    assert nums == {"kv_dense": 0.0, "kv1_share": 0.0, "kv_jump": 0.0}
    assert stats.rows.tolist() == [65, 65, 65]
    # one row of layer 1 off by 1%, and a row past the valid length of the
    # second sequence that counts nothing
    bad = rows.at[1, 0, 7, 0].multiply(1.01).at[1, 1, 30].set(9.0)
    nums, _ = check.check_served(ref, [], seqs, {"latent": bad})
    assert nums["kv1_share"] == pytest.approx(1 / 65) and nums["kv_jump"] == pytest.approx(1 / 65)
    assert nums["kv_dense"] == 0.0
    err = np.asarray(check._row_err(bad[1, 0], rows[1, 0], 40))
    assert err.shape == (40, 1) and err[7, 0] == pytest.approx(0.01, rel=1e-4)
