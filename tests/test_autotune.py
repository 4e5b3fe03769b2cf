"""Shape-keyed block autotuner: budget model, cost model, cache round-trip,
and the serving-engine warmup wiring (DESIGN.md §3)."""

import dataclasses
import json

import numpy as np
import pytest

from repro.kernels import autotune


def test_candidates_respect_vmem_budget():
    cands = list(autotune.enumerate_candidates("lut_amm", 4096, 14336, 128, 16, 32))
    assert cands, "must always yield at least one tiling"
    for c in cands:
        assert autotune.vmem_bytes(c.block_n, c.block_m, c.block_c, 16, 32) \
            <= autotune.VMEM_BUDGET
        assert 128 % c.block_c == 0


def test_predict_v2_never_slower_than_v1():
    """The analytic model must encode v2's advantage: no per-step dequant
    pass, doubled int8 MXU rate."""
    for (n, m, c, k, v) in [(256, 4096, 128, 16, 32), (8, 512, 16, 16, 8)]:
        for cand in autotune.enumerate_candidates("lut_amm", n, m, c, k, v):
            t1 = autotune.predict_us("lut_amm", n, m, c, k, v,
                                     cand.block_n, cand.block_m, cand.block_c,
                                     version=1)
            t2 = autotune.predict_us("lut_amm", n, m, c, k, v,
                                     cand.block_n, cand.block_m, cand.block_c,
                                     version=2)
            assert t2 <= t1


def test_lookup_heuristic_on_cache_miss(tmp_path):
    cache = autotune.AutotuneCache(tmp_path / "c.json")
    cfg = autotune.lookup("lut_amm", 100, 300, 8, 16, 8, cache=cache)
    assert cfg == autotune.heuristic("lut_amm", 100, 300, 8, 16, 8)


def test_tune_cache_roundtrip(tmp_path):
    """tune() persists the winner; a fresh cache object loads it back and
    lookup() serves it instead of the heuristic."""
    path = tmp_path / "cache.json"
    cache = autotune.AutotuneCache(path)
    shape = ("lut_amm", 64, 256, 16, 16, 8)
    best, rec = autotune.tune(*shape, dtype="float32", backend="cpu", cache=cache)
    assert path.exists()
    assert rec["source"] == "roofline_model" and not rec["measured"]

    fresh = autotune.AutotuneCache(path)
    got = autotune.lookup(*shape, dtype="float32", backend="cpu", cache=fresh)
    assert got == best

    # raw JSON sanity: versioned schema with the documented key format
    raw = json.loads(path.read_text())
    assert raw["version"] == 1
    key = autotune.shape_key("lut_amm", 64, 256, 16, 16, 8, "float32", "cpu")
    assert set(raw["entries"]) == {key}
    assert raw["entries"][key]["block_n"] == best.block_n


def test_tune_picks_measured_winner(tmp_path):
    """With a measure callable the tuner minimizes wall-clock, not the model."""
    cache = autotune.AutotuneCache(tmp_path / "m.json")
    # bc = C = 4: a block of the (bn, bc, V) activations is a multiple of
    # the 8-row sublane tile or the whole codebook axis
    target = autotune.BlockConfig(16, 128, 4)

    def measure(cfg):
        return 1e-6 if cfg == target else 1e-3

    best, rec = autotune.tune("lut_amm", 64, 256, 4, 16, 8,
                              cache=cache, measure=measure)
    assert best == target and rec["measured"] and rec["source"] == "wallclock"


def test_tune_sweeps_version_axis(tmp_path):
    """Kernel version is a tunable axis (DESIGN.md §13.2): the analytic
    sweep on a decode shape (8 slots) picks the fused kernel (encode charged
    once, not per M block) and records it. At prefill widths the all-of-C
    working set caps fused at small N tiles and v2 wins instead."""
    cache = autotune.AutotuneCache(tmp_path / "v.json")
    best, rec = autotune.tune("lut_amm", 8, 4096, 128, 16, 32, cache=cache)
    assert rec["version"] == autotune.VERSION_FUSED
    assert best.block_c == 128          # fused keeps the whole codebook axis
    assert not rec["measured"]


def test_tune_measured_version_wins_over_analytic_ranking(tmp_path):
    """A (cfg, version) measure callable overrides the model: if v1 times
    fastest on the live backend, the record says v1 — measured, so the
    engine/snapshot precedence never downgrades it to the analytic pick."""
    cache = autotune.AutotuneCache(tmp_path / "mv.json")

    def measure(cfg, version):
        return {1: 1e-6, 2: 1e-3, 3: 1e-3}[version]

    best, rec = autotune.tune("lut_amm", 64, 256, 4, 16, 8,
                              cache=cache, measure=measure)
    assert rec["version"] == 1 and rec["measured"]
    assert rec["source"] == "wallclock"


def test_tune_all_refused_on_tpu_raises(tmp_path):
    """On a TPU a measured sweep in which the compiler refuses every
    candidate is a failure, not a silent fall back to the model."""
    cache = autotune.AutotuneCache(tmp_path / "tpu.json")
    with pytest.raises(RuntimeError, match="refused every candidate"):
        autotune.tune("lut_amm", 64, 256, 4, 16, 8, backend="tpu", cache=cache,
                      measure=lambda cfg, ver: float("inf"))
    assert cache.load() == {}


def test_tune_all_inf_measure_falls_back_to_analytic(tmp_path):
    """Every measured candidate failing (backend can't run kernels) must
    degrade to the analytic ranking, flagged measured=False."""
    cache = autotune.AutotuneCache(tmp_path / "inf.json")
    best, rec = autotune.tune("lut_amm", 64, 256, 4, 16, 8, cache=cache,
                              measure=lambda cfg, ver: float("inf"))
    assert best is not None and not rec["measured"]
    assert rec["source"] == "roofline_model"


def test_kernel_choice_record_wins(tmp_path):
    cache = autotune.AutotuneCache(tmp_path / "kc.json")
    key = autotune.shape_key("lut_amm", 8, 128, 4, 16, 8, "float32", "cpu")
    cache.put(key, {"block_n": 8, "block_m": 128, "block_c": 4,
                    "version": 3, "measured": True})
    ver, cfg, from_rec = autotune.kernel_choice(
        8, 128, 4, 16, 8, backend="cpu", interpret=True, cache=cache)
    assert (ver, from_rec) == (3, True)
    assert cfg == autotune.BlockConfig(8, 128, 4)
    # legacy record without a "version" key means v2
    cache.put(key, {"block_n": 8, "block_m": 128, "block_c": 4})
    ver, _, _ = autotune.kernel_choice(
        8, 128, 4, 16, 8, backend="cpu", interpret=True, cache=cache)
    assert ver == 2


def test_kernel_choice_fallback_rules(tmp_path):
    """No record: interpret small-M -> v1 (the measured v2 regression);
    compiled or big-M -> fused when it fits, else v2."""
    cache = autotune.AutotuneCache(tmp_path / "fb.json")
    ver, _, from_rec = autotune.kernel_choice(
        8, 128, 4, 16, 8, backend="cpu", interpret=True, cache=cache)
    assert (ver, from_rec) == (1, False)
    ver, cfg, _ = autotune.kernel_choice(
        8, 4096, 4, 16, 8, backend="cpu", interpret=True, cache=cache)
    assert ver == autotune.VERSION_FUSED and cfg.block_c == 4
    ver, _, _ = autotune.kernel_choice(
        8, 128, 4, 16, 8, backend="tpu", interpret=False, cache=cache)
    assert ver == autotune.VERSION_FUSED
    # fused working set over budget (huge C*K*V codebook) -> v2
    ver, _, _ = autotune.kernel_choice(
        8, 4096, 4096, 16, 64, backend="tpu", interpret=False, cache=cache)
    assert ver == 2


def test_best_analytic_per_version():
    """best_analytic scores ONE version at its own legal tilings; fused
    reports (None, inf) when no all-of-C tiling fits VMEM."""
    cfg2, t2 = autotune.best_analytic("lut_amm", 8, 4096, 128, 16, 32,
                                      version=2)
    cfg3, t3 = autotune.best_analytic("lut_amm", 8, 4096, 128, 16, 32,
                                      version=3)
    assert cfg2 is not None and cfg3 is not None and t3 < t2
    cfg_bad, t_bad = autotune.best_analytic("lut_amm", 8, 4096, 4096, 16, 64,
                                            version=3)
    assert cfg_bad is None and t_bad == float("inf")


def test_corrupt_cache_degrades_gracefully(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text("{not json")
    cache = autotune.AutotuneCache(path)
    assert cache.get("anything") is None
    cfg = autotune.lookup("encode", 32, 0, 4, 16, 8, cache=cache)
    assert cfg == autotune.heuristic("encode", 32, 0, 4, 16, 8)


def test_engine_warmup_populates_cache(key, tmp_path, monkeypatch):
    """ServingEngine with a use_kernel bundle pre-tunes the decode/prefill
    LUT shapes into the autotune cache (DESIGN.md §3.3)."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "warm.json"))
    from repro.configs import build_model, get_arch, reduce_arch
    from repro.core.amm import Mode
    from repro.serving.engine import ServingEngine, iter_lut_kernel_sites

    arch = reduce_arch(get_arch("qwen3_1p7b"), n_layers=2, lut_use_kernel=True)
    bundle = build_model(arch, Mode.LUT_INFER)
    assert len(list(iter_lut_kernel_sites(bundle.cfg))) > 0

    params = bundle.init(key)
    eng = ServingEngine(bundle, params, n_slots=2, max_seq=32, prefill_chunk=8)
    assert eng.n_lut_shapes_tuned > 0
    raw = json.loads((tmp_path / "warm.json").read_text())
    assert len(raw["entries"]) == eng.n_lut_shapes_tuned
    # decode shape (N = n_slots) is among the tuned keys
    assert any("|n=2|" in k for k in raw["entries"])

    # and the engine still serves correctly through the kernel path
    eng.submit([1, 2, 3], max_tokens=3)
    done = eng.run_until_done()
    assert len(done) == 1 and len(done[0].out_tokens) == 3
    assert all(np.isfinite(t) for t in done[0].out_tokens)


def test_engine_warmup_measured_mode(key, tmp_path, monkeypatch):
    """REPRO_AUTOTUNE_MEASURE=1: warmup times candidates via
    repro.kernels.measure (stubbed here — no wall-clock in unit tests),
    marks records measured, and never re-tunes a measured record."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "meas.json"))
    monkeypatch.setenv("REPRO_AUTOTUNE_MEASURE", "1")
    from repro.configs import build_model, get_arch, reduce_arch
    from repro.core.amm import Mode
    from repro.kernels import measure
    from repro.serving.engine import ServingEngine, warm_lut_autotune

    built = []

    def fake_measure_lut_amm(n, m, c, k, v, **kw):
        built.append((n, m))
        # prefer v1 at one specific tiling so the winner is recognizable
        return lambda cfg, ver: (1e-6 if ver == 1 else 1e-3)

    monkeypatch.setattr(measure, "measure_lut_amm", fake_measure_lut_amm)

    arch = reduce_arch(get_arch("qwen3_1p7b"), n_layers=2, lut_use_kernel=True)
    bundle = build_model(arch, Mode.LUT_INFER)
    params = bundle.init(key)
    eng = ServingEngine(bundle, params, n_slots=2, max_seq=32, prefill_chunk=8)
    assert eng.n_lut_shapes_tuned > 0 and built

    raw = json.loads((tmp_path / "meas.json").read_text())
    assert all(rec["measured"] and rec["source"] == "wallclock"
               and rec["version"] == 1
               for rec in raw["entries"].values())

    # measured records are terminal: a second warmup re-measures nothing
    built.clear()
    assert warm_lut_autotune(bundle, [2, 16]) == 0
    assert built == []


def test_engine_warmup_measured_retunes_analytic_records(key, tmp_path, monkeypatch):
    """Precedence: an analytic record is RE-tuned once measurement is
    available — a measured winner always beats a projection."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "up.json"))
    from repro.configs import build_model, get_arch, reduce_arch
    from repro.core.amm import Mode
    from repro.kernels import measure
    from repro.serving.engine import warm_lut_autotune

    arch = reduce_arch(get_arch("qwen3_1p7b"), n_layers=2, lut_use_kernel=True)
    bundle = build_model(arch, Mode.LUT_INFER)
    n_analytic = warm_lut_autotune(bundle, [2])     # analytic pass
    assert n_analytic > 0
    raw = json.loads((tmp_path / "up.json").read_text())
    assert all(not rec["measured"] for rec in raw["entries"].values())

    monkeypatch.setenv("REPRO_AUTOTUNE_MEASURE", "1")
    monkeypatch.setattr(measure, "measure_lut_amm",
                        lambda *a, **kw: (lambda cfg, ver: 1e-6))
    assert warm_lut_autotune(bundle, [2]) == n_analytic
    raw = json.loads((tmp_path / "up.json").read_text())
    assert all(rec["measured"] for rec in raw["entries"].values())


def test_blockconfig_is_hashable_frozen():
    cfg = autotune.BlockConfig(8, 128, 1)
    assert hash(cfg) == hash(autotune.BlockConfig(8, 128, 1))
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.block_n = 16
