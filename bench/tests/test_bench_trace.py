"""The trace reduction: busy union, idle share, idle gaps by what the host
was doing, and attribution of device operations to forwards."""

import bench_paths  # noqa: F401
import pytest
from harness import tracing
from harness.tracing import Event, Trace

HAND = Trace(
    device_ops=[Event("fusion.1", 0.12, 0.08), Event("lut.2", 0.18, 0.10),
                Event("fusion.3", 0.31, 0.03), Event("late", 1.1, 0.1),
                Event("early", -0.05, 0.10)],
    spans=[Event("bench.window", 0.0, 1.0), Event("bench.prefill", 0.1, 0.2),
           Event("bench.decode", 0.3, 0.05), Event("bench.sample", 0.35, 0.01)])


def test_union_of_overlapping_intervals():
    assert tracing.union_s([(0, 1), (0.5, 2), (3, 4), (3.5, 3.6)]) == pytest.approx(3.0)
    assert tracing.union_s([]) == 0.0


def test_busy_and_idle_share_by_hand():
    red = tracing.reduce(HAND)
    assert red.window_s == pytest.approx(1.0)
    # [0, 0.05] + [0.12, 0.28] + [0.31, 0.34], clipped to the window
    assert red.busy_s == pytest.approx(0.24)
    assert 1 - red.busy_s / red.window_s == pytest.approx(0.76)


def test_attribution_to_forwards():
    red = tracing.reduce(HAND)
    kinds = {ev.name: k for ev, k in red.ops}
    assert kinds == {"fusion.1": "prefill", "lut.2": "prefill", "fusion.3": "decode",
                     "early": None}


def test_idle_gaps_are_labelled_by_the_host_span():
    red = tracing.reduce(HAND)
    gaps = sorted(red.idle_gaps, key=lambda g: -g[1])
    assert gaps[0] == ("outside engine calls", pytest.approx(0.66))
    assert ("prefill", pytest.approx(0.03)) in gaps
    assert sum(g for _, g in red.idle_gaps) == pytest.approx(red.window_s - red.busy_s)


def test_breakdown_lists_at_most_ten():
    ops = [Event(f"op{i}", i * 0.01, 0.001 * (i + 1)) for i in range(30)]
    tr = Trace(ops, [Event("bench.window", 0.0, 1.0)])
    b = tracing.breakdown(tracing.reduce(tr))
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["device_ops"][0][0] == "op29"


def test_json_round_trip():
    assert tracing.trace_from_json(tracing.trace_to_json(HAND)) == HAND


# --- a trace recorded on the chip: one prefill and one decode forward -------

def _recorded():
    import gzip
    import json

    from bench_paths import FIXTURES

    with gzip.open(FIXTURES / "trace_lut_chat.json.gz", "rt") as f:
        return tracing.trace_from_json(json.load(f))


def test_recorded_busy_union_matches_a_sampled_timeline():
    import numpy as np

    tr = _recorded()
    red = tracing.reduce(tr)
    grid = np.zeros(int(red.window_s * 1e6) + 1, bool)          # 1 us cells
    for ev in tr.device_ops:
        a, b = max(ev.start, 0.0), min(ev.start + ev.dur, red.window_s)
        if b > a:
            grid[int(a * 1e6): int(np.ceil(b * 1e6))] = True
    assert red.busy_s == pytest.approx(grid.sum() * 1e-6, rel=2e-3)
    assert 0 < red.busy_s < red.window_s


def test_recorded_attribution_finds_every_lut_site_in_each_forward():
    red = tracing.reduce(_recorded())
    for kind, n in (("prefill", 512), ("decode", 8)):
        calls = [ev for ev, k in red.ops if k == kind and ev.lut is not None]
        assert len(calls) == 27 * 7                      # LUT layers x sites
        assert {ev.lut[0] for ev in calls} == {n}


def _kernel_scales(cell):
    """The kernel path's m-shared table-scale layout of each LUT site."""
    return {s.name: (1, 1, s.d_out) for s in cell.model.layers()[1].sites}


def test_recorded_kernel_shares_stay_under_100():
    from bench_paths import ROOT

    from harness import spec
    from harness.peaks import peaks

    cell = spec.Benchmark(ROOT).cell("qwen3_1p7b-lut.chat")
    shapes = _kernel_scales(cell)
    ctx = tracing.Context(cell=cell, cfg=cell.config, settings=cell.settings, stats={},
                          sent=[], t0=0.0, t_end=0.0, forwards=None,
                          trace=tracing.reduce(_recorded()), peaks=peaks("TPU v5 lite"),
                          scale_shapes=shapes)
    for kind in ("prefill", "decode"):
        share = tracing.kernel_share(ctx, kind)
        assert 0 < share <= 100


def test_staged_bytes_are_the_vmem_operands():
    text = ("%k.1 = f32[8,2048]{1,0:T(8,128)S(1)} custom-call(f32[8,64,32]{2,1,0:T(8,128)} %a, "
            "f32[64,16,32]{2,1,0:T(8,128)S(1)} %b, s8[64,16,2048]{2,1,0:T(8,128)(4,1)S(1)} %c, "
            "f32[1,1,2048]{2,1,0:T(1,128)} %d), custom_call_target=\"tpu_custom_call\", "
            "operand_layout_constraints={f32[8,64,32]{2,1,0}, f32[64,16,32]{2,1,0}, "
            "s8[64,16,2048]{2,1,0}, f32[1,1,2048]{2,1,0}}, frontend_attributes={}")
    ev = tracing.op_event(text, 0.0, 1000.0)
    assert ev.name == "k.1" and ev.lut == (8, 2048, 2048, 16, 32)
    assert ev.operands == (("output", 8 * 2048 * 4, True, None),
                           ("input", 8 * 2048 * 4, False, "a"),
                           ("centroids", 64 * 16 * 32 * 4, True, "b"),
                           ("table", 64 * 16 * 2048, True, "c"),
                           ("other", 2048 * 4, False, "d"))
    staged = sum(b for _, b, st, _ in ev.operands if st)
    assert staged == 8 * 2048 * 4 + 64 * 16 * 32 * 4 + 64 * 16 * 2048


def _share_of(ops, cfg_cell="qwen3_1p7b-lut.chat"):
    from bench_paths import ROOT

    from harness import spec
    from harness.peaks import peaks

    cell = spec.Benchmark(ROOT).cell(cfg_cell)
    shapes = _kernel_scales(cell)
    tr = Trace(ops, [Event("bench.window", 0.0, 1.0), Event("bench.decode", 0.0, 1.0)])
    ctx = tracing.Context(cell=cell, cfg=cell.config, settings=cell.settings, stats={},
                          sent=[], t0=0.0, t_end=0.0, forwards=None,
                          trace=tracing.reduce(tr), peaks=peaks("TPU v5 lite"),
                          scale_shapes=shapes)
    return tracing.kernel_share(ctx, "decode"), ctx.peaks


def test_a_staged_table_charges_its_producer_and_an_untimed_one_is_left_out():
    lut = (8, 2048, 2048, 16, 32)
    table, rest = 64 * 16 * 2048, 2 * 8 * 2048 * 4 + 64 * 16 * 32 * 4 + 2048 * 4
    ops = [("output", 8 * 2048 * 4, False, None), ("input", 8 * 2048 * 4, False, "x"),
           ("centroids", 64 * 16 * 32 * 4, False, "c"), ("table", table, True, "stage.1"),
           ("other", 2048 * 4, False, "s")]
    call = Event("k.1", 0.5, 20e-6, lut, ops)
    # the table's producer timed: its bytes count and its time is charged
    share, p = _share_of([Event("stage.1", 0.4, 10e-6), call])
    assert share == pytest.approx(100 * (table + rest) / p.hbm_bytes / 30e-6)
    # an asynchronous copy's wait does not time the transfer: the table's
    # bytes are left out, and so is its time
    share, p = _share_of([Event("copy-done.3", 0.4, 1e-9), Event("k.1", 0.5, 20e-6, lut,
                          [o if o[0] != "table" else ("table", table, True, "copy-done.3")
                           for o in ops])])
    assert share == pytest.approx(100 * rest / p.hbm_bytes / 20e-6)
    # one producer feeding two calls is charged once
    two = [Event("stage.1", 0.4, 10e-6), call, Event("k.2", 0.6, 20e-6, lut, ops)]
    share, p = _share_of(two)
    assert share == pytest.approx(100 * (2 * (table + rest)) / p.hbm_bytes / 50e-6)
