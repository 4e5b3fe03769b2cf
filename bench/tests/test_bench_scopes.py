"""The program's named scopes and host spans in a trace: the scope of each
device op read from the serialized trace, each scope's share of the
forwards' device time, the host time of the steps the trace recorded, and
the per-layer metrics that read them and the program's counters. On the
recorded chip trace, two checks of the program's spans that no reader
needs: every idle gap of the device falls inside one of them, and they
give each device op the forward kind the benchmark's spans give it."""

import gzip
import json

import bench_paths  # noqa: F401
import pytest
from bench_paths import FIXTURES, ROOT
from harness import scopes, spec, tracing
from harness.scopes import ScopedEvent
from harness.tracing import Event, Trace

STEP_SHARES = ("kv_write", "lm_head", "attention", "linear")
# the pump thread is inside one of these from its first step to its last
PUMP_THREAD = ("pump.step", "pump.dispatch", "pump.idle")
OUTSIDE = "outside engine calls"
# a gap before the pump thread's first span or after its last: the spans
# open when the trace started or stopped are not recorded
EDGE = "trace edge"


def _innermost(spans, t):
    best = None
    for s in spans:
        if s.start <= t <= s.start + s.dur and s.name != tracing.SPAN + "window":
            if best is None or s.dur < best.dur:
                best = s
    if best is None:
        return OUTSIDE
    return best.name[len(tracing.SPAN):] if best.name.startswith(tracing.SPAN) else best.name


def idle_gaps(tr, red):
    """The device's idle gaps inside the window, each named by the
    innermost host span (the program's or the benchmark's) at its middle;
    EDGE before the pump thread's first span or after its last."""
    pump = [s for s in tr.spans if s.name in PUMP_THREAD]
    first = min((s.start for s in pump), default=None)
    last = max((s.start + s.dur for s in pump), default=None)

    def label(t):
        if first is not None and not first <= t <= last:
            return EDGE
        return _innermost(tr.spans, t)

    w0 = next(s.start for s in tr.spans if s.name == tracing.SPAN + "window")
    w1 = w0 + red.window_s
    clipped = ((max(ev.start, w0), min(ev.start + ev.dur, w1)) for ev in tr.device_ops)
    gaps, cursor = [], w0
    for a, b in sorted((a, b) for a, b in clipped if b > a):
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if cursor < w1:
        gaps.append((cursor, w1))
    return [(label((a + b) / 2), b - a) for a, b in gaps]


def forward_kinds(tr, prefix, suffix=""):
    """The forward kind ("prefill", "decode" or None) of each device op, by
    the `<prefix><kind><suffix>` span it starts in: `bench.` as
    `tracing.reduce` attributes ops, or `engine.` and `.forward` for the
    program's own dispatch-to-ready spans."""
    fwd = sorted(((s, kind) for kind in ("prefill", "decode") for s in tr.spans
                  if s.name == f"{prefix}{kind}{suffix}"), key=lambda x: x[0].start)
    return [next((k for s, k in fwd if s.start <= ev.start <= s.start + s.dur), None)
            for ev in tr.device_ops]


def _reduced(ops, spans=()):
    tr = Trace(ops, [Event("bench.window", 0.0, 1.0), Event("bench.prefill", 0.0, 0.5),
                     Event("bench.decode", 0.5, 0.4), *spans])
    return tr, tracing.reduce(tr)


def test_scope_path_keeps_the_listed_scopes_of_the_ops_own_name():
    assert scopes.scope_path("jit(step_fn)/while/body/closed_call/attention/linear/"
                             "jit(_lut_amm_call_v2)/lut_amm_v2/pallas_call") == "attention/linear"
    assert scopes.scope_path("jit(step_fn)/kv_write/jit(_where)/select_n") == "kv_write"
    # merged instructions: the first name is the op's own
    assert scopes.scope_path("a/linear/reshape;attention/reshape") == "linear"
    assert scopes.scope_path("") == scopes.scope_path("jit(f)/add") == ""


def test_the_innermost_listed_scope_owns_an_op():
    assert scopes.owner("attention/linear") == "linear"
    assert scopes.owner("attention/kv_write") == "kv_write"
    assert scopes.owner("kv_write/lm_head") == "lm_head"
    assert scopes.owner("") is None and scopes.owner("sample") is None


def test_scope_shares_by_hand():
    ops = [ScopedEvent("q", 0.00, 0.10, scope="attention/linear"),
           ScopedEvent("sc", 0.10, 0.05, scope="attention"),
           ScopedEvent("kv", 0.15, 0.05, scope="attention/kv_write"),
           ScopedEvent("merge", 0.55, 0.20, scope="kv_write"),
           ScopedEvent("copy", 0.75, 0.05),                          # no metadata
           ScopedEvent("head", 0.80, 0.05, scope="lm_head"),
           # a loop whose event spans its body's ops: counted through them
           ScopedEvent("while", 0.00, 0.20, scope="attention"),
           # outside every forward: not counted
           ScopedEvent("late", 0.95, 0.04, scope="linear")]
    _, red = _reduced(ops)
    sh = scopes.scope_shares(red)
    total = 0.10 + 0.05 + 0.05 + 0.20 + 0.05 + 0.05
    assert sh["linear"] == pytest.approx(100 * 0.10 / total)
    assert sh["attention"] == pytest.approx(100 * 0.05 / total)
    assert sh["kv_write"] == pytest.approx(100 * 0.25 / total)
    assert sh["lm_head"] == pytest.approx(100 * 0.05 / total)
    assert sh[None] == pytest.approx(100 * 0.05 / total)            # unscoped apart
    assert sum(sh.values()) == pytest.approx(100.0)


def test_a_trace_without_scopes_reads_nothing():
    _, red = _reduced([Event("fusion.1", 0.1, 0.1), Event("copy.2", 0.6, 0.1)])
    assert scopes.scope_shares(red) is None


def test_idle_gaps_are_named_by_the_pump_threads_spans():
    ops = [ScopedEvent("a", 0.1, 0.1), ScopedEvent("b", 0.3, 0.1), ScopedEvent("c", 0.6, 0.1)]
    spans = [Event("bench.window", 0.0, 1.0),
             Event("pump.idle", 0.02, 0.01), Event("pump.step", 0.05, 0.5),
             Event("engine.step", 0.06, 0.44), Event("engine.prefill.forward", 0.08, 0.35),
             Event("pump.dispatch", 0.55, 0.1), Event("pump.idle", 0.65, 0.15)]
    tr = Trace(ops, spans)
    red = tracing.reduce(tr)
    gaps = sorted((round(d, 9), n) for n, d in idle_gaps(tr, red))
    assert gaps == [(0.1, "engine.prefill.forward"), (0.1, "pump.step"),
                    (0.2, "engine.step"), (0.3, EDGE)]     # after the last span
    assert sum(d for d, _ in gaps) == pytest.approx(red.window_s - red.busy_s)


# --- the serialized trace ------------------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _plane(name: str, ops: dict, ref: bool = False) -> bytes:
    stat_meta = (_field(5, _field(1, 7) + _field(2, _field(1, 7) + _field(2, "tf_op")))
                 + _field(5, _field(1, 8) + _field(2, _field(1, 8) + _field(2, "flops"))))
    body = _field(2, name) + stat_meta
    for i, (op, tf_op) in enumerate(ops.items(), start=100):
        if ref:      # the value interned as a stat metadata name
            body += _field(5, _field(1, i) + _field(2, _field(1, i) + _field(2, tf_op)))
            value = _field(1, 7) + _field(7, i)
        else:
            value = _field(1, 7) + _field(5, tf_op)
        meta = (_field(1, i) + _field(2, op) + _field(5, _field(1, 8) + _field(3, 12))
                + _field(5, value))
        body += _field(4, _field(1, i) + _field(2, meta))
    body += _field(3, _field(2, "XLA Ops"))                     # a line, skipped
    return _field(1, body)


@pytest.mark.parametrize("ref", [False, True])
def test_op_names_are_read_from_the_device_plane(tmp_path, ref):
    want = {"%fusion.46 = f32[8] fusion()": "jit(step_fn)/lm_head/dot_general",
            "%broadcast_select_fusion = f32[8] fusion()": "jit(step_fn)/kv_write/select_n"}
    data = (_plane("/host:CPU", {"%x = f32[1] add()": "jit(g)/linear/add:"})
            + _plane("/device:TPU:0", {k: v + ":" for k, v in want.items()}, ref))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(data)
    assert scopes.op_names(str(path)) == want


# --- one engine step recorded on the chip ---------------------------------------

def _recorded():
    with gzip.open(FIXTURES / "trace_lut_chat_scopes.json.gz", "rt") as f:
        return scopes.trace_from_json(json.load(f))


def test_recorded_shares_cover_the_forwards():
    tr = _recorded()
    red = tracing.reduce(scopes.bench_only(tr))
    for kind in ("prefill", "decode"):
        sub = tracing.Reduced(red.window_s, red.busy_s,
                              [(e, k) for e, k in red.ops if k == kind], [])
        sh = scopes.scope_shares(sub)
        assert all(sh.get(s, 0) > 0 for s in STEP_SHARES), (kind, sh)
        assert sum(sh.get(s, 0) for s in STEP_SHARES) + sh[None] \
            == pytest.approx(100.0, abs=0.5)


def test_recorded_idle_gaps_all_have_an_owner():
    tr = _recorded()
    red = tracing.reduce(scopes.bench_only(tr))
    gaps = idle_gaps(tr, red)
    assert gaps and all(n not in (OUTSIDE, EDGE) for n, _ in gaps)
    # the gaps `tracing.reduce` finds, named by the program's spans
    assert sorted(d for _, d in gaps) == pytest.approx(sorted(d for _, d in red.idle_gaps))
    assert all(n.startswith(scopes.PROGRAM_SPANS) for n, _ in gaps)


def test_recorded_program_spans_agree_with_the_benchmarks():
    tr = _recorded()
    bench = forward_kinds(tr, "bench.")
    step = forward_kinds(tr, "engine.")
    fwd = forward_kinds(tr, "engine.", ".forward")
    assert bench == step
    assert {"prefill", "decode"} <= set(fwd)
    # the dispatch-to-ready span holds every op of its forward but the
    # slicing and sampling of the logits that follow it, which carry no
    # step scope
    assert all(f is None or f == b for f, b in zip(fwd, bench))
    assert all(b is None or f == b or scopes.owner(ev.scope) is None
               for ev, f, b in zip(tr.device_ops, fwd, bench))


def test_the_recorded_fixture_keeps_the_old_reduction():
    red = tracing.reduce(scopes.bench_only(_recorded()))
    for kind, n in (("prefill", 512), ("decode", 8)):
        calls = [ev for ev, k in red.ops if k == kind and ev.lut is not None]
        assert len(calls) == 27 * 7 and {ev.lut[0] for ev in calls} == {n}
        assert all(scopes.owner(ev.scope) == "linear" for ev in calls)


# --- the readers -----------------------------------------------------------------

def _ctx(stats, trace=None):
    return tracing.Context(cell=None, cfg={}, settings={}, stats=stats, sent=[], t0=0.0,
                           t_end=0.0, forwards=None, trace=trace, peaks=None,
                           scale_shapes={})


PROGRAM_STATS = {"submits": 4, "submit_wait_s": 2.0, "submit_wait_max_s": 1.5,
                 "admitted": 5, "queue_wait_s": 0.5, "steps": 10, "step_s": 1.0,
                 "prefill_s": 0.3, "decode_s": 0.5}
# what the program kept before it counted waits and step time
OLD_STATS = {"steps": 10, "prefill_s": 0.3, "decode_s": 0.5}


@pytest.mark.parametrize("name,want", [("submit_wait_ms", 500.0), ("queue_wait_ms", 100.0),
                                       ("submit_wait_ms.chat", 500.0),
                                       ("queue_wait_ms.chat", 100.0),
                                       ("step_host_ms", 20.0)])
def test_counter_readers(name, want):
    read = spec.load_reader(name, ROOT)
    assert read(_ctx(PROGRAM_STATS)) == pytest.approx(want)
    assert read(_ctx(OLD_STATS)) is None
    assert read(_ctx(dict(PROGRAM_STATS, submits=0, admitted=0, steps=0))) is None


def test_step_host_time_leaves_out_the_traced_steps(monkeypatch):
    spans = [Event("engine.step", 0.10, 0.05), Event("engine.prefill", 0.11, 0.03),
             Event("engine.prefill.forward", 0.115, 0.02),
             Event("engine.step", 0.20, 0.04), Event("engine.decode.forward", 0.21, 0.01),
             # its step began before the trace did: not a step the trace holds
             Event("engine.decode.forward", 0.001, 0.01)]
    tr = Trace([], spans)
    n, host = scopes.step_host_s(tr)
    assert n == 2 and host == pytest.approx((0.05 - 0.02) + (0.04 - 0.01))
    monkeypatch.setattr(scopes, "traced", lambda ctx: (tr, None))
    # ten steps hold 0.2 s of host time; the two traced ones 0.06 s of it
    got = spec.load_reader("step_host_ms", ROOT)(_ctx(PROGRAM_STATS, trace=object()))
    assert got == pytest.approx(1e3 * (0.2 - 0.06) / 8)


@pytest.mark.parametrize("scope", STEP_SHARES)
def test_share_readers_stay_silent_without_a_device_trace(scope):
    assert spec.load_reader(f"step_share.{scope}", ROOT)(_ctx(PROGRAM_STATS)) is None


def test_share_readers_read_the_runs_trace(monkeypatch):
    tr = _recorded()
    red = tracing.reduce(scopes.bench_only(tr))
    monkeypatch.setattr(scopes, "traced", lambda ctx: (tr, red))
    got = {s: spec.load_reader(f"step_share.{s}", ROOT)(_ctx({}, red)) for s in STEP_SHARES}
    sh = scopes.scope_shares(red)
    assert got == {s: pytest.approx(sh[s]) for s in STEP_SHARES}
    assert 99.5 <= sum(got.values()) + sh[None] <= 100.0 + 1e-9


def test_the_new_metrics_are_declared_with_their_cells():
    bench = spec.Benchmark(ROOT)
    names = {m.name: m for m in bench.per_layer}
    for s in STEP_SHARES:
        m = names[f"step_share.{s}"]
        assert m.moves == "gap_p95_ms" and len(m.workloads) == 3
    for n in ("submit_wait_ms", "queue_wait_ms"):
        assert names[n].moves == "tokens_per_s"
        assert names[n].workloads == ("qwen3_1p7b-lut.batch",)
        assert names[f"{n}.chat"].moves == "gap_p95_ms"
        assert names[f"{n}.chat"].workloads == ("qwen3_1p7b-lut.chat", "qwen3_1p7b-dense.chat")
        assert names[f"{n}.chat"].layer == names[n].layer
    assert len(names["step_host_ms"].workloads) == 3
    # every cell a metric lists reports the end-to-end metric it moves
    for m in bench.per_layer:
        for w in m.workloads or ():
            assert m.moves in {e.name for e in bench.metrics_for(w, trace=False)}, (m.name, w)
