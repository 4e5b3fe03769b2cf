"""What the traced run records and how its trace becomes per-layer
metrics.

The benchmark records its own host spans, on the profiler's clock, by
wrapping the engine's calls (no program file changes): `bench.admit`,
`bench.prefill` and `bench.decode` (one engine forward each, sampling
included), `bench.sample`, and `bench.window` around the traced part of
the window. Device operations are attributed to a forward by the
`bench.prefill`/`bench.decode` span they start in.

The device plane's "XLA Ops" events are named by their HLO instruction
text. LUT kernel calls are found in it structurally: a `tpu_custom_call`
whose operands hold an int8 (C, K, M) table and float32 (C, K, v)
centroids. Each call's work is counted from its row count and site shape
by `harness.work.lut_site_work`, whichever kernel generation runs it.

XLA stages most of a LUT call's operands in VMEM (memory space `S(1)` in
the op text) with ops of their own that run before the call: a
dynamic-slice fusion that reads the layer's table, a copy that lays out
its input. The call's event leaves that transfer out, so the kernel's
share of its roofline charges the call with the device time of each such
producer op as the trace measured it. An operand whose producer has no
timed event of its own (a bitcast of a buffer staged earlier, an
asynchronous copy whose transfer overlaps other ops) is left out of the
bytes counted for the call, as is a result left in VMEM: no event times
their transfer.
"""

from __future__ import annotations

import dataclasses
import glob
import pathlib
import re
import shutil
import time
from typing import Any

import jax

from harness import work as work_mod

SPAN = "bench."
FORWARD_SPANS = ("bench.prefill", "bench.decode")


class CompileCounter:
    """Counts compilations (JAX lowerings) once armed."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self._armed = False
        self._n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self._armed and event == self.EVENT:
            self._n += 1

    def arm(self):
        self._armed = True

    def count(self) -> int:
        return self._n


@dataclasses.dataclass
class Forward:
    kind: str                      # "prefill" | "decode"
    rows: list                     # [work.Row] of the valid rows
    measured_s: float              # the engine's own host-clock time for it


class Probe:
    """Spans and per-forward rows for the traced run, and the profiler."""

    def __init__(self, engine, trace_dir: pathlib.Path, trace_s: float = 4.0):
        self.engine = engine
        self.trace_dir = trace_dir
        self.trace_s = trace_s
        self.forwards: list[Forward] = []
        self.recording = False
        self.traced_path: str | None = None
        self._orig = {}
        for name, kind in (("_prefill_step", "prefill"), ("_decode_step", "decode")):
            self._wrap_forward(name, kind)
        for name in ("_admit", "_sample"):
            self._wrap_span(name)

    def _wrap_span(self, name):
        orig = getattr(self.engine, name)
        self._orig[name] = orig
        label = SPAN + name.strip("_")

        def wrapped(*a, **k):
            with jax.profiler.TraceAnnotation(label):
                return orig(*a, **k)
        setattr(self.engine, name, wrapped)

    def _wrap_forward(self, name, kind):
        eng = self.engine
        orig = getattr(eng, name)
        self._orig[name] = orig
        counter = f"{kind}_s"

        def wrapped():
            rows = forward_rows(eng, kind)
            before = eng._counters[counter]
            n_before = eng._counters[f"{kind}_forwards"]
            with jax.profiler.TraceAnnotation(SPAN + kind):
                out = orig()
            ran = eng._counters[f"{kind}_forwards"] > n_before
            if ran and self.recording:
                self.forwards.append(Forward(kind, rows, eng._counters[counter] - before))
            return out
        setattr(eng, name, wrapped)

    def during(self, t0: float, t_end: float) -> None:
        """Record forwards for the whole window; trace a part of it that ends
        well before the window does."""
        self.recording = True
        seconds = t_end - t0
        span = min(self.trace_s, 0.3 * seconds)
        start = t0 + 0.4 * seconds
        time.sleep(max(0.0, start - time.perf_counter()))
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.trace_dir))
        with jax.profiler.TraceAnnotation(SPAN + "window"):
            time.sleep(span)
        jax.profiler.stop_trace()
        found = glob.glob(str(self.trace_dir / "**" / "*.xplane.pb"), recursive=True)
        self.traced_path = found[0] if found else None
        time.sleep(max(0.0, t_end - time.perf_counter()))
        self.recording = False

    def release(self) -> None:
        for name in self._orig:
            self.engine.__dict__.pop(name, None)
        self.engine = None


def forward_rows(eng, kind: str) -> list:
    """The valid rows the engine's next forward of `kind` will carry."""
    rows = []
    for i, r in enumerate(eng.slots):
        if r is None:
            continue
        if kind == "prefill" and not r.prefill_done:
            new = min(eng.prefill_chunk, len(r.prompt) - r.n_prefilled)
            rows.append(work_mod.Row(r.n_prefilled, new,
                                     r.n_prefilled + new >= len(r.prompt)))
        elif kind == "decode" and r.prefill_done:
            rows.append(work_mod.Row(int(eng.cache_len[i]), 1, True))
    return rows


# ---------------------------------------------------------------------------
# compiled step programs: which custom calls are LUT kernels, on what shapes
# ---------------------------------------------------------------------------

_SHAPE = re.compile(r"\b(s8|f32|bf16|s32|f16)\[([0-9,]*)\]")
_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OPERANDS = re.compile(r"operand_layout_constraints=\{(.*?)\}, \w+=")


def _shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(text)]


def lut_calls_in_hlo(text: str) -> dict[str, tuple[int, int, int, int, int]]:
    """{instruction name: (N, d_in, d_out, K, v)} of every LUT kernel call
    in compiled HLO text: a tpu_custom_call with an int8 (C, K, M) table,
    float32 (C, K, v) centroids and N rows of d_in = C * v inputs."""
    out = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name, ops = _NAME.match(line), _OPERANDS.search(line)
        if not name or not ops:
            continue
        shapes = _shapes(ops.group(1))
        tables = [s for dt, s in shapes if dt == "s8" and len(s) == 3]
        if not tables:
            continue
        c, k, d_out = tables[0]
        cents = [s for dt, s in shapes if dt == "f32" and len(s) == 3 and s[:2] == (c, k)]
        if not cents:
            continue
        v = cents[0][2]
        # the rows: (N, C, v) or (N, d_in), the first operand
        dt, x = shapes[0]
        if len(x) == 3 and x[1:] == (c, v):
            n = x[0]
        elif len(x) == 2 and x[1] == c * v:
            n = x[0]
        else:
            continue
        out[name.group(1)] = (n, c * v, d_out, k, v)
    return out


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Event:
    name: str
    start: float          # seconds on the profiler's clock
    dur: float
    lut: tuple | None = None   # (N, d_in, d_out, K, v) of a LUT kernel call
    # of a LUT call: (role, bytes as laid out, staged in VMEM?, producer op)
    # for its result ("output") and each operand
    operands: tuple = ()

    def __post_init__(self):
        if self.lut is not None:
            self.lut = tuple(self.lut)
        self.operands = tuple(tuple(o) for o in self.operands)


@dataclasses.dataclass
class Trace:
    """A trace as the reduction needs it: device operations of one chip and
    the benchmark's host spans."""

    device_ops: list[Event]
    spans: list[Event]


def trace_to_json(tr: Trace) -> dict:
    return {"device_ops": [dataclasses.astuple(e) for e in tr.device_ops],
            "spans": [dataclasses.astuple(e) for e in tr.spans]}


def trace_from_json(d: dict) -> Trace:
    return Trace([Event(*e) for e in d["device_ops"]], [Event(*e) for e in d["spans"]])


_ITEM = {"s8": 1, "f32": 4, "bf16": 2, "s32": 4, "f16": 2}
_ARRAY = r"\b(s8|f32|bf16|s32|f16)\[([0-9,]*)\]\{([^}]*)\}"
_OPERAND = re.compile(_ARRAY + r" %([\w.\-]+)")
_RESULT = re.compile(r"=\s*" + _ARRAY)


def _nbytes(dt: str, dims: str) -> int:
    n = _ITEM[dt]
    for x in dims.split(","):
        n *= int(x) if x else 1
    return n


def lut_operands(text: str, lut: tuple) -> tuple:
    """A LUT call's result and operands from its op text: (role, bytes as
    laid out, staged in VMEM?, producer op name). Roles: "output", and
    "input" (the first operand), "centroids", "table", "other" (scales,
    bias)."""
    n, d_in, d_out, k, v = lut
    c = d_in // v
    head, _, rest = text.partition(" custom-call(")
    res = _RESULT.search(head)
    out = []
    if res:
        dt, dims, layout = res.groups()
        out.append(("output", _nbytes(dt, dims), "S(1)" in layout, None))
    for i, (dt, dims, layout, prod) in enumerate(
            _OPERAND.findall(rest.split("), custom_call_target")[0])):
        shape = tuple(int(x) for x in dims.split(",") if x)
        role = ("input" if i == 0 else "table" if dt == "s8" and shape == (c, k, d_out)
                else "centroids" if shape == (c, k, v) else "other")
        out.append((role, _nbytes(dt, dims), "S(1)" in layout, prod))
    return tuple(out)


def op_event(text: str, start_ns: float, dur_ns: float) -> Event:
    """A device op from its HLO instruction text: its short name, and the
    LUT call it makes, if any, with its operands."""
    m = _NAME.match(text)
    name = m.group(1) if m else text.split(" ", 1)[0]
    lut = lut_calls_in_hlo(text).get(name) if "tpu_custom_call" in text else None
    return Event(name, start_ns * 1e-9, dur_ns * 1e-9, lut,
                 lut_operands(text, lut) if lut else ())


def load_trace(path: str) -> Trace:
    pdata = jax.profiler.ProfileData.from_file(path)
    ops, spans = [], []
    for plane in pdata.planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    ops.append(op_event(ev.name, ev.start_ns, ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN):
                        spans.append(Event(ev.name, ev.start_ns * 1e-9,
                                           ev.duration_ns * 1e-9))
    return Trace(ops, spans)


def union_s(intervals: list[tuple[float, float]]) -> float:
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted(intervals):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def _clip(ev: Event, a: float, b: float):
    lo, hi = max(ev.start, a), min(ev.start + ev.dur, b)
    return (lo, hi) if hi > lo else None


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    ops: list[tuple[Event, str | None]]      # device ops in the window, by forward kind
    idle_gaps: list[tuple[str, float]]       # (what the host was doing, seconds)


def _innermost(spans: list[Event], t: float) -> str | None:
    best = None
    for s in spans:
        if s.start <= t <= s.start + s.dur and s.name != SPAN + "window":
            if best is None or s.dur < best.dur:
                best = s
    return None if best is None else best.name[len(SPAN):]


def reduce(tr: Trace) -> Reduced | None:
    windows = [s for s in tr.spans if s.name == SPAN + "window"]
    if not windows or not tr.device_ops:
        return None
    w0 = windows[0].start
    w1 = w0 + windows[0].dur
    fwd = sorted((s for s in tr.spans if s.name in FORWARD_SPANS), key=lambda s: s.start)
    clipped = [(ev, c) for ev in tr.device_ops if (c := _clip(ev, w0, w1))]
    busy = union_s([c for _, c in clipped])
    ops = []
    for ev, _ in clipped:
        kind = None
        for s in fwd:
            if s.start <= ev.start <= s.start + s.dur:
                kind = s.name[len(SPAN):]
                break
        ops.append((ev, kind))
    # idle gaps: the complement of the busy union inside the window
    gaps, cursor = [], w0
    for a, b in sorted(c for _, c in clipped):
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if cursor < w1:
        gaps.append((cursor, w1))
    idle = [(_innermost(tr.spans, (a + b) / 2) or "outside engine calls", b - a)
            for a, b in gaps]
    return Reduced(w1 - w0, busy, ops, idle)


# ---------------------------------------------------------------------------
# the shares the metric readers report
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    cell: Any
    cfg: dict
    settings: dict
    stats: dict
    sent: list
    t0: float
    t_end: float
    forwards: list | None
    trace: Reduced | None
    peaks: Any
    scale_shapes: dict


def step_share(ctx: Context, kind: str) -> float | None:
    fs = [f for f in (ctx.forwards or []) if f.kind == kind]
    measured = sum(f.measured_s for f in fs)
    if not fs or measured <= 0 or ctx.peaks is None:
        return None
    least = sum(work_mod.forward_work(ctx.cell.model, f.rows, ctx.scale_shapes)
                .least_s(ctx.peaks) for f in fs)
    return 100.0 * least / measured


def _timed(ev: Event | None) -> bool:
    """Whether an op's event times the transfer it makes: not the start or
    the wait of an asynchronous copy, whose transfer runs between them."""
    return ev is not None and ev.dur > 0 and not re.search(r"-(start|done)\b", ev.name)


def kernel_share(ctx: Context, kind: str) -> float | None:
    """Least time of the LUT calls' work inside `kind` forwards over their
    device time: each call's event, plus the timed producer ops of its
    operands staged in VMEM (each producer once, shared or not)."""
    if ctx.trace is None or ctx.peaks is None:
        return None
    least = dur = 0.0
    last: dict[str, Event] = {}
    charged: set[int] = set()
    names = ctx.cell.model.lut_site_names()
    for ev, k in sorted(ctx.trace.ops, key=lambda x: x[0].start):
        if ev.lut is None or k != kind:
            last[ev.name] = ev
            continue
        n, d_in, d_out, kk, v = ev.lut
        # sites of one shape (q and o, k and v, gate and up) scale alike
        name = names[d_in, d_out]
        parts = work_mod.lut_site_bytes(n, d_in, d_out, kk, v,
                                        work_mod.scale_elems(ctx.scale_shapes, name, d_out))
        moved = 0.0
        for role, nbytes, staged, prod in ev.operands:
            src = last.get(prod) if staged and prod else None
            if staged and not _timed(src):
                continue              # no event times this transfer
            if src is not None and id(src) not in charged:
                charged.add(id(src))
                dur += src.dur
            moved += parts.get(role, nbytes)
        w = work_mod.lut_site_work(n, d_in, d_out, kk, v,
                                   work_mod.scale_elems(ctx.scale_shapes, name, d_out))
        least += work_mod.Work(w.flops, w.int8_ops, moved).least_s(ctx.peaks)
        dur += ev.dur
        last[ev.name] = ev
    return None if dur <= 0 else 100.0 * least / dur


def leaf_ops(ops: list[Event]) -> list[Event]:
    """Ops that contain no other op (a while loop's event spans its body's
    ops, which would count twice)."""
    ops = sorted(ops, key=lambda e: (e.start, -e.dur))
    out = []
    for i, ev in enumerate(ops):
        end = ev.start + ev.dur
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt.start < end and nxt.start + nxt.dur <= end:
            continue
        out.append(ev)
    return out


def breakdown(red: Reduced) -> dict[str, list]:
    by_op: dict[str, float] = {}
    for ev in leaf_ops([ev for ev, _ in red.ops]):
        by_op[ev.name] = by_op.get(ev.name, 0.0) + ev.dur
    top = sorted(by_op.items(), key=lambda x: -x[1])[:10]
    gaps = sorted(red.idle_gaps, key=lambda x: -x[1])[:10]
    return {"device_ops": [[n, s] for n, s in top],
            "idle_gaps": [[n, s] for n, s in gaps]}
