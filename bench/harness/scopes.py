"""What the program records about itself in a traced run: the named scopes
of its device ops and its own host spans.

The program puts each device op of its step under a named scope
(`jax.named_scope`): `linear` (every linear site, LUT or dense),
`attention`, `kv_write` (the scatter of each forward's new rows into the
cache the step was given) and `lm_head`. XLA writes the scope path into
each instruction's `op_name` metadata, and the TPU profiler keeps it as
the `tf_op` stat of the op's event metadata in the device plane.
`jax.profiler.ProfileData` does not expose event metadata, so `op_names`
reads it from the serialized trace with a minimal protobuf reader. An op
that XLA inserted without metadata (a layout `copy`) has no scope.

The program's host spans are `engine.*` (step, admit, prefill, decode,
their `.forward` dispatch-to-ready intervals, sample) and `pump.*` (step,
dispatch, idle). `step_host_s` reads the engine's steps among them, to
take the traced window's steps out of `step_host_ms`: the profiler slows
the host while it records.

Nothing here changes what `harness.tracing` reads: its forwards are the
benchmark's `bench.*` spans, and this module hands `tracing.reduce` those
spans alone.
"""

from __future__ import annotations

import dataclasses
import functools
import glob
import os
import pathlib

import jax

from harness import tracing

TRACE_DIR = pathlib.Path(__file__).resolve().parents[1] / ".trace"
PROGRAM_SPANS = ("engine.", "pump.")
# the scopes a step's device time is split into; an op belongs to the
# innermost of them on its path
STEP_SCOPES = ("linear", "attention", "kv_write", "lm_head")


@dataclasses.dataclass
class ScopedEvent(tracing.Event):
    # the listed scopes on the op's metadata path, outermost first,
    # "/"-joined ("attention/kv_write"); "" for an op with none
    scope: str = ""


def trace_from_json(d: dict) -> tracing.Trace:
    return tracing.Trace([ScopedEvent(*e) for e in d["device_ops"]],
                         [tracing.Event(*e) for e in d["spans"]])


# ---------------------------------------------------------------------------
# the serialized trace: device op text -> op_name
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a serialized protobuf
    message: an int for a varint, a memoryview for anything delimited."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, value


def _map_entries(entries) -> dict[int, memoryview]:
    out = {}
    for entry in entries:
        f = dict(_fields(entry))
        out[f.get(1, 0)] = f.get(2, memoryview(b""))
    return out


def op_names(path: str, plane_prefix: str = "/device:TPU:0") -> dict[str, str]:
    """{device op text: its `op_name` metadata} for the ops of the device
    plane (tensorflow/tsl xplane.proto: XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .str_value = 5, .ref_value = 7;
    XStatMetadata.name = 2). The profiler appends ":" to each op_name."""
    data = memoryview(pathlib.Path(path).read_bytes())
    out: dict[str, str] = {}
    for num, plane in _fields(data):
        if num != 1:
            continue
        name, events, stats = "", [], []
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(v)
            elif f == 5:
                stats.append(v)
        if not name.startswith(plane_prefix):
            continue
        stat_names = {k: bytes(dict(_fields(v)).get(2, b"")).decode()
                      for k, v in _map_entries(stats).items()}
        for meta in _map_entries(events).values():
            op, tf_op = "", None
            for f, v in _fields(meta):
                if f == 2:
                    op = bytes(v).decode()
                elif f == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) != "tf_op":
                        continue
                    tf_op = (bytes(st[5]).decode() if 5 in st
                             else stat_names.get(st.get(7), ""))
            if op and tf_op:
                out[op] = tf_op.rstrip(":")
    return out


def scope_path(op_name: str) -> str:
    """The listed scopes on an op_name, outermost first. XLA joins the
    names of merged instructions with ";": the first is the op's own."""
    parts = op_name.split(";", 1)[0].split("/")
    return "/".join(p for p in parts if p in STEP_SCOPES)


def owner(scope: str) -> str | None:
    """The innermost listed scope on a scope path, or None."""
    for part in reversed(scope.split("/")):
        if part in STEP_SCOPES:
            return part
    return None


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------

def load_trace(path: str) -> tracing.Trace:
    """Device ops of chip 0 with their scopes, and the host spans of the
    benchmark (`bench.*`) and of the program (`engine.*`, `pump.*`)."""
    names = op_names(path)
    pdata = jax.profiler.ProfileData.from_file(path)
    ops, spans = [], []
    for plane in pdata.planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    base = tracing.op_event(ev.name, ev.start_ns, ev.duration_ns)
                    ops.append(ScopedEvent(*dataclasses.astuple(base),
                                           scope_path(names.get(ev.name, ""))))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith((tracing.SPAN,) + PROGRAM_SPANS):
                        spans.append(tracing.Event(ev.name, ev.start_ns * 1e-9,
                                                   ev.duration_ns * 1e-9))
    return tracing.Trace(ops, spans)


def bench_only(tr: tracing.Trace) -> tracing.Trace:
    """The trace as `harness.tracing` reads it: the benchmark's spans only."""
    return tracing.Trace(tr.device_ops,
                         [s for s in tr.spans if s.name.startswith(tracing.SPAN)])


@functools.lru_cache(maxsize=1)
def _load_reduced(path: str, mtime: float):
    tr = load_trace(path)
    return tr, tracing.reduce(bench_only(tr))


def traced(ctx) -> tuple[tracing.Trace, tracing.Reduced] | None:
    """The run's trace with scopes and program spans, and its reduction,
    or None where the run traced no device op."""
    if ctx.trace is None:
        return None
    found = glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"), recursive=True)
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    tr, red = _load_reduced(path, os.path.getmtime(path))
    return None if red is None else (tr, red)


# ---------------------------------------------------------------------------
# what the readers report
# ---------------------------------------------------------------------------

def scope_shares(red: tracing.Reduced) -> dict[str | None, float] | None:
    """Share (%) of the device time of the leaf ops inside forward spans
    that each listed scope owns; None keys the ops none of them owns.
    None where no op carries a scope (a program without them)."""
    leaves = tracing.leaf_ops([ev for ev, kind in red.ops if kind is not None])
    total = sum(ev.dur for ev in leaves)
    if total <= 0 or not any(getattr(ev, "scope", "") for ev in leaves):
        return None
    by: dict[str | None, float] = {}
    for ev in leaves:
        key = owner(getattr(ev, "scope", ""))
        by[key] = by.get(key, 0.0) + ev.dur
    return {k: 100.0 * v / total for k, v in by.items()}


def scope_share(ctx, scope: str) -> float | None:
    """Share (%) of the device time of ops inside forward spans that falls
    under `scope` (leaf ops only, as `tracing.breakdown` counts them)."""
    got = traced(ctx)
    shares = None if got is None else scope_shares(got[1])
    return None if shares is None else shares.get(scope, 0.0)


def step_host_s(tr: tracing.Trace) -> tuple[int, float]:
    """(steps, host seconds outside their forwards) of the engine steps the
    trace recorded whole: each `engine.step` span less the
    `engine.*.forward` spans that start inside it."""
    steps = [s for s in tr.spans if s.name == "engine.step"]
    fwds = [s for s in tr.spans
            if s.name in ("engine.prefill.forward", "engine.decode.forward")]
    host = 0.0
    for st in steps:
        host += st.dur - sum(f.dur for f in fwds if st.start <= f.start <= st.start + st.dur)
    return len(steps), host


def untraced_step_host_ms(ctx) -> float | None:
    """Mean host time (ms) of one engine step outside its forwards, over
    the window's steps that ran with the profiler off: the engine's
    `step_s - prefill_s - decode_s` and `steps`, less the steps the trace
    recorded. None where the program keeps no step time."""
    st = ctx.stats
    if "step_s" not in st or not st["steps"]:
        return None
    steps, host = st["steps"], st["step_s"] - st["prefill_s"] - st["decode_s"]
    got = traced(ctx)
    if got is not None:
        n, h = step_host_s(got[0])
        steps, host = steps - n, host - h
    return 1e3 * host / steps if steps > 0 else None
