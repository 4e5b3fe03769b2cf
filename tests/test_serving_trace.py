"""What the serving path records about itself: the engine's and the pump's
counters (queue wait, step time, compilations, the front end's lock wait),
the host spans it writes on the profiler's clock, and the named scopes its
compiled step carries for the device trace."""

import glob
import re
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs import build_model, get_arch, reduce_arch
from repro.core.amm import Mode
from repro.serving import engine as engine_mod
from repro.serving.engine import ServingEngine
from repro.serving.server import EnginePump, metrics_text

PROMPTS = [[3, 5, 7, 9, 11, 13], [2, 4], [6, 8, 10], [1, 3, 5, 7, 9]]
NEW_COUNTERS = ("admitted", "queue_wait_s", "step_s", "compiles",
                "submits", "submit_wait_s", "submit_wait_max_s")
ENGINE_SPANS = ("engine.step", "engine.admit", "engine.prefill", "engine.decode",
                "engine.prefill.forward", "engine.decode.forward", "engine.sample")
PUMP_SPANS = ("pump.step", "pump.dispatch", "pump.idle")


@pytest.fixture(scope="module")
def dense():
    bundle = build_model(reduce_arch(get_arch("qwen3_1p7b"), n_layers=2), Mode.DENSE)
    return bundle, bundle.init(jax.random.PRNGKey(0))


def _engine(model, **kw):
    bundle, params = model
    kw = {"n_slots": 2, "max_seq": 32, "prefill_chunk": 4, "autotune_lut": False, **kw}
    return ServingEngine(bundle, params, **kw)


def _wait(pred, timeout_s=60.0):
    deadline = time.monotonic() + timeout_s
    while not pred():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.01)


def test_queue_wait_is_the_sum_over_admitted_requests(dense):
    eng = _engine(dense)
    for p in PROMPTS:                  # four requests on two slots: two queue
        eng.submit(p, max_tokens=3)
    done = eng.run_until_done()
    st = eng.stats()
    assert st["admitted"] == len(PROMPTS) == len(done)
    assert all(r.submit_t <= r.admit_t <= r.finish_t for r in done)
    assert st["queue_wait_s"] == pytest.approx(sum(r.admit_t - r.submit_t for r in done))
    assert max(r.admit_t - r.submit_t for r in done) > 0


def test_step_time_holds_its_forwards(dense):
    eng = _engine(dense)
    for p in PROMPTS:
        eng.submit(p, max_tokens=3)
    eng.run_until_done()
    st = eng.stats()
    assert st["steps"] > 0 and st["prefill_forwards"] > 0 and st["decode_forwards"] > 0
    assert st["step_s"] >= st["prefill_s"] + st["decode_s"] > 0


def test_compiles_count_new_programs_only(dense):
    eng = _engine(dense)
    eng.warmup()                        # compiles both token shapes
    for p in PROMPTS:
        eng.submit(p, max_tokens=3)
    eng.run_until_done()
    eng.reset_stats()
    for p in PROMPTS:                   # the same shapes again
        eng.submit(p, max_tokens=3)
    eng.run_until_done()
    assert eng.stats()["compiles"] == 0
    jax.jit(lambda x: x * 3 + 1)(jnp.ones((7, 5))).block_until_ready()  # a new program
    assert eng.stats()["compiles"] >= 1


def test_pump_counts_one_submit_wait_per_submit(dense):
    pump = EnginePump(_engine(dense))
    try:
        rids = [pump.submit({"prompt": p, "max_tokens": 2}) for p in PROMPTS]
        assert len(set(rids)) == len(PROMPTS)
        st = pump.stats()
        assert st["submits"] == len(PROMPTS)
        assert 0 <= st["submit_wait_max_s"] <= st["submit_wait_s"]
        # a submit that finds the lock held waits, and is timed
        _wait(lambda: pump.pending() == 0)
        held = 0.2
        with pump._lock:
            t = threading.Thread(target=pump.submit, args=({"prompt": [1, 2]},))
            t.start()
            time.sleep(held)
        t.join(timeout=60)
        assert not t.is_alive()
        st = pump.stats()
        assert st["submits"] == len(PROMPTS) + 1
        assert st["submit_wait_max_s"] >= held * 0.9
    finally:
        pump.close()


def test_reset_zeroes_every_new_counter_and_metrics_exports_them(dense):
    pump = EnginePump(_engine(dense))
    try:
        for p in PROMPTS:
            pump.submit({"prompt": p, "max_tokens": 2})
        _wait(lambda: pump.pending() == 0)
        before = pump.stats()
        assert before["admitted"] == before["submits"] == len(PROMPTS)
        assert before["step_s"] > 0
        with pump._lock:                # the pump's counters are the engine's
            pump.engine.reset_stats()
        after = pump.stats()
        assert {k: after[k] for k in NEW_COUNTERS} == dict.fromkeys(NEW_COUNTERS, 0)
        text = metrics_text(after)
        for k in NEW_COUNTERS:
            assert re.search(rf"^lutnn_serving_{k} 0(\.0)?$", text, re.M), k
    finally:
        pump.close()


# --- spans on the profiler's clock -------------------------------------------

def _host_spans(trace_dir) -> list:
    (path,) = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("engine.", "pump.")):
                    out.append((ev.name, line.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


def _inside(span, spans, names) -> bool:
    _, line, a, b, _ = span
    if any(n in names and ln == line and sa <= a and b <= sb
           for n, ln, sa, sb, _ in spans):
        return True
    # a span open when the trace started is not recorded: the pump loop
    # the trace caught mid-way has no recorded span before this one
    return not any(n in PUMP_SPANS and ln == line and sa < a for n, ln, sa, _, _ in spans)


def test_every_span_is_written_and_nested(dense, tmp_path):
    pump = EnginePump(_engine(dense), idle_wait_s=0.01)
    try:
        pump.submit({"prompt": [1, 2]})                       # compile off the trace
        _wait(lambda: pump.pending() == 0)
        with jax.profiler.trace(str(tmp_path)):
            # a span open when the trace starts is not recorded: let the
            # idle pump begin its next loop inside the trace first
            time.sleep(0.1)
            rids = [pump.submit({"prompt": p, "max_tokens": 3}) for p in PROMPTS]
            _wait(lambda: pump.pending() == 0)
            time.sleep(0.1)                                   # the pump idles
    finally:
        pump.close()
    spans = _host_spans(tmp_path)
    names = {s[0] for s in spans}
    assert set(ENGINE_SPANS + PUMP_SPANS) <= names
    for s in spans:
        if s[0] == "engine.prefill.forward":
            assert _inside(s, spans, ("engine.prefill",)), s
        elif s[0] == "engine.decode.forward":
            assert _inside(s, spans, ("engine.decode",)), s
        elif s[0] in ("engine.prefill", "engine.decode", "engine.admit"):
            assert _inside(s, spans, ("engine.step",)), s
        elif s[0] == "engine.step":
            assert _inside(s, spans, ("pump.step",)), s
    steps = [s[4]["step_num"] for s in spans if s[0] == "engine.step"]
    assert steps == sorted(steps) and len(set(steps)) == len(steps)
    # spans of one request carry its rid
    def rids_of(name):
        return {int(r) for s in spans if s[0] == name and "rids" in s[4]
                for r in str(s[4]["rids"]).split()}
    assert rids_of("engine.admit") == rids_of("engine.prefill") == set(rids)
    assert rids_of("engine.decode") <= set(rids)


def test_attributes_are_formatted_only_while_tracing(dense, tmp_path, monkeypatch):
    calls = []
    real = engine_mod._rids

    def counting(reqs):
        calls.append(1)
        return real(reqs)
    monkeypatch.setattr(engine_mod, "_rids", counting)
    eng = _engine(dense)
    for p in PROMPTS:
        eng.submit(p, max_tokens=2)
    eng.run_until_done()
    assert calls == []
    for p in PROMPTS:
        eng.submit(p, max_tokens=2)
    with jax.profiler.trace(str(tmp_path)):
        eng.run_until_done()
    assert calls


# --- named scopes in the compiled step ----------------------------------------

SCOPES = ("linear", "attention", "kv_write", "lm_head")


def _scopes_in_step(eng, tokens_per_row) -> set:
    toks = jnp.zeros((eng.n_slots, tokens_per_row), jnp.int32)
    text = eng._step_fn.lower(eng.params, toks, jnp.zeros((eng.n_slots,), jnp.int32),
                              eng.caches, jnp.ones((eng.n_slots,), bool)).compile().as_text()
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        found |= set(op_name.replace(";", "/").split("/")) & set(SCOPES)
    return found


@pytest.mark.parametrize("mode", [Mode.DENSE, Mode.LUT_INFER])
@pytest.mark.parametrize("tokens_per_row", [1, 4])
def test_the_compiled_step_carries_each_scope(mode, tokens_per_row):
    bundle = build_model(reduce_arch(get_arch("qwen3_1p7b"), n_layers=2), mode)
    eng = _engine((bundle, bundle.init(jax.random.PRNGKey(0))))
    assert _scopes_in_step(eng, tokens_per_row) == set(SCOPES)


# --- the compiled step writes the caches it is given, in place ----------------

def _control_instructions(text):
    """(opcode, dims, fused root opcode) of each instruction in the step's
    control computations (entry, loop bodies and conditions, calls), not the
    insides of fusions."""
    comps, roots = {}, {}
    for block in re.split(r"\n(?=\S)", text):
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(", block)
        if head:
            comps[head.group(2)] = (bool(head.group(1)), block)
            root = re.search(r"ROOT %?[\w.\-]+ = \S+ ([\w\-]+)\(", block)
            roots[head.group(2)] = root.group(1) if root else None
    control = set(re.findall(r"(?:body|condition|to_apply)=%?([\w.\-]+)", text))
    for name, (entry, block) in comps.items():
        if not (entry or name in control):
            continue
        for m in re.finditer(r"\n\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* "
                             r"([\w\-]+)\((.*)", block):
            dims, op, rest = m.groups()
            called = re.search(r"calls=%?([\w.\-]+)", rest)
            yield op, tuple(int(d) for d in dims.split(",") if d), \
                roots.get(called.group(1)) if called else None


@pytest.mark.parametrize("mode", [Mode.DENSE, Mode.LUT_INFER])
@pytest.mark.parametrize("tokens_per_row", [1, 4])
def test_the_compiled_step_updates_each_cache_leaf_in_place(mode, tokens_per_row):
    """The step aliases every cache leaf from input to output, and nothing in
    it but an update in place makes a whole leaf: no copy, select or
    broadcast of the cache, in any layout."""
    bundle = build_model(reduce_arch(get_arch("qwen3_1p7b"), n_layers=3), mode)
    eng = _engine((bundle, bundle.init(jax.random.PRNGKey(0))))
    toks = jnp.zeros((eng.n_slots, tokens_per_row), jnp.int32)
    text = eng._step_fn.lower(eng.params, toks, jnp.zeros((eng.n_slots,), jnp.int32),
                              eng.caches, jnp.ones((eng.n_slots,), bool)).compile().as_text()
    leaves = jax.tree.leaves(eng.caches)
    cache_params = set(re.findall(r"parameter\((\d+)\)[^\n]*op_name=\"caches\[", text))
    header = re.search(r"HloModule [^\n]*", text).group(0)
    aliased = set(re.findall(r"\((\d+), \{\}, (?:may|must)-alias\)", header))
    assert len(cache_params) == len(leaves) and aliased == cache_params
    whole = {tuple(sorted(leaf.shape)) for leaf in leaves}
    in_place = ("dynamic-update-slice", "scatter")
    made = [(op, dims, root) for op, dims, root in _control_instructions(text)
            if tuple(sorted(dims)) in whole
            and op not in ("parameter", "get-tuple-element", "bitcast") + in_place
            and not (op == "fusion" and root in in_place)]
    assert not made, made
