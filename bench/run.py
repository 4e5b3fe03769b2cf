"""Benchmark of the served LUT-NN engine on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run: build the cell's system (weights from the seed, the engine, both
token shapes compiled: that is set-up), drive it with the cell's traffic for
`--seconds`, then check what it served against the plain reference. The
last line of standard output is one JSON object:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "check": {...}}

`--trace 0` reports the cell's end-to-end metrics, `--trace 1` its
per-layer metrics, read from a profiler trace of part of the window and
from the engine's counters. The numbers compared for `correct` come last
on standard error and under "check". With no TPU, or fewer chips than the
cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.time()              # set-up is timed from here

import argparse
import gc
import json
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"


def _set_env() -> None:
    """Caches inside the checkout, at fixed paths (before JAX loads)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(BENCH / ".autotune" / "autotune.json")


def _paths() -> None:
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(chips: int, require_chip: bool) -> dict | None:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip and (info["platform"] != "tpu" or info["count"] < chips):
        print(f"bench: the cell needs {chips} TPU chip(s); JAX found {info}",
              file=sys.stderr)
        return None
    return info


def serve(cell, seed: int, seconds: float, *, trace: bool, engine_hook=None):
    """Set-up, the window and its clean-up: everything of a run up to the
    correctness check. The engine and its weights are freed on return."""
    import jax

    from harness import check, loadgen, measure, system, traffic, tracing

    cfg = cell.config
    jax.config.update("jax_default_matmul_precision", cfg["compute"]["matmul_precision"])
    compiles = tracing.CompileCounter()
    sysm = system.build(cell.model, cell.settings["engine"], seed, time.perf_counter)
    if engine_hook is not None:
        engine_hook(sysm)
    plan = traffic.plan(cell.traffic, cell.settings["load"], seed=seed,
                        seconds=seconds, vocab=cfg["vocab_size"],
                        engine=cell.settings["engine"])
    slots = check.SlotLog(sysm.engine, cell.model.family)
    probe = tracing.Probe(sysm.engine, BENCH / ".trace") if trace else None
    lg = loadgen.LoadGen(sysm.pump, plan)
    setup_s = time.time() - T_START
    compiles.arm()
    at_close = {}

    def close():
        at_close["stats"] = sysm.pump.stats()
        at_close["compiles"] = compiles.count()
    lg.run(seconds, probe.during if probe else None, close)
    stats, n_compiles = at_close["stats"], at_close["compiles"]
    healthy = sysm.pump.healthy
    sysm.pump.close()                     # the engine is still from here on
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    kv_seqs, kv_prog = slots.cached(sysm.engine)
    out = Served(
        setup_s=setup_s, phases={k: v - sysm.times["start"] for k, v in sysm.times.items()},
        stats=stats, compiles=n_compiles, healthy=healthy, sent=lg.sent, t0=lg.t0, t_end=lg.t_end,
        late=lg.late_s() if plan.due_s is not None else [],
        scale_shapes=sysm.scale_shapes,
        forwards=probe.forwards if probe else None,
        traced=probe.traced_path if probe else None,
        kv_seqs=kv_seqs, kv_prog=kv_prog, peak=peak)
    lg.abandon()
    out.e2e = measure.end_to_end(out.sent, out.t0, out.t_end, lg.t_drained)
    out.finished = [{"idx": s.idx, "prompt": s.spec["prompt"], "tokens": s.tokens}
                    for s in out.sent if s.status == "ok" and s.done_t is not None
                    and s.done_t <= out.t_end]
    if probe:
        probe.release()
    del sysm, lg, probe
    gc.collect()
    return out


class Served:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def main(argv=None, *, require_chip: bool = True, engine_hook=None,
         bench=None) -> int:
    """One run. `require_chip=False`, `engine_hook(system)` (which may break
    the timed path) and `bench` (a `spec.Benchmark` of test cells) are for
    the tests on the CPU."""
    args = parse(argv)
    _paths()
    from harness import spec

    bench = bench or spec.Benchmark(ROOT)
    cell = bench.cell(args.workload)
    device = device_info(cell.chips, require_chip)
    if device is None:
        return 2

    from harness import check, measure, tracing
    from harness.peaks import peaks

    cfg = cell.config
    sv = serve(cell, args.seed, args.seconds, trace=bool(args.trace),
               engine_hook=engine_hook)
    device["memory_peak_bytes"] = sv.peak
    e2e = sv.e2e
    phases = ", ".join(f"{k} {v:.3f}s" for k, v in sv.phases.items() if k != "start")
    late = (f"{measure.percentile(sv.late, 50):.6f}/{max(sv.late):.6f}s"
            if sv.late else "none (closed loop)")
    print(f"bench: {cell.name} seed {args.seed}: set-up {sv.setup_s:.3f}s ({phases}); "
          f"{len(sv.sent)} sent, {e2e['n_ttft']} timed to first token, {e2e['n_gaps']} "
          f"gaps in the window; {e2e['tokens_per_s']} tokens/s, ttft p50/p90 "
          f"{e2e['ttft_p50_ms']}/{e2e['ttft_p90_ms']} ms, gap p50 "
          f"{e2e['gap_p50_ms']} ms; compilations in the window: {sv.compiles}; "
          f"generator late p50/max {late}", flush=True)

    t_ref = time.perf_counter()
    reqs = check.sample(sv.finished, args.seed)
    ref = check.Reference(cell.model, args.seed, sv.scale_shapes,
                          cell.settings["engine"]["max_seq"])
    numbers, kv_stats = check.check_served(ref, reqs, sv.kv_seqs, sv.kv_prog)
    del ref, sv.kv_prog
    limits = cell.settings["check"]
    served = sum(len(r["tokens"]) for r in reqs)
    print(f"bench: reference over {len(reqs)} of {len(sv.finished)} finished "
          f"requests ({served} served tokens) and {len(sv.kv_seqs)} live slots "
          f"({sum(map(len, sv.kv_seqs))} cached positions) took "
          f"{time.perf_counter() - t_ref:.3f}s; per layer [max row error, rows over "
          f"{check.TAU}, rows]: {kv_stats.per_layer()}", flush=True)
    correct = sv.healthy and all(
        numbers.get(name) is not None and numbers[name] <= limit
        for name, limit in limits.items())

    breakdown = None
    metrics = {}
    if args.trace:
        red = tracing.reduce(tracing.load_trace(sv.traced)) if sv.traced else None
        if red is not None:
            device["busy_s"] = red.busy_s
            device["window_s"] = red.window_s
            breakdown = tracing.breakdown(red)
        ctx = tracing.Context(
            cell=cell, cfg=cfg, settings=cell.settings, stats=sv.stats, sent=sv.sent,
            t0=sv.t0, t_end=sv.t_end, forwards=sv.forwards, trace=red,
            peaks=peaks(device["kind"]) if device["platform"] == "tpu" else None,
            scale_shapes=sv.scale_shapes)
        values = {m.name: spec.load_reader(m.name, ROOT)(ctx)
                  for m in bench.metrics_for(cell.name, trace=True)}
    else:
        # every end-to-end number of the window, under its own name: the
        # cell reports those BENCHMARK.json lists for it
        values = dict(e2e, peak_hbm_gb=None if sv.peak is None else sv.peak / 1e9,
                      setup_s=sv.setup_s)
    for m in bench.metrics_for(cell.name, trace=bool(args.trace)):
        if values.get(m.name) is not None:
            metrics[m.name] = {"value": values[m.name], "unit": m.unit}

    for name, limit in limits.items():
        print(f"check: {name} {numbers.get(name)!r} (limit {limit!r}): "
              f"{check.DESCRIBE[name]}", file=sys.stderr)
    print(f"check: engine healthy {sv.healthy}", file=sys.stderr, flush=True)
    out = {"correct": correct, "attempted": len(sv.sent),
           "failed": measure.failed(sv.sent), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = {name: {"value": numbers.get(name), "limit": limit}
                    for name, limit in limits.items()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    _set_env()
    sys.exit(main())
