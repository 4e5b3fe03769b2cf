"""The sweep that finds an open-loop cell's knee (not part of a run).

    python3 bench/sweep.py --workload <cell> --rates 1,2,3 --seconds 30 --seed 1

One system, built once, serves the cell's traffic at each rate in turn
(requests still live at a window's end are cancelled before the next).
Per rate: tokens/s, time to first token p50/p90 in each half of the
window (a backlog that grows shows as a second half slower than the
first), decode occupancy, the queue left at the window's close, how long
after the close the last request due in the window got its first token
(`drain_s`) and how late the generator sent (median and most).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    run._paths()
    import jax

    from harness import loadgen, measure, spec, system, traffic

    cell = spec.Benchmark(run.ROOT).cell(args.workload)
    if run.device_info(cell.chips, True) is None:
        return 2
    cfg = cell.config
    jax.config.update("jax_default_matmul_precision", cfg["compute"]["matmul_precision"])
    sysm = system.build(cell.model, cell.settings["engine"], args.seed, time.perf_counter)
    for rate in (float(r) for r in args.rates.split(",")):
        plan = traffic.plan(cell.traffic, {"rate_per_s": rate}, seed=args.seed,
                            seconds=args.seconds, vocab=cfg["vocab_size"],
                            engine=cell.settings["engine"])
        sysm.engine.reset_stats()
        lg = loadgen.LoadGen(sysm.pump, plan)
        lg.run(args.seconds)
        st = sysm.pump.stats()
        lg.finish()
        mid = lg.t0 + args.seconds / 2
        e = measure.end_to_end(lg.sent, lg.t0, lg.t_end, lg.t_drained)
        first = measure.ttfts(lg.sent, lg.t0, mid, lg.t_drained)
        second = measure.ttfts(lg.sent, mid, lg.t_end, lg.t_drained)
        row = {"workload": cell.name, "rate_per_s": rate, "sent": len(lg.sent),
               "tokens_per_s": e["tokens_per_s"], "ttft_p50_ms": e["ttft_p50_ms"],
               "ttft_p90_ms": e["ttft_p90_ms"], "gap_p95_ms": e["gap_p95_ms"],
               "ttft_p90_ms_first_half": 1e3 * measure.percentile(first, 90) if first else None,
               "ttft_p90_ms_second_half": 1e3 * measure.percentile(second, 90) if second else None,
               "queue_at_close": st["queue_depth"], "decode_occupancy": st["decode_occupancy"],
               "drain_s": lg.t_drained - lg.t_end, "failed": measure.failed(lg.sent),
               "late_p50_s": measure.percentile(lg.late_s(), 50),
               "late_max_s": max(lg.late_s(), default=None)}
        print(json.dumps(row), flush=True)
        while sysm.pump.pending():
            time.sleep(0.05)
    sysm.pump.close()
    return 0


if __name__ == "__main__":
    run._set_env()
    sys.exit(main())
