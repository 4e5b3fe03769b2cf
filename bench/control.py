"""Readings that the limits of `correct` are set from (not part of a run).

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20 \\
        [--control-seeds 1,2,3] [--faults token_altered,drop_q] [--out <file.jsonl>]

For each seed, in one process: the cell's system serves its traffic for
`--seconds` and the run's numbers are read as a run reads them (the
program's readings, or with `--faults` the readings of each fault planted
in the timed path in turn, from `harness.faults`); for the control seeds
(read with the program as it is), the reference at the next
precision below the configuration's (`Precision.HIGH`, three bfloat16
passes, for float32 at HIGHEST) is read in the program's place on the same sequences. One JSON
line per seed.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    run._paths()
    from harness import faults, spec

    cell = spec.Benchmark(run.ROOT).cell(args.workload)
    if run.device_info(cell.chips, True) is None:
        return 2
    ctrl_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    for fault in [f for f in args.faults.split(",") if f] or [None]:
        hook = faults.for_model(cell.model)[fault] if fault else None
        for seed in (int(s) for s in args.seeds.split(",")):
            _read(cell, seed, args, fault, hook, seed in ctrl_seeds and fault is None)
    return 0


def _read(cell, seed, args, fault, hook, with_control) -> None:
    from harness import check

    run.T_START = time.time()
    sv = run.serve(cell, seed, args.seconds, trace=False, engine_hook=hook)
    reqs = check.sample(sv.finished, seed)
    ref = check.Reference(cell.model, seed, sv.scale_shapes,
                          cell.settings["engine"]["max_seq"])
    t = time.perf_counter()
    prog, stats = check.check_served(ref, reqs, sv.kv_seqs, sv.kv_prog)
    row = {"workload": cell.name, "seed": seed, "fault": fault,
           "requests": len(reqs), "served_tokens": sum(len(r["tokens"]) for r in reqs),
           "kv_positions": sum(map(len, sv.kv_seqs)), "program": prog,
           "program_layers": stats.per_layer(), "reference_s": time.perf_counter() - t,
           "tokens_per_s": sv.e2e["tokens_per_s"], "ttft_p90_ms": sv.e2e["ttft_p90_ms"],
           "setup_s": sv.setup_s}
    del sv.kv_prog
    if with_control:
        t = time.perf_counter()
        row["control"], cstats = check.control(ref, reqs, sv.kv_seqs)
        row["control_layers"] = cstats.per_layer()
        row["control_s"] = time.perf_counter() - t
    print(json.dumps(row), flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    run._set_env()
    sys.exit(main())
