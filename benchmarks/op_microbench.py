"""Paper Fig. 7 analog: per-operator cost, dense vs LUT-NN, v1 vs v2 vs fused.

Real TPU wall-clock is unavailable here, so this reports THREE views per op:

  * measured CPU wall-clock of the XLA paths — dense matmul, fp32 one-hot
    LUT, int8-dot LUT (honest but CPU-flavored);
  * measured wall-clock of the Pallas kernels — v1, v2, and the fused
    encode→lookup decode kernel (DESIGN.md §13) — in interpret mode on an
    N-capped slice (interpret executes the kernel body through XLA — it
    exercises the exact kernel dataflow but does NOT model MXU int8
    throughput, so off-TPU these columns track emulation cost only; each
    row records its truncation in `kernel_n_cap`);
  * the autotuner's analytic v5e roofline projection for the FULL shape,
    v1 vs v2 vs fused, each at its own best tiling (DESIGN.md §3/§13) — the
    numbers a real TPU run regresses against.

Each row also records the autotune verdict for its shape: the winning
kernel version (`tuned_version`), its blocks, and `tuned_measured` (0/1) —
the measured-vs-analytic flag. With REPRO_AUTOTUNE_MEASURE=1 the tuning
sweep times compiled runs on the live backend (repro.kernels.measure)
instead of scoring the roofline model.

With `json_path` set (benchmarks/run.py --json) the rows are written to
BENCH_kernels.json so future PRs have a perf trajectory to regress against;
`benchmarks/check_regression.py` gates the structural keys. `--smoke`
restricts the run to the two small CI shapes (fast enough for the
kernel-parity job); the big rows are marked best-effort in the gate so a
smoke artifact still diffs cleanly.
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import time

import jax
import jax.numpy as jnp

from repro.core import pq, quant
from repro.kernels import autotune, measure, ops
from repro.roofline.analysis import chip_peaks

OPS = [
    # (name, N, D, M, K, V)
    ("bert_ffn_up", 512, 768, 3072, 16, 32),
    ("llama3_qproj", 256, 4096, 4096, 16, 32),
    ("llama3_ffn_gate", 256, 4096, 14336, 16, 32),
]

# small shapes the CI kernel-parity job can regenerate in seconds; part of
# the full run too, so the committed artifact always carries them
SMOKE_OPS = [
    ("smoke_ffn", 32, 64, 128, 16, 8),
    ("smoke_proj", 16, 128, 64, 16, 16),
]

# interpret-mode kernels run the grid as emulated XLA steps on CPU — cap the
# row count so the measured v1/v2/fused comparison stays cheap. The
# full-shape numbers come from the analytic roofline projection.
KERNEL_N_CAP = 64


def _time(fn, *args, iters: int = 3) -> float:
    """Median-free mean wall-clock per call; exactly one warmup execution."""
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def bench_op(name: str, n: int, d: int, m: int, k: int, v: int) -> dict:
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n, d), jnp.float32)
    w = jax.random.normal(key, (d, m), jnp.float32)
    P = jax.random.normal(key, (d // v, k, v))
    table = pq.build_table(P, w, stop_weight_grad=False)
    qt = quant.quantize_table(table)
    qt_sh = quant.quantize_table(table, m_shared=True)

    dense_fn = jax.jit(lambda x, w: x @ w)

    def lut_fn(x, P, tq, ts):
        tbl = tq.astype(jnp.float32) * ts
        enc = pq.hard_encode(pq.pairwise_sq_dists(pq.split_subvectors(x, v), P))
        return pq.lut_contract(enc, tbl)

    def lut_i8_fn(x, P, tq, ts):
        enc = pq.hard_encode(pq.pairwise_sq_dists(pq.split_subvectors(x, v), P))
        return pq.lut_contract_int8(enc, tq, ts)

    t_dense = _time(dense_fn, x, w) * 1e3
    t_lut = _time(jax.jit(lut_fn), x, P, qt.q, qt.scale) * 1e3
    t_lut_i8 = _time(jax.jit(lut_i8_fn), x, P, qt_sh.q, qt_sh.scale) * 1e3

    # the autotune verdict for this shape: version axis swept (v1/v2/fused),
    # measured on the live backend when REPRO_AUTOTUNE_MEASURE=1
    c = d // v
    measure_fn = (
        measure.measure_lut_amm(n, m, c, k, v) if measure.measure_enabled()
        else None
    )
    blk, rec = autotune.tune("lut_amm", n, m, c, k, v, save=False,
                             measure=measure_fn)

    # per-version analytic best tilings — each generation judged at ITS
    # blocks, not the winner's (a fused (bn, bm, C) tiling is not a
    # meaningful v1/v2 config)
    blk_v2, v2_us = autotune.best_analytic("lut_amm", n, m, c, k, v, version=2)
    _, v1_us = autotune.best_analytic("lut_amm", n, m, c, k, v, version=1)
    blk_f, fused_us = autotune.best_analytic("lut_amm", n, m, c, k, v, version=3)

    # Pallas v1 vs v2 vs fused, measured (interpret off-TPU) on the N-capped
    # slice at each generation's analytic-best blocks.
    nk = min(n, KERNEL_N_CAP)
    xk = x[:nk]
    bn, bm, bc = min(blk_v2.block_n, nk), blk_v2.block_m, blk_v2.block_c
    t_v1 = _time(
        lambda *a: ops.lut_amm_v1(*a, block_n=bn, block_m=bm, block_c=bc),
        xk, P, qt_sh.q, jnp.broadcast_to(qt_sh.scale, (c, 1, m)),
        iters=2,
    ) * 1e3
    t_v2 = _time(
        lambda *a: ops.lut_amm(*a, version=2, block_n=bn, block_m=bm, block_c=bc),
        xk, P, qt_sh.q, qt_sh.scale,
        iters=2,
    ) * 1e3
    if blk_f is not None:
        t_fused = _time(
            lambda *a: ops.lut_amm_fused(
                *a, block_n=min(blk_f.block_n, nk), block_m=blk_f.block_m),
            xk, P, qt_sh.q, qt_sh.scale,
            iters=2,
        ) * 1e3
    else:
        t_fused = math.nan                   # fused working set over budget

    # v5e roofline (decode regime: weight/table bytes dominate)
    dense_bytes_ = d * m * 2 + (n * d + n * m) * 2
    lut_bytes_ = c * k * m + c * k * v * 4 + (n * d + n * m) * 2
    dense_flops_ = 2 * n * d * m
    lut_flops_ = 2 * n * d * k + 2 * n * c * k * m   # one-hot MXU path
    peaks = chip_peaks()
    t_roof_dense = max(dense_bytes_ / peaks.hbm_bw,
                       dense_flops_ / peaks.bf16_flops) * 1e6
    t_roof_lut = max(lut_bytes_ / peaks.hbm_bw,
                     lut_flops_ / peaks.bf16_flops) * 1e6

    return {
        "op": name,
        "n": n, "d": d, "m": m, "k": k, "v": v,
        "cpu_dense_ms": t_dense,
        "cpu_lut_ms": t_lut,
        "cpu_lut_int8_ms": t_lut_i8,
        "kernel_n": nk,
        "kernel_n_cap": KERNEL_N_CAP,        # truncation recorded per row
        "kernel_backend": "tpu" if jax.default_backend() == "tpu" else "interpret",
        "pallas_v1_ms": t_v1,
        "pallas_v2_ms": t_v2,
        "fused_ms": t_fused,
        "tuned_version": rec.get("version", 2),
        "tuned_measured": int(bool(rec.get("measured"))),   # measured-vs-analytic
        "tuned_block_n": blk.block_n,
        "tuned_block_m": blk.block_m,
        "tuned_block_c": blk.block_c,
        "v1_model_us": v1_us,
        "v2_model_us": v2_us,
        "fused_model_us": fused_us if blk_f is not None else math.nan,
        "tpu_roofline_dense_us": t_roof_dense,
        "tpu_roofline_lut_us": t_roof_lut,
        "decode_byte_ratio": (d * m * 2) / (c * k * m),
    }


COLUMNS = (
    "op", "cpu_dense_ms", "cpu_lut_ms", "cpu_lut_int8_ms",
    "pallas_v1_ms", "pallas_v2_ms", "fused_ms",
    "tuned_version", "tuned_measured",
    "tuned_block_n", "tuned_block_m", "tuned_block_c",
    "v1_model_us", "v2_model_us", "fused_model_us",
    "tpu_roofline_dense_us", "tpu_roofline_lut_us", "decode_byte_ratio",
)


def main(
    json_path: str | pathlib.Path | None = None, *, smoke: bool = False
) -> list[dict]:
    t0 = time.time()
    print("# Fig. 7 analog: per-op dense vs LUT (xla/int8/pallas v1/v2/fused)")
    print(",".join(COLUMNS))
    rows = []
    todo = SMOKE_OPS if smoke else OPS + SMOKE_OPS
    for name, n, d, m, k, v in todo:
        r = bench_op(name, n, d, m, k, v)
        rows.append(r)
        print(",".join(
            f"{r[c]:.3f}" if isinstance(r[c], float) else str(r[c])
            for c in COLUMNS
        ))
    if json_path is not None:
        payload = {
            "benchmark": "op_microbench",
            "backend": jax.default_backend(),
            "kernel_n_cap": KERNEL_N_CAP,
            "measured_autotune": measure.measure_enabled(),
            "rows": rows,
        }
        pathlib.Path(json_path).write_text(json.dumps(payload, indent=1))
        print(f"# wrote {json_path}")
    print(f"op_microbench,{(time.time()-t0)*1e6:.0f},cpu+roofline")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_kernels.json at the repo root")
    ap.add_argument("--json-out", default=None,
                    help="write the payload to this explicit path instead "
                         "(CI fresh-dir flow for check_regression)")
    ap.add_argument("--smoke", action="store_true",
                    help="only the two small smoke shapes (CI kernel-parity)")
    args = ap.parse_args()
    # anchor at the repo root (same path run.py and roofline_table.py use),
    # independent of the invocation cwd
    _JSON = pathlib.Path(__file__).resolve().parents[1] / "BENCH_kernels.json"
    out = args.json_out if args.json_out else (_JSON if args.json else None)
    main(json_path=out, smoke=args.smoke)
