"""Wall-clock measurement harness for the autotuner (DESIGN.md §13.2).

`measure_lut_amm` builds the operands for one lut_amm shape ONCE and returns
a `measure(cfg, version) -> seconds` callable that `autotune.tune` sweeps:
each candidate (tiling × kernel version) is compiled and run on the live
backend — compiled ahead of the clock, one (or more) discarded warmup
executions, then the median of k timed runs. A candidate the compiler
refuses (a tiling the real hardware cannot hold) returns +inf so the sweep
skips it; a failure while running propagates.

This is what turns the autotuner's ranking from a roofline *projection* into
a measurement: `ServingEngine` warmup uses it when REPRO_AUTOTUNE_MEASURE=1,
and `benchmarks/op_microbench.py` when the same flag is set, writing records
with `measured: true` that take precedence over analytic ones everywhere
(DESIGN.md §13.3).

Knobs (env): REPRO_AUTOTUNE_MEASURE_REPS (default 5) and
REPRO_AUTOTUNE_MEASURE_WARMUP (default 1) bound the per-candidate cost.
"""

from __future__ import annotations

import math
import os
import statistics
import time
from typing import Callable

import jax
import jax.numpy as jnp

from repro.kernels import autotune


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def measure_enabled() -> bool:
    """Whether the wall-clock measurement path is switched on (env flag)."""
    return os.environ.get("REPRO_AUTOTUNE_MEASURE", "0").lower() not in (
        "", "0", "false", "no",
    )


def measure_lut_amm(
    n: int, m: int, c: int, k: int, v: int,
    *,
    dtype: str = "float32",
    interpret: bool | None = None,
    warmup: int | None = None,
    reps: int | None = None,
    seed: int = 0,
) -> Callable[[autotune.BlockConfig, int], float]:
    """Build a timed-compiled-run measure callable for one lut_amm shape.

    Operands are synthesized once (per-shape, not per-candidate): random
    activations in `dtype`, fp32 centroids, an int8 table with the m-shared
    (1,1,M) scale layout — the layout `deploy_params` emits for kernel
    sites, so the timed path is the production dataflow.
    """
    from repro.kernels.fused_decode import fused_decode_pallas
    from repro.kernels.lut_amm import lut_amm_pallas, lut_amm_pallas_v1
    from repro.kernels.ops import resolve_interpret

    interpret = resolve_interpret(interpret)
    warmup = warmup if warmup is not None else _env_int("REPRO_AUTOTUNE_MEASURE_WARMUP", 1)
    reps = reps if reps is not None else _env_int("REPRO_AUTOTUNE_MEASURE_REPS", 5)

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(k1, (n, c * v), jnp.dtype(dtype))
    P = jax.random.normal(k2, (c, k, v), jnp.float32)
    tq = jax.random.randint(k3, (c, k, m), -127, 128, jnp.int8)
    scale = jnp.full((1, 1, m), 0.02, jnp.float32)
    scale_v1 = jnp.broadcast_to(scale, (c, 1, m))        # v1 wants (C, ...) scales

    def measure(cfg: autotune.BlockConfig, version: int = 2) -> float:
        bn, bm, bc = cfg.block_n, cfg.block_m, cfg.block_c
        args = (x, P, tq, scale)
        if version >= autotune.VERSION_FUSED:
            if bc != c:           # fused keeps all of C resident by definition
                return math.inf

            def kernel(x, P, tq, scale):
                return fused_decode_pallas(x, P, tq, scale, block_n=bn,
                                           block_m=bm, interpret=interpret)
        elif version == 2:
            def kernel(x, P, tq, scale):
                return lut_amm_pallas(x, P, tq, scale, block_n=bn, block_m=bm,
                                      block_c=bc, interpret=interpret)
        else:
            args = (x, P, tq, scale_v1)

            def kernel(x, P, tq, scale):
                return lut_amm_pallas_v1(x, P, tq, scale, block_n=bn,
                                         block_m=bm, block_c=bc,
                                         interpret=interpret)
        try:
            run = jax.jit(kernel).lower(*args).compile()
        except Exception:     # noqa: BLE001 — the compiler refused the tiling
            return math.inf
        for _ in range(max(1, warmup)):
            jax.block_until_ready(run(*args))
        times = []
        for _ in range(max(1, reps)):
            t0 = time.perf_counter()
            jax.block_until_ready(run(*args))
            times.append(time.perf_counter() - t0)
        return statistics.median(times)
    return measure
