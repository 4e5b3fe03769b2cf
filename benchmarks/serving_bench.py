"""Serving-engine throughput: decode tok/s, prefill tok/s, and batch
occupancy through the LUT_INFER int8-table model, across four configs:

  * light_2req / heavy_12req — under- vs over-subscribed slot pool,
    in-memory params (the PR-2 baseline rows)
  * artifact_12req — the same heavy load served from a LUTArtifact
    round-tripped through disk (DESIGN.md §8): any artifact-load overhead
    or drift shows up against heavy_12req
  * tp2_12req — heavy load on a (1, 2) ("data", "model") mesh in a
    subprocess with 2 forced host devices (the tests/_subproc.py pattern),
    measuring the tensor-parallel engine path end to end. Refused on a TPU:
    this process already holds the chip, and one chip serves one process
  * prefix_chat_{shared,nosharing}_8req — the paged-KV chat pattern
    (DESIGN.md §12): a primer request warms the prefix cache, then 8
    requests share a 32-token system prompt. The shared row must beat the
    no-sharing row on prefill forwards (pages skip prefill), and its KV
    bytes resident must sit strictly below the dense per-slot footprint —
    both asserted here so the bench doubles as a perf regression gate.

A warm-up request compiles the engine's two token shapes off the clock, so
the rows measure steady-state scheduler throughput, not jit. With
`json_path` set (benchmarks/run.py --json) the rows are written to
BENCH_serving.json so serving perf joins the BENCH_kernels.json trajectory.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

from repro.configs import build_model, get_arch, reduce_arch
from repro.core.amm import Mode
from repro.serving.engine import ServingEngine

N_SLOTS = 4
MAX_SEQ = 64
PREFILL_CHUNK = 8
MAX_TOKENS = 8
# loads: half the slot pool (occupancy-starved) vs 3x the pool (saturated,
# requests queue behind busy slots)
LOADS = [("light_2req", 2), ("heavy_12req", 12)]
_TP2_MARKER = "TP2_ROW "
# prefix-heavy chat scenario: 8 requests share a 4-page system prompt
PAGE_SIZE = 8
SYS_PROMPT = [(j * 5) % 256 + 1 for j in range(32)]       # 4 full pages
N_CHAT = 8


def _run_load(bundle, params, n_requests: int, *, mesh=None) -> dict:
    eng = ServingEngine(
        bundle, params, n_slots=N_SLOTS, max_seq=MAX_SEQ,
        prefill_chunk=PREFILL_CHUNK, compute_dtype=jnp.float32,
        autotune_lut=False, mesh=mesh,
    )
    # warm-up: compile the chunked-prefill and decode shapes off the clock
    eng.warmup()

    key = jax.random.PRNGKey(2)
    t0 = time.perf_counter()
    for i in range(n_requests):
        key, k = jax.random.split(key)
        plen = int(jax.random.randint(k, (), 3, 2 * PREFILL_CHUNK))
        eng.submit([(i * 7 + j) % 256 + 1 for j in range(plen)],
                   max_tokens=MAX_TOKENS)
    done = eng.run_until_done(max_steps=10_000)
    wall_s = max(time.perf_counter() - t0, 1e-9)
    assert len(done) == n_requests, (len(done), n_requests)

    st = eng.stats()
    return {
        "requests": n_requests,
        "n_slots": N_SLOTS,
        "prefill_chunk": PREFILL_CHUNK,
        "tp": 1 if mesh is None else int(mesh.shape["model"]),
        "steps": st["steps"],
        "prefill_tokens": st["prefill_tokens"],
        "prefill_forwards": st["prefill_forwards"],
        "prefill_tok_s": round(st["prefill_tok_s"], 1),
        "decode_tokens": st["decode_tokens"],
        "decode_forwards": st["decode_forwards"],
        "decode_tok_s": round(st["decode_tok_s"], 1),
        "decode_occupancy": round(st["decode_occupancy"], 3),
        "shape_cache_hits": st["shape_cache_hits"],
        "wall_s": round(wall_s, 3),
    }


def _prefix_chat_row(bundle, params, *, sharing: bool) -> dict:
    """Paged engine under the chat pattern. A primer request registers the
    system prompt's pages, then the timed burst: N requests with distinct
    tails plus one verbatim resubmit of the system prompt (fully cached —
    exercises the final-token clamp and copy-on-write)."""
    eng = ServingEngine(
        bundle, params, n_slots=N_SLOTS, max_seq=MAX_SEQ,
        prefill_chunk=PREFILL_CHUNK, compute_dtype=jnp.float32,
        autotune_lut=False, paged=True, page_size=PAGE_SIZE,
        prefix_sharing=sharing,
    )
    eng.warmup()
    # primer: one completed request leaves the system prompt's 4 pages
    # registered and resident (refcount 0, evictable) for the burst
    eng.submit(SYS_PROMPT + [200], max_tokens=2)
    eng.run_until_done(max_steps=10_000)
    eng.finished.clear()
    eng.reset_stats()

    t0 = time.perf_counter()
    for i in range(N_CHAT):
        tail = [] if i == 0 else [210 + i, 220 + i, 230 + i]
        eng.submit(SYS_PROMPT + tail, max_tokens=MAX_TOKENS)
    done = eng.run_until_done(max_steps=10_000)
    wall_s = max(time.perf_counter() - t0, 1e-9)
    assert len(done) == N_CHAT and all(r.status == "ok" for r in done)

    st = eng.stats()
    return {
        "requests": N_CHAT,
        "n_slots": N_SLOTS,
        "prefill_chunk": PREFILL_CHUNK,
        "tp": 1,
        "steps": st["steps"],
        "prefill_tokens": st["prefill_tokens"],
        "prefill_forwards": st["prefill_forwards"],
        "prefill_tok_s": round(st["prefill_tok_s"], 1),
        "decode_tokens": st["decode_tokens"],
        "decode_forwards": st["decode_forwards"],
        "decode_tok_s": round(st["decode_tok_s"], 1),
        "decode_occupancy": round(st["decode_occupancy"], 3),
        "shape_cache_hits": st["shape_cache_hits"],
        "wall_s": round(wall_s, 3),
        # pool gauges (deterministic scheduler counters — regression-gated)
        "page_size": PAGE_SIZE,
        "kv_pages_total": st["kv_pages_total"],
        "kv_pages_peak": st["kv_pages_peak"],
        "kv_bytes_resident": st["kv_bytes_resident"],
        "kv_bytes_peak": st["kv_bytes_peak"],
        "kv_bytes_dense_equiv": st["kv_bytes_dense_equiv"],
        "pool_utilization": round(st["pool_utilization"], 3),
        "prefix_hits": st["prefix_hits"],
        "prefix_lookups": st["prefix_lookups"],
        "prefix_hit_rate": round(
            st["prefix_hits"] / st["prefix_lookups"], 3
        ) if st["prefix_lookups"] else 0.0,
        "prefill_tokens_skipped": st["prefill_tokens_skipped"],
        "cow_copies": st["cow_copies"],
    }


def _bundle_and_params():
    arch = reduce_arch(get_arch("qwen3_1p7b"), n_layers=2)
    bundle = build_model(arch, Mode.LUT_INFER)
    return bundle, bundle.init(jax.random.PRNGKey(0))


def _tp2_row(timeout: int = 900) -> dict:
    """Heavy load on a tp=2 mesh, in a subprocess with 2 forced host
    devices (the tests/_subproc.py pattern — works on any host)."""
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ)
    # append (not replace) so user-set XLA flags apply to the tp2 row too —
    # otherwise the tp=1 vs tp=2 rows would measure different XLA configs
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2").strip()
    env["PYTHONPATH"] = str(src) + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, str(pathlib.Path(__file__).resolve()), "--tp2-child"],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    if out.returncode != 0:
        raise RuntimeError(f"tp2 child failed:\n{out.stdout}\n{out.stderr}")
    for line in out.stdout.splitlines():
        if line.startswith(_TP2_MARKER):
            return json.loads(line[len(_TP2_MARKER):])
    raise RuntimeError(f"tp2 child printed no row:\n{out.stdout}")


def _tp2_child() -> None:
    from repro.launch.mesh import make_host_mesh

    bundle, params = _bundle_and_params()
    mesh = make_host_mesh(data=1, model=2)
    row = {"load": "tp2_12req", **_run_load(bundle, params, 12, mesh=mesh)}
    print(_TP2_MARKER + json.dumps(row))


def main(json_path: str | pathlib.Path | None = None) -> list[dict]:
    bundle, params = _bundle_and_params()

    rows = []
    cols = ["load", "requests", "tp", "decode_tok_s", "prefill_tok_s",
            "decode_occupancy", "steps", "shape_cache_hits"]
    print(",".join(cols))

    def emit(row):
        rows.append(row)
        print(",".join(str(row[c]) for c in cols))

    for load, n in LOADS:
        emit({"load": load, **_run_load(bundle, params, n)})

    # artifact-loaded engine: disk round trip, then the heavy load again
    with tempfile.TemporaryDirectory() as td:
        from repro.serving.artifact import load_artifact, save_artifact

        save_artifact(pathlib.Path(td) / "art", bundle, params)
        art = load_artifact(pathlib.Path(td) / "art")
        emit({"load": "artifact_12req", **_run_load(art.bundle, art.params, 12)})

    # paged-KV chat pattern: prefix sharing must pay for itself, in both
    # compute (prefill forwards skipped) and memory (resident below dense)
    shared = _prefix_chat_row(bundle, params, sharing=True)
    cold = _prefix_chat_row(bundle, params, sharing=False)
    emit({"load": "prefix_chat_shared_8req", **shared})
    emit({"load": "prefix_chat_nosharing_8req", **cold})
    assert shared["prefill_forwards"] < cold["prefill_forwards"], (shared, cold)
    assert shared["prefill_tokens_skipped"] > 0, shared
    assert shared["kv_bytes_peak"] < shared["kv_bytes_dense_equiv"], shared

    if jax.default_backend() == "tpu":
        # this process holds the chip, so the tp=2 child could not get it
        print("# tp2 row refused on tpu: it runs in a second process")
    else:
        emit(_tp2_row())

    if json_path is not None:
        payload = {
            "schema": "serving_bench.v3",
            "arch": "qwen3_1p7b(reduced,L=2)",
            "mode": "lut_infer",
            "backend": jax.default_backend(),
            "rows": rows,
        }
        pathlib.Path(json_path).write_text(json.dumps(payload, indent=1))
        print(f"# wrote {json_path}")
    return rows


if __name__ == "__main__":
    if "--tp2-child" in sys.argv:
        _tp2_child()
    else:
        _JSON = pathlib.Path(__file__).resolve().parents[1] / "BENCH_serving.json"
        main(json_path=_JSON if "--json" in sys.argv else None)
