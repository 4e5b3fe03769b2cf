"""Bring-up check: qwen3_1p7b at its published width, served on a TPU.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # one host with four chips

One chip runs four phases, each in a child process of its own:

  A        the XLA one-hot path (`lut_use_kernel=False`): `build_model` ->
           `ServingEngine` (warmup, submit, `run_until_done`) serves a few
           greedy requests, and the engine's prefill logits for one prompt
           are compared with `bundle.train_logits` (the uncached forward) on
           the same parameters, in float32 on the host CPU.
  A-warm   phase A again in a fresh process: its compiles come from the
           persistent compilation cache the first one wrote.
  B        the Pallas kernel path (`--use-kernel`): the same, after the
           engine's autotune warmup, with the kernel picked per site shape
           and the compiled (not interpreted) kernels counted in the step.
  C        `python -m repro.launch.serve --arch qwen3_1p7b --port ...`
           answers the same requests over HTTP (one streamed) with the
           tokens of phase A, then drains on SIGTERM and exits 0.

`--four-chips` runs only what exists across chips, and what it is
compared with: a `--tp 4` engine's prefill logits against the one-chip
engine's, then four one-chip router replicas (`--replicas 4`) against a
single replica on the same requests and artifact.

Every matmul runs at float32 precision (JAX_DEFAULT_MATMUL_PRECISION=
highest) in every phase: the engines serve float32, and a bf16 pass would
flip centroid codes against the float32 reference. The weights are random
(`jax.jit(bundle.init)` at seed 0, as `launch/serve.py` makes them).

This parent process never imports JAX: a chip belongs to one process, and
each phase's child holds it until it exits. The script exits non-zero, and
prints no result, when JAX finds no TPU or any phase fails. Its last line
is `{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import json
import os
import pathlib
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

ROOT = pathlib.Path(__file__).resolve().parent
WORK = ROOT / ".smoke"                   # git-ignored scratch of this script
ARCH_ID = "qwen3_1p7b"
SLOTS, MAX_SEQ, CHUNK = 4, 128, 32       # launch/serve.py's defaults
MAX_TOKENS = 16
# greedy requests; the last is longer than one prefill chunk
PROMPTS = [
    [151, 2048, 7, 99, 31337, 5, 1024, 77],
    [3, 14, 15, 92, 65, 35, 89, 79, 32, 38, 46, 26],
    list(range(1000, 1024)),
    [(i * 7919) % 151936 for i in range(1, 41)],
]
CHECK_PROMPT = PROMPTS[0]
# max |logit - float32 reference|; logits are O(1) and float32 rounding
# through 28 layers stays near 1e-5, while one flipped centroid code moves
# them by O(0.1)
LOGIT_TOL = 1e-3
RESULT_TAG = "CHIP_SMOKE_RESULT "
PHASE_TIMEOUT_S = 900
READY_TIMEOUT_S = 300     # a server loads and compiles in well under this


# ---------------------------------------------------------------------------
# child side: one phase, holding the chip
# ---------------------------------------------------------------------------

def _tpu_devices(count: int) -> dict:
    """The device JAX reports; exits 2 unless there are `count` TPUs."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" or info["count"] < count:
        print(f"chip_smoke: needs {count} TPU device(s), JAX found {info}",
              file=sys.stderr)
        sys.exit(2)
    return info


def _arch(use_kernel: bool):
    import dataclasses

    from repro.configs import get_arch

    return dataclasses.replace(get_arch(ARCH_ID), lut_use_kernel=use_kernel)


def _reference_logits(arch, params, prompt):
    """`train_logits` of the XLA bundle, float32, on the host CPU."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import build_model
    from repro.core.amm import Mode

    bundle = build_model(dataclasses.replace(arch, lut_use_kernel=False),
                         Mode.LUT_INFER)
    cpu = jax.devices("cpu")[0]
    tokens = jax.device_put(np.asarray([prompt], np.int32), cpu)

    @jax.jit
    def forward(p, t):
        return bundle.train_logits(p, {"tokens": t, "labels": t},
                                   compute_dtype=jnp.float32)[0][0]

    with jax.default_matmul_precision("highest"):
        return np.asarray(forward(jax.device_put(params, cpu), tokens))


def _agreement(got, ref) -> dict:
    import numpy as np

    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return {
        "max_abs_diff": float(np.max(np.abs(got - ref))),
        "top1_agreement": float(np.mean(got.argmax(-1) == ref.argmax(-1))),
        "tol": LOGIT_TOL,
        "positions": int(got.shape[0]),
    }


def _serve(eng) -> dict:
    """The PROMPTS, greedy, through submit/run_until_done."""
    t0 = time.perf_counter()
    for p in PROMPTS:
        eng.submit(p, max_tokens=MAX_TOKENS)
    done = sorted(eng.run_until_done(), key=lambda r: r.rid)
    dt = time.perf_counter() - t0
    n_tok = sum(len(r.out_tokens) for r in done)
    st = eng.stats()
    return {
        "tokens": [r.out_tokens for r in done],
        "statuses": [r.status for r in done],
        "tok_s": n_tok / dt, "wall_s": dt, "generated": n_tok,
        "prefill_tok_s": st["prefill_tok_s"], "decode_tok_s": st["decode_tok_s"],
    }


def _kernel_report(bundle, params) -> tuple[list[dict], int]:
    """The kernel each LUT site shape runs, and how many compiled Pallas
    kernels (`tpu_custom_call`) the prefill step lowers to."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import autotune

    shapes = sorted({(s.d_in, s.d_out, s.lut.k, s.lut.v)
                     for s in bundle.lut_sites()})
    rows = []
    for d, m, k, v in shapes:
        for n in (SLOTS, SLOTS * CHUNK):
            ver, cfg, from_record = autotune.kernel_choice(
                n, m, d // v, k, v, dtype="float32")
            rows.append({"n": n, "d": d, "m": m, "version": ver,
                         **cfg.as_dict(), "from_record": from_record})
    caches = bundle.init_caches(SLOTS, MAX_SEQ, dtype=jnp.float32, abstract=True)
    batch = {"tokens": jax.ShapeDtypeStruct((SLOTS, CHUNK), jnp.int32),
             "cache_len": jax.ShapeDtypeStruct((SLOTS,), jnp.int32)}
    lowered = jax.jit(
        lambda p, b, c: bundle.forward_step(p, b, c, compute_dtype=jnp.float32)
    ).lower(params, batch, caches)
    return rows, lowered.as_text().count("tpu_custom_call")


def phase_engine(use_kernel: bool) -> dict:
    """Phases A and B: build, warm up, serve, compare with the reference."""
    import jax
    import numpy as np

    from repro.configs import build_model
    from repro.core.amm import Mode
    from repro.launch.compile_cache import init_compile_cache
    from repro.serving.engine import ServingEngine

    device = _tpu_devices(1)
    cache_dir = init_compile_cache()
    cache_events = collections.Counter()        # persistent-cache reads/writes
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_events.update([event.rsplit("/", 1)[-1]]))
    arch = _arch(use_kernel)
    bundle = build_model(arch, Mode.LUT_INFER)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(bundle.init)(jax.random.PRNGKey(0)))
    init_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    eng = ServingEngine(bundle, params, n_slots=SLOTS, max_seq=MAX_SEQ,
                        prefill_chunk=CHUNK)            # autotune warmup
    autotune_s = time.perf_counter() - t0
    before = dict(cache_events)
    t0 = time.perf_counter()
    eng.warmup()                  # compiles the prefill and decode steps
    compile_s = time.perf_counter() - t0
    warmup_cache = {k: cache_events[k] - before.get(k, 0)
                    for k in ("cache_hits", "cache_misses")}
    served = _serve(eng)
    got = np.asarray(eng.prefill_logits(CHECK_PROMPT))
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    t0 = time.perf_counter()
    check = _agreement(got, _reference_logits(arch, params, CHECK_PROMPT))
    reference_s = time.perf_counter() - t0
    out = {
        "device": device, "compile_cache": cache_dir,
        "arch": {f: getattr(arch, f) for f in (
            "name", "n_layers", "d_model", "d_ff", "vocab", "lut_k", "lut_v")},
        "lut_sites": len(bundle.lut_sites()),
        "init_s": init_s, "autotune_s": autotune_s, "compile_s": compile_s,
        "warmup_cache": warmup_cache,
        "reference_s": reference_s, "peak_bytes_in_use": peak,
        **served, "logits": check,
    }
    failures = []
    if check["max_abs_diff"] > LOGIT_TOL:
        failures.append(f"logits off the reference: {check}")
    if served["statuses"] != ["ok"] * len(PROMPTS):
        failures.append(f"request statuses {served['statuses']}")
    if use_kernel:
        out["kernels"], out["tpu_custom_calls"] = _kernel_report(bundle, params)
        if out["tpu_custom_calls"] == 0:
            failures.append("kernel path lowered to no compiled Pallas kernel")
    out["failures"] = failures
    return out


def phase_tp() -> dict:
    """Four chips: `--tp 4` engine vs the one-chip engine (which phase A
    holds to the reference); then the parameters are saved as the router
    phase's artifact."""
    import jax
    import numpy as np

    from repro.configs import build_model
    from repro.core.amm import Mode
    from repro.launch.compile_cache import init_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.serving.artifact import save_artifact
    from repro.serving.engine import ServingEngine

    device = _tpu_devices(4)
    init_compile_cache()
    arch = _arch(False)
    bundle = build_model(arch, Mode.LUT_INFER)
    params = jax.jit(bundle.init)(jax.random.PRNGKey(0))
    one = ServingEngine(bundle, params, n_slots=SLOTS, max_seq=MAX_SEQ,
                        prefill_chunk=CHUNK)
    logits_one = np.asarray(one.prefill_logits(CHECK_PROMPT))
    del one
    t0 = time.perf_counter()
    tp = ServingEngine(bundle, params, n_slots=SLOTS, max_seq=MAX_SEQ,
                       prefill_chunk=CHUNK, mesh=make_host_mesh(data=1, model=4))
    logits_tp = np.asarray(tp.prefill_logits(CHECK_PROMPT))
    tp_s = time.perf_counter() - t0
    del tp
    check = _agreement(logits_tp, logits_one)
    out = {"device": device, "tp4_build_and_prefill_s": tp_s,
           "tp4_vs_one_chip": check, "failures": []}
    if check["max_abs_diff"] > LOGIT_TOL:
        out["failures"].append(f"tp4 logits off the one-chip engine: {check}")
    WORK.mkdir(exist_ok=True)
    save_artifact(WORK / "artifact", bundle, params)
    return out


# ---------------------------------------------------------------------------
# parent side: no JAX here
# ---------------------------------------------------------------------------

def _env() -> dict:
    env = dict(os.environ)
    src = str(ROOT / "src")
    env["PYTHONPATH"] = src + (os.pathsep + env["PYTHONPATH"]
                               if env.get("PYTHONPATH") else "")
    env["JAX_DEFAULT_MATMUL_PRECISION"] = "highest"
    return env


def _run_phase(name: str) -> dict:
    """Run one phase in a child; echo its output; return its result."""
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, str(pathlib.Path(__file__).resolve()), "--phase", name],
        env=_env(), stdout=subprocess.PIPE, text=True, timeout=PHASE_TIMEOUT_S,
    )
    result = None
    for line in proc.stdout.splitlines():
        if line.startswith(RESULT_TAG):
            result = json.loads(line[len(RESULT_TAG):])
        else:
            print(f"  [{name}] {line}")
    if proc.returncode != 0 or result is None:
        raise SystemExit(f"chip_smoke: phase {name} failed (exit "
                         f"{proc.returncode})")
    result["process_s"] = time.perf_counter() - t0
    return result


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(port: int, path: str, body: dict | None = None, timeout: float = 600):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    return urllib.request.urlopen(req, timeout=timeout)


def _generate(port: int, prompt: list[int], stream: bool) -> dict:
    spec = {"prompt": prompt, "max_tokens": MAX_TOKENS, "stream": stream}
    t0 = time.perf_counter()
    with _http(port, "/generate", spec) as resp:
        if not stream:
            out = json.loads(resp.read())
        else:
            lines = [json.loads(raw) for raw in resp if raw.strip()]
            streamed = [ln["token"] for ln in lines if "token" in ln]
            out = lines[-1]
            if streamed != out["tokens"]:
                out["status"] = f"stream {streamed} != final {out['tokens']}"
    out["latency_s"] = time.perf_counter() - t0
    return out


def _serve_http(label: str, args: list[str]) -> dict:
    """Start launch/serve.py, send PROMPTS concurrently (the first one
    streamed), drain it with SIGTERM; the server must exit 0."""
    WORK.mkdir(exist_ok=True)
    port = _free_port()
    log_path = WORK / f"serve_{label}.log"
    cmd = [sys.executable, "-m", "repro.launch.serve", "--port", str(port),
           "--slots", str(SLOTS), "--max-seq", str(MAX_SEQ),
           "--prefill-chunk", str(CHUNK), *args]
    t0 = time.perf_counter()
    # a session of its own: on failure the whole group goes, replica
    # workers included, so none keeps holding a chip
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, env=_env(), cwd=ROOT, stdout=log,
                                stderr=subprocess.STDOUT, start_new_session=True)
    failures = []
    try:
        while True:
            if proc.poll() is not None:
                raise SystemExit(f"chip_smoke: server {label} exited "
                                 f"{proc.returncode} before ready:\n"
                                 + log_path.read_text()[-4000:])
            try:
                with _http(port, "/readyz", timeout=5) as r:
                    if r.status == 200:
                        break
            except OSError:
                pass
            if time.perf_counter() - t0 > READY_TIMEOUT_S:
                raise SystemExit(f"chip_smoke: server {label} not ready after "
                                 f"{READY_TIMEOUT_S}s:\n"
                                 + log_path.read_text()[-4000:])
            time.sleep(1.0)
        ready_s = time.perf_counter() - t0
        replies: list[dict | None] = [None] * len(PROMPTS)

        def send(i):
            replies[i] = _generate(port, PROMPTS[i], stream=(i == 0))

        threads = [threading.Thread(target=send, args=(i,))
                   for i in range(len(PROMPTS))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=PHASE_TIMEOUT_S)
        with _http(port, "/stats") as r:
            stats = json.loads(r.read())
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        # whatever of the group outlived the server (none after a clean
        # drain) goes, so the next phase finds its chips free
        with contextlib.suppress(ProcessLookupError):
            os.killpg(proc.pid, signal.SIGKILL)
        proc.wait()
    if any(r is None or r["status"] != "ok" for r in replies):
        failures.append(f"replies {replies}")
    if rc != 0:
        failures.append(f"server exit code {rc} after SIGTERM")
    return {
        "ready_s": ready_s, "exit_code": rc,
        "tokens": [r["tokens"] if r else None for r in replies],
        "latency_s": [r["latency_s"] if r else None for r in replies],
        "routed": {k: v.get("routed") for k, v in
                   stats.get("per_replica", {}).items()},
        "failures": failures,
    }


def _print_engine(label: str, r: dict) -> None:
    a = r["arch"]
    print(f"{label}: {a['name']} n_layers={a['n_layers']} d_model={a['d_model']} "
          f"d_ff={a['d_ff']} vocab={a['vocab']} lut_k={a['lut_k']} "
          f"lut_v={a['lut_v']} ({r['lut_sites']} LUT sites) on {r['device']}")
    c = r["warmup_cache"]
    print(f"  compile (warmup) {r['compile_s']:.2f}s, autotune {r['autotune_s']:.2f}s, "
          f"param init {r['init_s']:.2f}s, process {r['process_s']:.2f}s, "
          f"cache {r['compile_cache']} (warmup read {c['cache_hits']}, "
          f"wrote {c['cache_misses']} entries)")
    print(f"  served {r['generated']} tokens in {r['wall_s']:.3f}s = "
          f"{r['tok_s']:.2f} tok/s (prefill {r['prefill_tok_s']:.2f} tok/s, "
          f"decode {r['decode_tok_s']:.2f} tok/s), peak device memory "
          f"{r['peak_bytes_in_use']} B")
    c = r["logits"]
    print(f"  prefill logits vs float32 train_logits on CPU: max|diff| "
          f"{c['max_abs_diff']:.3e} (tol {c['tol']:.0e}), top-1 agreement "
          f"{c['top1_agreement']:.3f} over {c['positions']} positions "
          f"(reference {r['reference_s']:.1f}s)")
    for k in r.get("kernels", []):
        print(f"  kernel N={k['n']} {k['d']}->{k['m']}: v{k['version']} "
              f"bn={k['block_n']} bm={k['block_m']} bc={k['block_c']}"
              f"{'' if k['from_record'] else ' (no record)'}")
    if "tpu_custom_calls" in r:
        print(f"  prefill step lowers to {r['tpu_custom_calls']} compiled "
              f"Pallas kernel calls")


def _one_chip() -> dict:
    a = _run_phase("xla")
    _print_engine("phase A (XLA one-hot)", a)
    warm = _run_phase("xla")
    _print_engine("phase A-warm (XLA one-hot, second process)", warm)
    print(f"compile seconds: cold {a['compile_s']:.2f}, warm "
          f"{warm['compile_s']:.2f}")
    b = _run_phase("kernel")
    _print_engine("phase B (Pallas kernels)", b)
    c = _serve_http("random_init", ["--arch", ARCH_ID])
    print(f"phase C (HTTP launch/serve.py): ready after {c['ready_s']:.1f}s, "
          f"latencies {[round(x, 3) for x in c['latency_s']]}s, exit code "
          f"{c['exit_code']} after SIGTERM")
    failures = {n: r["failures"] for n, r in
                (("A", a), ("A-warm", warm), ("B", b), ("C", c))}
    # the second process must find every step program the first one wrote
    # (a cache that came warm with the machine leaves the first with none
    # to write); when the first did compile, the second must be faster
    wc = warm["warmup_cache"]
    if wc["cache_misses"] or not wc["cache_hits"]:
        failures["A-warm"].append(f"second process missed the cache: {wc}")
    if a["warmup_cache"]["cache_misses"] and warm["compile_s"] >= a["compile_s"]:
        failures["A-warm"].append("warm process compiled no faster than cold")
    # A-warm and C run phase A's program on its parameters: same tokens.
    # B's kernels round differently, so its greedy tokens may part from A's
    # after a near-tied centroid code; its logits check is what binds it.
    for n, r in (("A-warm", warm), ("C", c)):
        if r["tokens"] != a["tokens"]:
            failures[n].append(f"tokens differ from phase A: {r['tokens']} "
                               f"vs {a['tokens']}")
    print(f"phase B tokens equal phase A's: {b['tokens'] == a['tokens']}")
    _report(failures)
    return a["device"]


def _four_chips() -> dict:
    tp = _run_phase("tp")
    c = tp["tp4_vs_one_chip"]
    print(f"--tp 4 vs one-chip prefill logits: max|diff| "
          f"{c['max_abs_diff']:.3e} (tol {c['tol']:.0e}), top-1 agreement "
          f"{c['top1_agreement']:.3f} ({tp['tp4_build_and_prefill_s']:.1f}s "
          f"to build and run the tp=4 engine)")
    art = str(WORK / "artifact")
    router = _serve_http("router", ["--artifact", art, "--replicas", "4"])
    single = _serve_http("single", ["--artifact", art])
    print(f"router x4: ready after {router['ready_s']:.1f}s, routed per replica "
          f"{router['routed']}; single replica: ready after "
          f"{single['ready_s']:.1f}s; tokens identical: "
          f"{router['tokens'] == single['tokens']}")
    failures = {"tp": tp["failures"], "router": router["failures"],
                "single": single["failures"]}
    if router["tokens"] != single["tokens"]:
        failures["router"].append(f"tokens {router['tokens']} != single "
                                  f"replica {single['tokens']}")
    _report(failures)
    return tp["device"]


def _report(failures: dict) -> None:
    bad = {k: v for k, v in failures.items() if v}
    if bad:
        for k, v in bad.items():
            for f in v:
                print(f"FAILED {k}: {f}", file=sys.stderr)
        raise SystemExit(1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run the router and --tp 4 paths on four chips")
    ap.add_argument("--phase", choices=("xla", "kernel", "tp"),
                    help=argparse.SUPPRESS)        # child side
    args = ap.parse_args()
    if args.phase is not None:
        result = (phase_tp() if args.phase == "tp"
                  else phase_engine(args.phase == "kernel"))
        print(RESULT_TAG + json.dumps(result))
        return
    device = _four_chips() if args.four_chips else _one_chip()
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
