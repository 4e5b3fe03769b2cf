"""Serving launcher: batch mode (timed request burst) or an HTTP front end
over the continuous-batching engine with a LUT_INFER (int8 table) model.

  # serve a deployed artifact (the output of launch/train.py --lut):
  PYTHONPATH=src python -m repro.launch.serve --artifact /tmp/ckpt_artifact

  # HTTP front end (per-token streaming, /healthz /readyz /metrics,
  # graceful drain on SIGTERM — DESIGN.md §11.2):
  PYTHONPATH=src python -m repro.launch.serve --artifact <dir> --port 8000

  # crash-supervised: the engine runs in a worker process restarted from
  # the artifact on failure (DESIGN.md §11.4):
  PYTHONPATH=src python -m repro.launch.serve --artifact <dir> --port 8000 --supervise

  # tensor-parallel over 2 devices, bfloat16 compute:
  XLA_FLAGS=--xla_force_host_platform_device_count=2 \\
  PYTHONPATH=src python -m repro.launch.serve --artifact <dir> --tp 2 --dtype bfloat16

  # no artifact: randomly-initialized tables at the published width
  # (smoke/perf mode only; --layers/--d-model/--vocab cut it down):
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_1p7b --requests 8

In batch mode a warm-up request runs (and is discarded) before the timed
region so the reported tok/s measures steady state, not the one-off jit
compile of the two engine shapes. In HTTP mode the process exits 0 on a
clean drain and `server.EXIT_STRANDED` if the drain deadline expired with
requests unresolved.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, build_model, get_arch, reduce_arch
from repro.core.amm import Mode
from repro.launch.compile_cache import init_compile_cache
from repro.serving.engine import KV_DTYPES, ServingEngine
from repro.serving.sampling import SamplingParams


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--artifact", default=None,
                    help="path to a LUTArtifact directory (launch/train.py "
                         "--lut output): serve the DEPLOYED tables instead "
                         "of randomly-initialized ones")
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3_1p7b",
                    help="arch for random-init mode (ignored with --artifact: "
                         "the manifest carries the arch)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-tokens", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard tables/weights over "
                         "a (1, tp) ('data','model') mesh (needs >= tp "
                         "devices; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--dtype", choices=("float32", "bfloat16"), default="float32",
                    help="engine compute dtype; also keys the LUT autotune "
                         "warmup so tuned blocks match runtime")
    # paged KV cache (DESIGN.md §12)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: pooled pages + block tables with "
                         "prefix sharing and copy-on-write; tokens are "
                         "byte-identical to the dense engine")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (must divide --max-seq)")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="pool size in pages; default slots*max_seq/page_size"
                         "+1 (dense-equivalent) — pass less to overcommit "
                         "memory (exhaustion sheds, never OOMs)")
    ap.add_argument("--kv-dtype", choices=sorted(KV_DTYPES), default=None,
                    help="KV-cache storage dtype (default: compute dtype); "
                         "fp8 halves cache HBM, K/V are upcast at use")
    # speculative decoding (DESIGN.md §14)
    ap.add_argument("--spec-decode", action="store_true",
                    help="draft/verify speculative decoding: γ cheap draft "
                         "forwards per round, one batched target verify; "
                         "tokens are byte-identical to plain decode")
    ap.add_argument("--draft-plan", default="draft",
                    help="plan name to load the draft model from a multi-"
                         "plan artifact (see describe_artifact); 'target' "
                         "= explicit self-draft; random-init mode always "
                         "self-drafts")
    ap.add_argument("--spec-gamma", type=int, default=4,
                    help="speculation depth: draft tokens proposed per "
                         "verify forward (the verify shape is (slots, γ+1))")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature; 0 = greedy")
    ap.add_argument("--top-k", type=int, default=0, help="top-k filter; 0 disables")
    ap.add_argument("--top-p", type=float, default=1.0, help="nucleus mass; 1 disables")
    ap.add_argument("--seed", type=int, default=0, help="base sampling seed")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the untimed warm-up request (tok/s then "
                         "includes jit compile)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="run LUT sites through the fused Pallas v2 kernel "
                         "(random-init mode; artifacts carry their own "
                         "lut_use_kernel setting)")
    # random-init reductions (CI / laptop smoke: serve a tiny model); with
    # none of them, random-init mode serves the published architecture
    ap.add_argument("--layers", type=int, default=None,
                    help="random-init mode: reduce the arch to N layers")
    ap.add_argument("--d-model", type=int, default=None,
                    help="random-init mode: reduce the arch width")
    ap.add_argument("--vocab", type=int, default=None,
                    help="random-init mode: reduce the vocab")
    # HTTP front end (DESIGN.md §11.2)
    ap.add_argument("--port", type=int, default=None,
                    help="start the HTTP front end on this port instead of "
                         "the batch run (/generate streaming, /healthz, "
                         "/readyz, /metrics; SIGTERM drains gracefully)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--max-queue", type=int, default=256,
                    help="admission high-water mark: past it the lowest-"
                         "priority queued request is shed (HTTP mode)")
    ap.add_argument("--drain-timeout", type=float, default=30.0,
                    help="seconds SIGTERM waits for in-flight requests "
                         "before aborting them and exiting non-zero")
    ap.add_argument("--supervise", action="store_true",
                    help="run the engine in a crash-supervised worker "
                         "process restarted from the artifact (requires "
                         "--artifact; DESIGN.md §11.4)")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="consecutive worker crashes before the supervisor "
                         "gives up (with --supervise or --replicas)")
    # multi-replica router (DESIGN.md §15)
    ap.add_argument("--replicas", type=int, default=1,
                    help="run N crash-supervised engine replicas off the one "
                         "artifact behind an EngineRouter (requires "
                         "--artifact and --port; implies supervision)")
    ap.add_argument("--routing", choices=("least_loaded", "prefix_affinity"),
                    default="least_loaded",
                    help="router placement policy: least-loaded live replica, "
                         "or rendezvous-hash on the first KV page of the "
                         "prompt (same-prefix sessions share a replica and "
                         "its prefix cache) with load-based spill")
    ap.add_argument("--fault-json", default=None,
                    help="JSON FaultSpec (e.g. '{\"kill_at_step\": 4}') "
                         "injected into ONE replica's worker, for failover "
                         "testing (with --replicas)")
    ap.add_argument("--fault-replica", type=int, default=0,
                    help="replica index --fault-json applies to")
    args = ap.parse_args(argv)

    if args.replicas < 1:
        ap.error("--replicas must be >= 1")
    if args.replicas > 1:
        if not args.artifact:
            ap.error("--replicas > 1 requires --artifact (each replica's "
                     "worker restarts from the artifact directory)")
        if args.port is None:
            ap.error("--replicas > 1 requires --port")
        if args.tp > 1:
            ap.error("--replicas does not compose with --tp > 1")
    if args.fault_json is not None and args.replicas < 2:
        ap.error("--fault-json needs --replicas >= 2 (a survivor must "
                 "exist to fail over to)")
    if args.supervise and not args.artifact:
        ap.error("--supervise requires --artifact (the worker restarts "
                 "from the artifact directory)")
    if args.supervise and args.port is None:
        ap.error("--supervise requires --port (supervised batch mode is "
                 "not wired)")
    if args.supervise and args.tp > 1:
        ap.error("--supervise does not support --tp > 1 yet")
    if args.spec_decode and args.tp > 1:
        ap.error("--spec-decode does not compose with --tp > 1 (the draft "
                 "caches are host-managed)")

    init_compile_cache()
    if args.port is not None:
        return _serve_http(args)

    if args.artifact:
        from repro.serving.artifact import load_artifact

        art = load_artifact(args.artifact)
        bundle, params = art.bundle, art.params
        # per-site plans can mix kernel/XLA sites: report kernel use from
        # the registry, not a global flag
        use_kernel = any(
            s.lut is not None and s.lut.use_kernel for s in bundle.lut_sites()
        )
        source = f"artifact {args.artifact} ({art.arch_name})"
    else:
        arch = _random_init_arch(args)
        bundle = build_model(arch, Mode.LUT_INFER)
        # one compiled init: op by op, a published width compiles hundreds
        # of small programs
        params = jax.jit(bundle.init)(jax.random.PRNGKey(0))
        use_kernel = args.use_kernel
        source = f"random init ({arch.name})"

    mesh = None
    if args.tp > 1:
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(data=1, model=args.tp)

    compute_dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    eng = ServingEngine(
        bundle, params, n_slots=args.slots, max_seq=args.max_seq,
        prefill_chunk=args.prefill_chunk, compute_dtype=compute_dtype,
        mesh=mesh, **_paged_kwargs(args),
        **_resolve_draft(_spec_kwargs(args), args.artifact),
    )

    if not args.no_warmup:
        eng.warmup()          # compile both engine shapes off the clock

    key = jax.random.PRNGKey(1)
    t0 = time.time()
    for i in range(args.requests):
        key, k = jax.random.split(key)
        plen = int(jax.random.randint(k, (), 4, 24))
        prompt = list(range(i + 1, i + 1 + plen))
        eng.submit(
            prompt, max_tokens=args.max_tokens,
            sampling=SamplingParams(
                temperature=args.temperature, top_k=args.top_k,
                top_p=args.top_p, seed=args.seed + i,
            ),
        )
    done = eng.run_until_done()
    dt = max(time.time() - t0, 1e-9)
    total_tok = sum(len(r.out_tokens) for r in done)
    mode = "pallas kernel (autotuned v1/v2/fused)" if use_kernel else "XLA one-hot"
    st = eng.stats()
    tp = f", tp={args.tp}" if mesh is not None else ""
    print(f"{len(done)} requests, {total_tok} tokens in {dt:.1f}s "
          f"({total_tok/dt:.1f} tok/s, {args.slots} slots, LUT INT8 tables, "
          f"{mode}, {args.dtype}{tp}, {source}, "
          f"{eng.n_lut_shapes_tuned} LUT shapes autotuned)")
    print(f"  steps={st['steps']} prefill: {st['prefill_tokens']} tok / "
          f"{st['prefill_forwards']} fwd ({st['prefill_tok_s']:.1f} tok/s)  "
          f"decode: {st['decode_tokens']} tok / {st['decode_forwards']} fwd "
          f"({st['decode_tok_s']:.1f} tok/s)  "
          f"occupancy={st['decode_occupancy']:.2f}  "
          f"shape_cache_hits={st['shape_cache_hits']}")
    if args.paged:
        hits = (st["prefix_hits"] / st["prefix_lookups"]
                if st["prefix_lookups"] else 0.0)
        print(f"  pool: {st['kv_pages_resident']}/{st['kv_pages_total']} pages "
              f"resident (peak {st['kv_pages_peak']}, "
              f"util {st['pool_utilization']:.2f}, "
              f"{st['kv_bytes_resident']} B vs dense "
              f"{st['kv_bytes_dense_equiv']} B)  "
              f"prefix: {st['prefix_hits']} hits / {st['prefix_lookups']} "
              f"lookups ({hits:.2f}/req), {st['prefill_tokens_skipped']} "
              f"prefill tok skipped  cow={st['cow_copies']}  "
              f"shed={st['shed']}")
    if eng.spec is not None:
        print(f"  spec: γ={st['spec_gamma']} acceptance="
              f"{st['spec_acceptance_rate']:.2f} "
              f"target_forwards_per_token={st['target_forwards_per_token']:.2f} "
              f"({st['spec_rounds']} rounds, {st['spec_draft_forwards']} draft "
              f"fwd, {st['spec_bonus_tokens']} bonus)")
    for r in sorted(done, key=lambda r: r.rid)[:4]:
        print(f"  req {r.rid}: {r.out_tokens[:8]}...")


def _paged_kwargs(args) -> dict:
    """Engine kwargs for the paged pool + KV dtype. JSON-safe on purpose:
    the supervisor ships engine_kwargs to the worker process as JSON, and
    KV_DTYPES resolves the dtype string on the far side."""
    kw: dict = {}
    if args.paged:
        kw.update(paged=True, page_size=args.page_size, n_pages=args.n_pages)
    if args.kv_dtype is not None:
        kw["kv_dtype"] = args.kv_dtype
    return kw


def _spec_kwargs(args) -> dict:
    """Speculative-decoding kwargs, JSON-safe like _paged_kwargs: the
    supervisor ships these to the worker, which resolves `draft_plan`
    against the artifact on its side of the pipe."""
    kw: dict = {}
    if args.spec_decode:
        kw.update(spec_decode=True, spec_gamma=args.spec_gamma,
                  draft_plan=args.draft_plan)
    return kw


def _resolve_draft(engine_kwargs: dict, artifact: str | None) -> dict:
    """In-process half of the draft_plan handshake: swap the JSON-safe
    plan NAME for loaded draft_bundle/draft_params. Without an artifact
    (random-init smoke) the engine self-drafts."""
    plan = engine_kwargs.pop("draft_plan", None)
    if engine_kwargs.get("spec_decode") and plan is not None and artifact:
        from repro.serving.artifact import load_artifact

        art = load_artifact(artifact, plan=plan, restore_autotune=False)
        engine_kwargs.update(draft_bundle=art.bundle, draft_params=art.params)
    return engine_kwargs


def _random_init_arch(args):
    """The published arch with `lut_use_kernel` from the flag; cut down by
    `reduce_arch` only when --layers, --d-model or --vocab is given."""
    cuts = {
        field: value for field, value in (
            ("n_layers", args.layers), ("d_model", args.d_model),
            ("vocab", args.vocab),
        ) if value is not None
    }
    arch = get_arch(args.arch)
    if cuts:
        arch = reduce_arch(arch, **cuts)
    return dataclasses.replace(arch, lut_use_kernel=args.use_kernel)


def _serve_http(args) -> None:
    """HTTP front-end mode: build a backend (local pump or supervised
    worker), serve until SIGTERM drains it, exit with the drain code."""
    import asyncio

    from repro.serving.server import EnginePump, run_server

    engine_kwargs = dict(
        n_slots=args.slots, max_seq=args.max_seq,
        prefill_chunk=args.prefill_chunk, max_queue=args.max_queue,
        **_paged_kwargs(args), **_spec_kwargs(args),
    )
    if args.replicas > 1:
        import json

        from repro.serving.faults import FaultSpec
        from repro.serving.router import EngineRouter

        faults = None
        if args.fault_json is not None:
            faults = [None] * args.replicas
            faults[args.fault_replica] = FaultSpec.from_dict(
                json.loads(args.fault_json))
        backend = EngineRouter(
            args.artifact, replicas=args.replicas, routing=args.routing,
            engine_kwargs=engine_kwargs, faults=faults,
            supervisor_kwargs={"max_restarts": args.max_restarts},
        )
        if not backend.wait_ready(timeout=600) or not backend.healthy:
            print("no router replica came up", file=sys.stderr)
            sys.exit(1)
        source = (f"artifact {args.artifact} x{args.replicas} replicas "
                  f"({args.routing})")
    elif args.supervise:
        from repro.serving.supervisor import EngineSupervisor

        backend = EngineSupervisor(
            args.artifact, engine_kwargs=engine_kwargs,
            max_restarts=args.max_restarts,
        )
        if not backend.wait_ready(timeout=600) or not backend.healthy:
            print("supervised worker failed to come up", file=sys.stderr)
            sys.exit(1)
        source = f"supervised artifact {args.artifact}"
    else:
        compute_dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
        if args.artifact:
            from repro.serving.artifact import load_artifact

            art = load_artifact(args.artifact)
            bundle, params = art.bundle, art.params
            source = f"artifact {args.artifact} ({art.arch_name})"
        else:
            arch = _random_init_arch(args)
            bundle = build_model(arch, Mode.LUT_INFER)
            params = jax.jit(bundle.init)(jax.random.PRNGKey(0))
            source = f"random init ({arch.name})"
        mesh = None
        if args.tp > 1:
            from repro.launch.mesh import make_host_mesh

            mesh = make_host_mesh(data=1, model=args.tp)
        eng = ServingEngine(
            bundle, params, compute_dtype=compute_dtype, mesh=mesh,
            **_resolve_draft(engine_kwargs, args.artifact),
        )
        if not args.no_warmup:
            eng.warmup()          # compile both engine shapes before /readyz
        backend = EnginePump(eng)

    def on_started(fe):
        print(f"serving {source} on http://{fe.host}:{fe.port} "
              f"({args.slots} slots, max_queue={args.max_queue}; "
              f"SIGTERM drains, timeout {args.drain_timeout:.0f}s)",
              flush=True)

    code = asyncio.run(run_server(
        backend, args.host, args.port,
        drain_timeout_s=args.drain_timeout, on_started=on_started,
    ))
    sys.exit(code)


if __name__ == "__main__":
    main()
