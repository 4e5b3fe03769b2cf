"""Slot-based continuous-batching serving engine.

vLLM-style control plane scaled to this repo: a fixed pool of B slots backed
by batched KV caches. Scheduler state machine (DESIGN.md §6):

    queue --admit--> PREFILL --(prompt consumed)--> DECODE --(done)--> retired

* **Batched admission** — every free slot is filled from the queue at the
  top of `step()`; all admitted (and still-prefilling) slots share ONE
  padded `(n_slots, prefill_chunk)` prefill forward per step, row-masked so
  untouched slots' caches never move (select-merge on per-slot `cache_len`).
* **Chunked prefill** — prompts longer than `prefill_chunk` consume exactly
  one fixed-size chunk per engine step, interleaved with the decode step of
  already-active slots, so decode latency stays bounded by one chunk
  forward. Every forward the engine ever issues therefore has one of
  exactly two token shapes — `(n_slots, prefill_chunk)` and `(n_slots, 1)`
  — which caps jit compile-cache growth at O(1) and lets the autotuner
  warm-up match runtime LUT shapes exactly (N = n_slots·prefill_chunk and
  N = n_slots).
* **Sampling** — per-request temperature/top-k/top-p/greedy with a
  deterministic per-request PRNG stream (repro.serving.sampling); the first
  token is sampled from the final prefill chunk's logits and checked
  against max_tokens/EOS immediately, so `max_tokens=1` returns exactly one
  token.
* **Observability** — `stats()` reports prefill/decode token and forward
  counts, wall-clock split, mean decode batch occupancy, and token-shape
  cache hits.
* **Request lifecycle** (DESIGN.md §11.1) — every request resolves to a
  terminal `status` in {ok, timeout, cancelled, shed, error}. `submit()`
  takes a `priority` and a relative `deadline_s`; expired or cancelled
  requests retire at the top of the next `step()` without burning another
  forward. With `max_queue` set, admission past the high-water mark sheds
  the lowest-priority queued request (arrivals lose priority ties) instead
  of growing the queue without bound. `run_until_done` never silently
  strands work: exhausting `max_steps` with requests still live raises (or,
  with `on_exhausted="strand"`, retires them as `error`).

The jitted step is the same `forward_step` the multi-pod dry-run lowers —
the engine is pure host-side orchestration, so it works identically on
1 CPU device and a 512-chip mesh. Limitation: padded prefill rows assume
position-indexed caches (attention masks padding causally); SSM state is
sequential, so mamba-family bundles need chunk-aligned prompts.

* **Paged KV cache** (DESIGN.md §12) — with `paged=True` the attention
  cache leaves become a pooled `(n_pages, page_size)` page set shared by
  all slots; the scheduler owns per-slot block tables, a free-list
  allocator with refcounted prefix sharing (`serving/kv_pool.py`), and
  copy-on-write. Prompt prefixes already resident skip their prefill
  chunks entirely; pool exhaustion preempts by shedding (status "shed"),
  never by raising. Token output is byte-identical to the dense engine.
* **Speculative decoding** (DESIGN.md §14) — with `spec_decode=True` the
  width-1 decode step becomes a draft/verify round (`serving/spec_decode`):
  γ cheap draft forwards through a shared-table draft plan, then ONE target
  verify over the fixed `(n_slots, γ+1)` shape, emitting up to γ+1 tokens
  per target forward. Output is byte-identical to non-speculative decode in
  both greedy and sampled modes; rejected positions roll back by cache_len
  bookkeeping (dense) plus page rewind (paged). Bundles with per-slot
  recurrent state auto-disable with a warning, the same seam as prefix
  sharing above.
* **Mesh-sharded construction** (DESIGN.md §6.4) — pass `mesh=` (and
  optionally `rules=`) and the engine becomes tensor-parallel: params are
  device_put under `distributed.sharding`'s specs (`table_q` column-sharded
  over M on "model", `table_scale`/`centroids` replicated), KV caches shard
  on the slot/batch axis (and sequence over "model" when divisible), and
  `step_fn` is jitted with explicit in/out shardings so GSPMD emits exactly
  the column-parallel psum the replaced matmul would need. The host-side
  scheduler is unchanged — sharding is a construction-time concern only.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ModelBundle
from repro.models.attention import PagedSpec
from repro.serving.kv_pool import KVPagePool
from repro.serving.sampling import GREEDY, SamplingParams, batch_arrays, sample_tokens
from repro.serving.spec_decode import SpecDecoder

# KV-cache storage dtypes accepted by name (process-boundary friendly:
# the supervisor ships engine kwargs as JSON). Sub-bf16 entries store K/V
# in 8 bits; _attend_stats upcasts at use (models/attention.py).
KV_DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "float8_e4m3fn": jnp.float8_e4m3fn,
    "float8_e5m2": jnp.float8_e5m2,
}


def _is_pool_leaf(path) -> bool:
    """True for paged-pool cache leaves (k_pool/v_pool) in a tree path."""
    return any(getattr(k, "key", None) in ("k_pool", "v_pool") for k in path)


def iter_lut_kernel_sites(cfg: Any, _seen: set[int] | None = None) -> Iterator[Any]:
    """Yield every LUT_INFER linear-site config under `cfg` that runs the
    fused kernel.

    Legacy duck-typed config walk (a site has d_in/d_out/mode/lut
    attributes), kept for callers that only hold a cfg; bundle-holding
    callers use the site registry (`ModelBundle.sites()`) instead.
    """
    if _seen is None:
        _seen = set()
    if cfg is None or id(cfg) in _seen:
        return
    _seen.add(id(cfg))
    if all(hasattr(cfg, a) for a in ("d_in", "d_out", "mode", "lut")):
        if getattr(cfg.mode, "value", cfg.mode) == "lut_infer" and cfg.lut.use_kernel:
            yield cfg
        return
    if dataclasses.is_dataclass(cfg) and not isinstance(cfg, type):
        children: Iterator[Any] = (
            getattr(cfg, f.name) for f in dataclasses.fields(cfg)
        )
    elif isinstance(cfg, (tuple, list)):
        children = iter(cfg)
    else:
        return
    for child in children:
        yield from iter_lut_kernel_sites(child, _seen)


def warm_lut_autotune(
    bundle: ModelBundle, token_counts: list[int], dtype: str = "float32"
) -> int:
    """Pre-tune kernel version + block sizes for every (LUT site x token
    count) pair.

    `dtype` must be the dtype the LUT sites will actually see at runtime
    (the engine's compute dtype) — the kernel keys its cache lookups on
    `str(x.dtype)`, so a mismatched dtype warms keys nobody reads.

    Default scoring is the analytic roofline model (fast: pure python); with
    REPRO_AUTOTUNE_MEASURE=1 each candidate (tiling × v1/v2/fused) is
    instead timed with compiled runs on the live backend
    (repro.kernels.measure — warmup + median-of-k), which is the honest
    mode on a real accelerator. Returns the number of (site, N) shapes
    tuned; winners persist in the autotune JSON cache.

    Record precedence (DESIGN.md §13.3): measured records — whether from a
    previous measured warmup or restored from a LUTArtifact's autotune
    snapshot — are never re-derived. Analytic records are kept as-is in
    analytic mode but are RE-TUNED when measurement is enabled: a measured
    winner always beats a projection.
    """
    from repro.core.amm import Mode
    from repro.kernels import autotune, measure

    backend = jax.default_backend()
    measure_live = measure.measure_enabled()
    cache = autotune.get_cache()
    tuned = set()
    # site registry walk (DESIGN.md §9.2): one entry per (site, layer), so
    # heterogeneous plans warm every distinct (m, c, k, v) signature
    for site in bundle.sites():
        if site.mode != Mode.LUT_INFER or site.lut is None or not site.lut.use_kernel:
            continue
        lut = site.lut
        c = site.d_in // lut.v
        for n in token_counts:
            key = ("lut_amm", n, site.d_out, c, lut.k, lut.v)
            if key in tuned:
                continue
            rec = cache.get(autotune.shape_key(*key, dtype, backend))
            if rec is not None and (not measure_live or rec.get("measured")):
                continue
            measure_fn = (
                measure.measure_lut_amm(*key[1:], dtype=dtype)
                if measure_live else None
            )
            autotune.tune(*key, dtype=dtype, save=False, measure=measure_fn)
            tuned.add(key)
    if tuned:
        try:
            autotune.get_cache().save()
        except OSError:
            # persistence is an optimization — winners stay in the
            # in-process cache; never fail serving over a cache file.
            pass
    return len(tuned)


# terminal request statuses (DESIGN.md §11.1); `status` is meaningful only
# once `done` is True — a live request always reads "ok"
STATUSES = ("ok", "timeout", "cancelled", "shed", "error")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_tokens: int = 16
    eos_id: int | None = None
    sampling: SamplingParams = GREEDY
    priority: int = 0                  # higher = evicted later under overload
    deadline: float | None = None      # absolute time.monotonic() deadline
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    status: str = "ok"
    n_prefilled: int = 0     # prompt tokens already consumed by chunk forwards
    submit_t: float = 0.0    # time.monotonic() at submit
    finish_t: float = 0.0    # time.monotonic() at terminal transition
    cancel_requested: bool = False
    spec_decode: bool | None = None   # per-request override; None = engine default

    @property
    def prefill_done(self) -> bool:
        return self.n_prefilled >= len(self.prompt)

    @property
    def ok(self) -> bool:
        return self.done and self.status == "ok"

    @property
    def latency_s(self) -> float:
        return max(self.finish_t - self.submit_t, 0.0) if self.done else 0.0

    def expired(self, now: float) -> bool:
        return self.deadline is not None and now >= self.deadline


class ServingEngine:
    def __init__(
        self,
        bundle: ModelBundle,
        params: Any,
        *,
        n_slots: int = 4,
        max_seq: int = 256,
        prefill_chunk: int = 32,
        compute_dtype=jnp.float32,
        autotune_lut: bool = True,
        mesh: Mesh | None = None,
        rules: Any | None = None,
        max_queue: int | None = None,
        faults: Any | None = None,
        paged: bool = False,
        page_size: int = 16,
        n_pages: int | None = None,
        prefix_sharing: bool = True,
        kv_dtype: Any | None = None,
        spec_decode: bool = False,
        draft_bundle: ModelBundle | None = None,
        draft_params: Any | None = None,
        spec_gamma: int = 4,
    ):
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue={max_queue} must be >= 1 (or None)")
        if not 1 <= prefill_chunk <= max_seq:
            raise ValueError(
                f"prefill_chunk={prefill_chunk} must be in [1, max_seq={max_seq}] "
                f"— no prompt could ever be admitted"
            )
        self.bundle = bundle
        self.params = params
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.prefill_chunk = prefill_chunk
        if rules is not None and mesh is None:
            mesh = rules.mesh
        if mesh is not None and rules is None:
            from repro.distributed.sharding import ShardingRules

            rules = ShardingRules(mesh)
        self.mesh = mesh
        self.rules = rules
        # speculative decoding (DESIGN.md §14): resolved BEFORE the autotune
        # warm-up so the (n_slots, γ+1) verify shape is part of the warmed
        # token counts, and before the paged block so prefix sharing can be
        # forced off (a prefix-skipped chunk would strand the draft's dense
        # cache, which must see every prompt token).
        self.spec: SpecDecoder | None = None
        if (draft_bundle is None) != (draft_params is None):
            raise ValueError("draft_bundle and draft_params come together")
        if spec_decode:
            if mesh is not None:
                raise ValueError(
                    "spec_decode does not compose with mesh-sharded "
                    "construction yet — the draft caches are host-managed")
            # rollback is cache_len bookkeeping (+ page rewind), which only
            # works for position-indexed caches: probe exactly like the
            # prefix-sharing seam — every leaf poolable <=> pure attention KV
            probe = jax.tree_util.tree_flatten_with_path(
                bundle.init_caches(n_slots, max_seq, abstract=True,
                                   paged=PagedSpec(n_pages=2, page_size=16))
            )[0]
            if not all(_is_pool_leaf(p) for p, _ in probe):
                warnings.warn(
                    "spec_decode disabled: bundle carries per-slot recurrent "
                    "state (mamba conv/ssm, encdec cross-KV) that cannot roll "
                    "back rejected tokens by cache_len bookkeeping; serving "
                    "continues non-speculatively")
                spec_decode = False
            else:
                prefix_sharing = False
        # the engine only ever issues two token shapes — (n_slots, 1) decode
        # and (n_slots, prefill_chunk) chunked prefill — plus, under spec
        # decoding, the fixed (n_slots, γ+1) verify — so the LUT warm-up is
        # exactly those N values, no ladder needed (DESIGN.md §3.3).
        if autotune_lut:
            counts = [n_slots, n_slots * prefill_chunk]
            if spec_decode:
                counts.append(n_slots * (spec_gamma + 1))
            self.n_lut_shapes_tuned = warm_lut_autotune(
                bundle, counts, dtype=jnp.dtype(compute_dtype).name,
            )
            if spec_decode and draft_bundle is not None:
                self.n_lut_shapes_tuned += warm_lut_autotune(
                    draft_bundle, [n_slots, n_slots * prefill_chunk],
                    dtype=jnp.dtype(compute_dtype).name,
                )
        else:
            self.n_lut_shapes_tuned = 0

        # KV storage dtype: defaults to the compute dtype; sub-bf16 (fp8)
        # halves cache HBM — _attend_stats upcasts at the dot
        if kv_dtype is None:
            kv_dtype = compute_dtype
        elif isinstance(kv_dtype, str):
            if kv_dtype not in KV_DTYPES:
                raise ValueError(
                    f"kv_dtype={kv_dtype!r}: pick one of {sorted(KV_DTYPES)}")
            kv_dtype = KV_DTYPES[kv_dtype]
        self.kv_dtype = kv_dtype

        # paged KV pool (DESIGN.md §12): attention cache leaves become a
        # shared (n_pages, page_size) pool; the scheduler owns block tables
        self.paged = bool(paged)
        paged_spec = None
        if self.paged:
            if max_seq % page_size:
                raise ValueError(
                    f"page_size={page_size} must divide max_seq={max_seq} "
                    f"(the block table covers exactly max_seq positions)")
            self.n_tables = max_seq // page_size
            if n_pages is None:
                # dense-equivalent capacity by default (+ the garbage page):
                # memory wins come from passing a smaller n_pages
                n_pages = n_slots * self.n_tables + 1
            paged_spec = PagedSpec(n_pages=n_pages, page_size=page_size)
            # prefix sharing is only sound when the ENTIRE cache state lives
            # in the pool: skipping a prefill chunk also skips computing any
            # per-slot recurrent state (mamba conv/ssm, encdec cross-KV) for
            # those tokens, which pages cannot carry. Auto-disable it for
            # such bundles — paging itself (tables, COW, shed) still works.
            if prefix_sharing:
                spec_leaves = jax.tree_util.tree_flatten_with_path(
                    bundle.init_caches(n_slots, max_seq, abstract=True,
                                       dtype=self.kv_dtype, paged=paged_spec)
                )[0]
                prefix_sharing = all(_is_pool_leaf(p) for p, _ in spec_leaves)
            self.pool = KVPagePool(n_pages, page_size, prefix_sharing=prefix_sharing)
            self.block_tables = np.zeros((n_slots, self.n_tables), np.int32)
            self.slot_pages: list[list[int]] = [[] for _ in range(n_slots)]
            self._pending_copies: list[tuple[int, int]] = []
        self.caches = bundle.init_caches(
            n_slots, max_seq, dtype=self.kv_dtype, paged=paged_spec
        )
        # bytes per pool page across all layers (0 when no attention leaves,
        # e.g. a pure-SSM bundle) — drives the kv_bytes_* gauges
        if self.paged:
            pool_bytes = sum(
                int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
                for path, leaf in jax.tree_util.tree_flatten_with_path(self.caches)[0]
                if _is_pool_leaf(path)
            )
            self._page_bytes = pool_bytes // n_pages
        if rules is not None:
            # place model state once at construction (DESIGN.md §6.4):
            # tables column-sharded / codebooks replicated per param_spec,
            # caches sharded on the slot axis (+ sequence over "model")
            self._param_shardings = rules.params_shardings(
                jax.eval_shape(lambda: params), bundle=bundle
            )
            self.params = jax.device_put(params, self._param_shardings)
            self._cache_shardings = rules.cache_shardings(
                jax.eval_shape(lambda: self.caches), n_slots
            )
            self.caches = jax.device_put(self.caches, self._cache_shardings)
        self.cache_len = np.zeros((n_slots,), np.int32)
        self.slots: list[Request | None] = [None] * n_slots
        self.queue: deque[Request] = deque()
        self.finished: list[Request] = []
        self.max_queue = max_queue
        self.faults = faults                 # FaultInjector hook (§11.3)
        self._next_rid = 0
        self._compute_dtype = compute_dtype
        self.reset_stats()

        def step_fn(params, tokens, cache_len, caches, slot_mask,
                    block_tables=None, write_len=None):
            batch = {"tokens": tokens, "cache_len": cache_len}
            if block_tables is not None:
                batch["block_tables"] = block_tables
                batch["write_len"] = write_len
            logits, new_caches = bundle.forward_step(
                params, batch, caches, compute_dtype=compute_dtype,
            )
            # merge: only the masked slots' cache rows advance. Pool leaves
            # carry no slot axis — their writes are already masked in-kernel
            # (invalid rows route to the garbage page), so they pass through.
            def merge(path, old, new):
                if _is_pool_leaf(path):
                    return new
                # every per-slot cache leaf is layer-stacked: (L, B, ...)
                shape = [1] * old.ndim
                shape[1] = n_slots
                m = slot_mask.reshape(shape)
                return jnp.where(m, new, old)

            merged = jax.tree_util.tree_map_with_path(merge, caches, new_caches)
            return logits, merged

        # one jitted row-masked forward serves both phases; the two token
        # shapes (chunk vs 1) are its only two compile-cache entries
        if rules is not None:
            # explicit in/out shardings: token rows ride the slot axis, and
            # the caches keep their construction-time layout across steps so
            # GSPMD never re-shards state between forwards
            row = NamedSharding(mesh, P(rules.batch_dim(n_slots)))
            tok = NamedSharding(mesh, P(rules.batch_dim(n_slots), None))
            logits_sh = NamedSharding(mesh, P(rules.batch_dim(n_slots), None, None))
            in_sh = [self._param_shardings, tok, row, self._cache_shardings, row]
            if self.paged:
                in_sh += [tok, row]     # block_tables ride the slot axis too
            self._step_fn = jax.jit(
                step_fn,
                in_shardings=tuple(in_sh),
                out_shardings=(logits_sh, self._cache_shardings),
            )
        else:
            self._step_fn = jax.jit(step_fn)

        if spec_decode:
            # self-draft (no draft bundle) is valid: acceptance ~1.0, used
            # by warmup smoke paths; a real deployment loads a cheaper plan
            # from the same multi-plan artifact (load_artifact(plan=...))
            self.spec = SpecDecoder(
                self,
                bundle if draft_bundle is None else draft_bundle,
                params if draft_params is None else draft_params,
                gamma=spec_gamma,
                compute_dtype=compute_dtype,
                kv_dtype=self.kv_dtype,
            )

    # ------------------------------------------------------------------
    def reset_stats(self) -> None:
        self._counters = {
            "steps": 0,
            "prefill_forwards": 0,
            "prefill_tokens": 0,          # valid prompt tokens (padding excluded)
            "prefill_s": 0.0,
            "decode_forwards": 0,
            "decode_tokens": 0,
            "decode_s": 0.0,
            "shape_cache_hits": 0,        # forwards that reused a seen token shape
            # terminal-status counters (DESIGN.md §11.1)
            "completed": 0,               # retired with status "ok"
            "timeout": 0,
            "cancelled": 0,
            "shed": 0,
            "error": 0,
            # prompt tokens satisfied from the prefix cache (never forwarded)
            "prefill_tokens_skipped": 0,
        }
        self._shapes_seen: set[tuple[Any, ...]] = set()
        if self.paged:
            self.pool.reset_counters()
        if getattr(self, "spec", None) is not None:
            self.spec.reset_counters()

    def stats(self) -> dict[str, Any]:
        """Scheduler counters since construction / the last reset_stats()."""
        c = dict(self._counters)
        c["queue_depth"] = len(self.queue)
        c["active_slots"] = sum(s is not None for s in self.slots)
        dec_f = c["decode_forwards"]
        # each decode forward advances one token per active slot, so tokens
        # per forward IS the occupancy
        c["decode_occupancy"] = (
            c["decode_tokens"] / (dec_f * self.n_slots) if dec_f else 0.0
        )
        c["prefill_tok_s"] = c["prefill_tokens"] / c["prefill_s"] if c["prefill_s"] else 0.0
        c["decode_tok_s"] = c["decode_tokens"] / c["decode_s"] if c["decode_s"] else 0.0
        c["lut_shapes_tuned"] = self.n_lut_shapes_tuned
        if self.paged:
            # pool gauges (DESIGN.md §12.4) — numeric, so server.py /metrics
            # exports each as lutnn_serving_<key> with no extra wiring
            pool = self.pool
            c["kv_pages_total"] = pool.n_allocatable
            c["kv_pages_free"] = pool.n_free
            c["kv_pages_cached"] = pool.n_cached
            c["kv_pages_shared"] = pool.n_shared
            c["kv_pages_resident"] = pool.n_resident
            c["kv_pages_peak"] = pool.peak_resident
            c.update(pool.counters)       # prefix_hits/lookups, cow_copies, ...
            c["kv_bytes_resident"] = pool.n_resident * self._page_bytes
            c["kv_bytes_peak"] = pool.peak_resident * self._page_bytes
            # what the dense per-slot layout would pin for the same leaves
            c["kv_bytes_dense_equiv"] = (
                self._page_bytes * self.n_slots * self.n_tables)
            c["pool_utilization"] = (
                pool.n_resident / pool.n_allocatable if pool.n_allocatable else 0.0)
        if self.spec is not None:
            # acceptance-rate / tokens-per-target-forward counters (§14.4);
            # numeric, so /metrics exports them with no extra wiring
            c.update(self.spec.counters())
        return c

    # ------------------------------------------------------------------
    def warmup(self) -> None:
        """Run (and discard) one throwaway request that compiles both engine
        token shapes — (n_slots, prefill_chunk) and (n_slots, 1) — off the
        clock, then re-arm the stats counters.

        The probe prompt is longer than one chunk when the cache allows so
        the multi-chunk prefill path warms, and short enough that submit()'s
        max_tokens cap still leaves a decode forward (max_tokens=2 must
        survive, or the decode shape would compile inside the timed region).
        """
        wlen = (self.prefill_chunk + 1
                if 2 * self.prefill_chunk <= self.max_seq
                else min(self.prefill_chunk, self.max_seq - 1))
        # spec engines warm one full draft/verify round too: γ+2 tokens
        # makes round one speculate at full depth, compiling the draft's
        # width-1 chain and the (n_slots, γ+1) verify off the clock
        max_tok = 2 if self.spec is None else self.spec.gamma + 2
        self.submit(list(range(1, wlen + 1)), max_tokens=max_tok)
        self.run_until_done()
        self.finished.clear()
        self.reset_stats()

    def prefill_logits(self, prompt: list[int]) -> jax.Array:
        """`(len(prompt), vocab)` logits of one prompt's prefill through the
        engine's own jitted step, in slot 0 of fresh caches — the served
        forward, for checking against a reference. Engine state (caches,
        slots, counters) is untouched."""
        if self.paged:
            raise ValueError("prefill_logits runs on dense caches only")
        if not 1 <= len(prompt) <= self.prefill_chunk:
            raise ValueError(f"prompt length {len(prompt)} must be in "
                             f"[1, prefill_chunk={self.prefill_chunk}]")
        toks = np.zeros((self.n_slots, self.prefill_chunk), np.int32)
        toks[0, : len(prompt)] = prompt
        mask = np.zeros((self.n_slots,), bool)
        mask[0] = True
        caches = self.bundle.init_caches(self.n_slots, self.max_seq,
                                         dtype=self.kv_dtype)
        if self.rules is not None:
            caches = jax.device_put(caches, self._cache_shardings)
        logits, _ = self._step_fn(
            self.params, jnp.asarray(toks),
            jnp.zeros((self.n_slots,), jnp.int32), caches, jnp.asarray(mask),
        )
        return logits[0, : len(prompt)]

    def submit(
        self,
        prompt: list[int],
        *,
        max_tokens: int = 16,
        eos_id: int | None = None,
        sampling: SamplingParams | None = None,
        priority: int = 0,
        deadline_s: float | None = None,
        spec_decode: bool | None = None,
    ) -> int:
        """Queue a request; returns its rid.

        `deadline_s` is relative (seconds from now); a request past its
        deadline retires with status "timeout" — queued or mid-generation —
        at the top of the next step, without burning further forwards.
        `priority` orders both admission (higher first) and overload
        shedding (lower evicted first). A request shed at submit time STILL
        gets a rid: it lands in `finished` with status "shed" immediately,
        so every rid ever returned resolves to a terminal status.
        """
        prompt = list(prompt) or [0]
        # chunk padding writes cache rows up to the padded length, so the
        # PADDED prompt must fit — an over-long prompt would otherwise have
        # its scatter writes silently dropped at the max_seq boundary.
        # (Paged mode routes out-of-range padding writes to the garbage
        # page, but the block table still only covers max_seq positions.)
        padded = -(-len(prompt) // self.prefill_chunk) * self.prefill_chunk
        if padded > self.max_seq:
            raise ValueError(
                f"prompt of {len(prompt)} tokens (chunk-padded to {padded}) "
                f"exceeds max_seq={self.max_seq}"
            )
        if max_tokens < 1:
            raise ValueError(f"max_tokens must be >= 1, got {max_tokens}")
        # per-request opt-IN needs an engine that actually has a draft;
        # opt-OUT (False) is always honored — the slot rides the verify
        # forward at γ_eff=0, token-identical to plain decode
        if spec_decode and self.spec is None:
            raise ValueError(
                "spec_decode=True requested but the engine was built without "
                "speculative decoding (spec_decode=False or auto-disabled)")
        if self.paged:
            # admission-time capacity in PAGE-POOL terms: a request that
            # could never hold enough pages even running alone must be
            # rejected here, not discovered as an endless shed loop later
            ps = self.pool.page_size
            need = -(-len(prompt) // ps)
            if need > self.pool.n_allocatable:
                raise ValueError(
                    f"prompt of {len(prompt)} tokens needs {need} pages; the "
                    f"pool only has {self.pool.n_allocatable} allocatable "
                    f"pages of {ps} (n_pages={self.pool.n_pages} incl. the "
                    f"reserved garbage page)"
                )
            # decode writes positions len(prompt) .. len(prompt)+max_tokens-2
            # (the final token is sampled but never fed back): cap against
            # the positions a lone request could actually be allocated
            cap = min(self.max_seq, self.pool.n_allocatable * ps)
            max_tokens = min(max_tokens, cap - len(prompt) + 1)
        else:
            # dense cache: every slot owns a full max_seq row
            max_tokens = min(max_tokens, self.max_seq - len(prompt) + 1)
        now = time.monotonic()
        rid = self._next_rid
        self._next_rid += 1
        req = Request(
            rid, prompt, max_tokens, eos_id, sampling or GREEDY,
            priority=priority,
            deadline=None if deadline_s is None else now + deadline_s,
            spec_decode=spec_decode,
        )
        req.submit_t = now
        # bounded queue (DESIGN.md §11.2): past the high-water mark, shed
        # the lowest-priority request — the newest among ties, so older
        # work at equal priority keeps its place and arrivals lose ties
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            self._sweep_queue(now)           # expired entries free space first
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            victim = min(reversed(self.queue), key=lambda r: r.priority)
            if victim.priority >= req.priority:
                self._finish_queued(req, "shed")
                return rid
            self.queue.remove(victim)
            self._finish_queued(victim, "shed")
        self.queue.append(req)
        return rid

    def cancel(self, rid: int) -> bool:
        """Cancel a live (queued or in-flight) request.

        Retires it immediately with status "cancelled" (partial out_tokens
        kept). Returns False when the rid is unknown or already terminal.
        Single-threaded like every engine call — front ends route cancels
        through the thread that owns the engine.
        """
        for req in self.queue:
            if req.rid == rid:
                self.queue.remove(req)
                self._finish_queued(req, "cancelled")
                return True
        for i, req in enumerate(self.slots):
            if req is not None and req.rid == rid:
                req.cancel_requested = True
                self._retire(i, req, "cancelled")
                return True
        return False

    def _finish_queued(self, req: Request, status: str) -> None:
        """Terminal transition for a request that never held a slot."""
        req.done = True
        req.status = status
        req.finish_t = time.monotonic()
        self._counters[status if status != "ok" else "completed"] += 1
        self.finished.append(req)

    def _sweep_queue(self, now: float) -> None:
        expired = [r for r in self.queue if r.expired(now)]
        for req in expired:
            self.queue.remove(req)
            self._finish_queued(req, "timeout")

    def _sweep(self) -> None:
        """Retire deadline-expired and cancelled requests — queued and
        in-flight alike — before any forward is issued this step."""
        now = time.monotonic()
        self._sweep_queue(now)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            if req.cancel_requested:
                self._retire(i, req, "cancelled")
            elif req.expired(now):
                self._retire(i, req, "timeout")

    def _admit(self) -> None:
        """Fill free slots from the queue, highest priority first (FIFO
        within a priority level). Pure bookkeeping — the admitted slots'
        prompts are consumed by the shared chunk forward in step().

        Paged mode also runs the prefix-cache lookup here: the longest
        chain of cached full-page prefixes of the prompt maps straight into
        the slot's block table and those tokens never reach a prefill
        forward — the chunked-prefill loop starts at the first unshared
        token. A fully-cached prompt is clamped to len-1 shared tokens (the
        final prompt token must still run one forward to produce the first
        output logits); its re-write into the shared final page is what
        exercises copy-on-write end-to-end."""
        for i in range(self.n_slots):
            if self.slots[i] is None and self.queue:
                req = max(self.queue, key=lambda r: (r.priority, -r.rid))
                self.queue.remove(req)
                self.slots[i] = req
                self.cache_len[i] = 0
                if self.spec is not None:
                    self.spec.reset_slot(i)
                if self.paged:
                    pages = self.pool.lookup_prefix(req.prompt)
                    shared = len(pages) * self.pool.page_size
                    if shared >= len(req.prompt):
                        shared = len(req.prompt) - 1
                    self.slot_pages[i] = pages
                    self.block_tables[i, :] = 0
                    self.block_tables[i, : len(pages)] = pages
                    req.n_prefilled = shared
                    self.cache_len[i] = shared
                    self._counters["prefill_tokens_skipped"] += shared

    def _retire(self, slot: int, req: Request, status: str = "ok") -> None:
        req.done = True
        req.status = status
        req.finish_t = time.monotonic()
        self._counters[status if status != "ok" else "completed"] += 1
        self.finished.append(req)
        self.slots[slot] = None
        self.cache_len[slot] = 0
        if self.spec is not None:
            self.spec.reset_slot(slot)
        if self.paged:
            for page in self.slot_pages[slot]:
                self.pool.unref(page)     # registered pages stay evictable
            self.slot_pages[slot] = []
            self.block_tables[slot, :] = 0

    # ---------------- paged allocation (DESIGN.md §12.3) ----------------
    def _shed_for_pages(self, needy_slot: int) -> bool:
        """Preemption-by-shedding: free pages by retiring the lowest-
        priority active request (the newest among ties, matching queue-shed
        semantics) with status "shed". Returns False when the victim was
        the needy request itself — the caller must stop allocating for it."""
        live = [(i, r) for i, r in enumerate(self.slots) if r is not None]
        vi, vr = min(live, key=lambda ir: (ir[1].priority, -ir[1].rid))
        self._retire(vi, vr, "shed")
        return vi != needy_slot

    def _alloc_page_for(self, slot: int) -> int | None:
        """One page for `slot`, shedding requests on pool exhaustion until
        one frees (alloc itself already reclaims evictable prefix pages
        first). Returns None only when `slot`'s own request was shed —
        allocation failure is always a clean `shed`, never an exception."""
        while True:
            page = self.pool.alloc()
            if page is not None:
                return page
            if not self._shed_for_pages(slot):
                return None

    def _prepare_slot_writes(self, slot: int, n_new: int) -> bool:
        """Make slot's block table safely writable for the next `n_new`
        logical positions: extend it with fresh pages, and copy-on-write
        any page in the write range that other requests or the prefix
        cache can still see. Returns False when the slot's request was
        shed during allocation (the caller drops it from this forward)."""
        ps = self.pool.page_size
        start = int(self.cache_len[slot])
        need = -(-(start + n_new) // ps)              # pages covering the write
        pages = self.slot_pages[slot]
        while len(pages) < need:
            page = self._alloc_page_for(slot)
            if page is None:
                return False
            self.block_tables[slot, len(pages)] = page
            pages.append(page)
        for pi in range(start // ps, need):
            if not self.pool.needs_cow(pages[pi]):
                continue
            dst = self._alloc_page_for(slot)
            if dst is None:
                return False
            # device copy happens in one batched transfer before the
            # forward (_flush_copies); bookkeeping moves over now
            self._pending_copies.append((pages[pi], dst))
            self.pool.unref(pages[pi])
            pages[pi] = dst
            self.block_tables[slot, pi] = dst
            self.pool.counters["cow_copies"] += 1
        return True

    def _flush_copies(self) -> None:
        """Apply all pending COW page copies to the device pool in one
        batched gather/scatter per K/V leaf."""
        if not self._pending_copies:
            return
        src = jnp.asarray([s for s, _ in self._pending_copies], jnp.int32)
        dst = jnp.asarray([d for _, d in self._pending_copies], jnp.int32)
        self._pending_copies = []

        def copy(path, leaf):
            if not _is_pool_leaf(path):
                return leaf
            return leaf.at[:, dst].set(leaf[:, src])   # (L, n_pages, ps, ...)

        self.caches = jax.tree_util.tree_map_with_path(copy, self.caches)

    def _register_prefixes(self, slot: int, req: Request) -> None:
        """Publish this request's fully-prefilled prompt pages to the
        prefix cache. K/V at a position depends only on tokens at or
        before it (causal), so a page wholly covered by prompt tokens is
        exactly determined by the token-id prefix that keys it."""
        ps = self.pool.page_size
        for pi in range(req.n_prefilled // ps):
            if (pi + 1) * ps > len(req.prompt):
                break
            self.pool.register_prefix(
                tuple(req.prompt[: (pi + 1) * ps]), self.slot_pages[slot][pi]
            )

    def _record(self, tokens: np.ndarray, tag: str = "target") -> None:
        # keyed per model: the draft has its own jit fn, so its first
        # forward at a shape the target already saw is still a compile
        shape = (tag,) + tuple(tokens.shape)
        if shape in self._shapes_seen:
            self._counters["shape_cache_hits"] += 1
        self._shapes_seen.add(shape)

    def _sample(self, logits_rows: jax.Array) -> np.ndarray:
        """Batched sample over all n_slots rows; callers read only the rows
        of slots they own (other rows ride along with greedy defaults)."""
        params = [
            (self.slots[i].sampling if self.slots[i] is not None else GREEDY)
            for i in range(self.n_slots)
        ]
        if all(p.greedy for p in params):
            # hot default: skip the sort/softmax/categorical machinery —
            # sample_tokens is argmax-identical for greedy rows
            return np.asarray(jnp.argmax(logits_rows, axis=-1))
        counters = [
            len(self.slots[i].out_tokens) if self.slots[i] is not None else 0
            for i in range(self.n_slots)
        ]
        return np.asarray(sample_tokens(logits_rows, *batch_arrays(params, counters)))

    def _check_done_after_token(self, slot: int, req: Request, tok: int) -> None:
        """Done-conditions run after EVERY sampled token — including the one
        produced by prefill, fixing the max_tokens off-by-one."""
        hit_eos = req.eos_id is not None and tok == req.eos_id
        out_of_cache = self.cache_len[slot] >= self.max_seq   # defensive; capped at submit
        if hit_eos or len(req.out_tokens) >= req.max_tokens or out_of_cache:
            self._retire(slot, req)

    # ------------------------------------------------------------------
    def _prefill_step(self) -> None:
        """One shared `(n_slots, prefill_chunk)` forward consuming the next
        chunk of every prefilling slot's prompt."""
        chunk = self.prefill_chunk
        pre = [
            (i, r) for i, r in enumerate(self.slots)
            if r is not None and not r.prefill_done
        ]
        if self.paged and pre:
            # page allocation + COW before the forward; preparation for one
            # slot can shed another (or itself) on pool exhaustion, so
            # re-check slot ownership after the whole pass
            for i, r in pre:
                if self.slots[i] is not r:
                    continue
                n = min(chunk, len(r.prompt) - r.n_prefilled)
                self._prepare_slot_writes(i, n)
            pre = [(i, r) for i, r in pre if self.slots[i] is r]
            self._flush_copies()
        if not pre:
            return
        toks = np.zeros((self.n_slots, chunk), np.int32)
        cache_len = np.zeros((self.n_slots,), np.int32)
        mask = np.zeros((self.n_slots,), bool)
        write_len = np.zeros((self.n_slots,), np.int32)
        n_new = {}
        for i, r in pre:
            part = r.prompt[r.n_prefilled : r.n_prefilled + chunk]
            toks[i, : len(part)] = part
            cache_len[i] = r.n_prefilled
            mask[i] = True
            n_new[i] = len(part)
            write_len[i] = len(part)
        t0 = time.perf_counter()
        step_args = (
            self.params, jnp.asarray(toks), jnp.asarray(cache_len),
            self.caches, jnp.asarray(mask),
        )
        if self.paged:
            step_args += (jnp.asarray(self.block_tables), jnp.asarray(write_len))
        logits, self.caches = self._step_fn(*step_args)
        logits = jax.block_until_ready(logits)
        self._record(toks)
        self._counters["prefill_forwards"] += 1
        self._counters["prefill_tokens"] += sum(n_new.values())
        self._counters["prefill_s"] += time.perf_counter() - t0
        if self.spec is not None:
            # the draft's dense cache must see every prompt token: mirror
            # the chunk with the SAME pre-update arrays the target consumed
            self.spec.mirror_prefill(toks, cache_len, mask, write_len)

        # sample the first output token for every slot whose prompt just
        # completed, from that slot's last valid position in this chunk
        last_idx = np.zeros((self.n_slots,), np.int32)
        finishing = []
        for i, r in pre:
            r.n_prefilled += n_new[i]
            self.cache_len[i] = r.n_prefilled
            if self.paged:
                self._register_prefixes(i, r)
            if r.prefill_done:
                last_idx[i] = n_new[i] - 1
                finishing.append((i, r))
        if not finishing:
            return
        rows = logits[jnp.arange(self.n_slots), jnp.asarray(last_idx)]
        nxt = self._sample(rows)
        for i, r in finishing:
            tok = int(nxt[i])
            r.out_tokens.append(tok)
            self._check_done_after_token(i, r, tok)

    def _decode_step(self) -> None:
        """One `(n_slots, 1)` forward advancing every DECODE-phase slot."""
        dec = [
            (i, r) for i, r in enumerate(self.slots)
            if r is not None and r.prefill_done
        ]
        if self.paged and dec:
            for i, r in dec:
                if self.slots[i] is not r:
                    continue
                self._prepare_slot_writes(i, 1)
            dec = [(i, r) for i, r in dec if self.slots[i] is r]
            self._flush_copies()
        if not dec:
            return
        toks = np.zeros((self.n_slots, 1), np.int32)
        mask = np.zeros((self.n_slots,), bool)
        write_len = np.zeros((self.n_slots,), np.int32)
        for i, r in dec:
            toks[i, 0] = r.out_tokens[-1] if r.out_tokens else r.prompt[-1]
            mask[i] = True
            write_len[i] = 1
        t0 = time.perf_counter()
        step_args = (
            self.params, jnp.asarray(toks), jnp.asarray(self.cache_len),
            self.caches, jnp.asarray(mask),
        )
        if self.paged:
            step_args += (jnp.asarray(self.block_tables), jnp.asarray(write_len))
        logits, self.caches = self._step_fn(*step_args)
        logits = jax.block_until_ready(logits)
        self._record(toks)
        self._counters["decode_forwards"] += 1
        self._counters["decode_tokens"] += len(dec)
        self._counters["decode_s"] += time.perf_counter() - t0

        nxt = self._sample(logits[:, 0, :])
        for i, r in dec:
            self.cache_len[i] += 1
            tok = int(nxt[i])
            r.out_tokens.append(tok)
            self._check_done_after_token(i, r, tok)

    def step(self) -> None:
        """One engine step: fault hook, lifecycle sweep, admit, one prefill
        chunk, one decode forward.

        Prefill consumes at most one chunk per step so long prompts cannot
        starve the decode of already-active slots (bounded decode latency).
        The sweep runs before admission so expired/cancelled requests never
        consume a forward, and a freed slot is re-admitted the same step.
        """
        if self.faults is not None:
            self.faults.on_step()        # may sleep, or raise Injected{Fault,Kill}
        self._counters["steps"] += 1
        self._sweep()
        self._admit()
        self._prefill_step()
        if self.spec is not None:
            self.spec.decode_round()
        else:
            self._decode_step()

    def has_work(self) -> bool:
        return bool(self.queue) or any(s is not None for s in self.slots)

    def run_until_done(
        self, max_steps: int = 1000, *, on_exhausted: str = "raise"
    ) -> list[Request]:
        """Step until all requests are terminal, or `max_steps` is spent.

        Exhausting `max_steps` with requests still live is a scheduler bug
        or an undersized budget — never silent: `on_exhausted="raise"` (the
        default) raises RuntimeError naming the stranded rids;
        `"strand"` retires them with status "error" and returns, so every
        rid still resolves to a terminal status.
        """
        if on_exhausted not in ("raise", "strand"):
            raise ValueError(f"on_exhausted={on_exhausted!r}")
        for _ in range(max_steps):
            if not self.has_work():
                break
            self.step()
        if self.has_work():
            stranded = [r.rid for r in self.queue] + [
                r.rid for r in self.slots if r is not None
            ]
            if on_exhausted == "raise":
                raise RuntimeError(
                    f"run_until_done exhausted max_steps={max_steps} with "
                    f"{len(stranded)} request(s) still live: rids {stranded}"
                )
            self.abort_all("error")
        return self.finished

    def abort_all(self, status: str = "error") -> list[Request]:
        """Retire every live request with a terminal `status` (no forward).

        Used by front ends when the engine itself dies (status "error") and
        by `run_until_done(on_exhausted="strand")`. Returns the aborted
        requests.
        """
        aborted = []
        while self.queue:
            req = self.queue.popleft()
            self._finish_queued(req, status)
            aborted.append(req)
        for i, req in enumerate(self.slots):
            if req is not None:
                self._retire(i, req, status)
                aborted.append(req)
        return aborted


# keys a front-end request spec may carry (HTTP body / supervisor wire format)
SPEC_KEYS = frozenset({
    "prompt", "max_tokens", "eos_id", "priority", "deadline_s",
    "temperature", "top_k", "top_p", "seed", "spec_decode",
})


def validate_spec(spec: dict[str, Any]) -> None:
    """Type-check a front-end request spec (SPEC_KEYS) without an engine.

    Shared by `submit_from_spec` and the process-boundary backends
    (`EngineSupervisor.submit`, `EngineRouter.submit`), which ship the spec
    to a worker process as-is: a malformed field must be rejected with a
    ValueError at the door — HTTP 400 — not discovered as a worker crash
    (or a confusing failure deep inside the engine) after the pipe hop.
    Raises ValueError; returns None on a well-formed spec.
    """
    if not isinstance(spec, dict):
        raise ValueError("request spec must be a JSON object")
    unknown = set(spec) - SPEC_KEYS
    if unknown:
        raise ValueError(f"unknown request fields: {sorted(unknown)}")
    prompt = spec.get("prompt")
    if not isinstance(prompt, (list, tuple)) or not all(
        isinstance(t, int) and not isinstance(t, bool) for t in prompt
    ):
        raise ValueError("prompt must be a list of ints")
    spec_decode = spec.get("spec_decode")
    if spec_decode is not None and not isinstance(spec_decode, bool):
        raise ValueError("spec_decode must be a bool")
    priority = spec.get("priority")
    if priority is not None and (
        isinstance(priority, bool) or not isinstance(priority, int)
    ):
        raise ValueError(f"priority must be an int, got {priority!r}")
    deadline_s = spec.get("deadline_s")
    if deadline_s is not None and (
        isinstance(deadline_s, bool) or not isinstance(deadline_s, (int, float))
    ):
        raise ValueError(f"deadline_s must be a number, got {deadline_s!r}")


def submit_from_spec(engine: "ServingEngine", spec: dict[str, Any]) -> int:
    """Submit a front-end request spec (a plain JSON-safe dict, SPEC_KEYS)
    to an engine. Shared by the HTTP server's pump and the supervised
    worker so both sides of the process boundary speak one format."""
    validate_spec(spec)
    sampling = None
    if any(k in spec for k in ("temperature", "top_k", "top_p", "seed")):
        sampling = SamplingParams(
            temperature=float(spec.get("temperature", 0.0)),
            top_k=int(spec.get("top_k", 0)),
            top_p=float(spec.get("top_p", 1.0)),
            seed=int(spec.get("seed", 0)),
        )
    return engine.submit(
        list(spec["prompt"]),
        max_tokens=int(spec.get("max_tokens", 16)),
        eos_id=spec.get("eos_id"),
        sampling=sampling,
        priority=spec.get("priority") or 0,
        deadline_s=spec.get("deadline_s"),
        spec_decode=spec.get("spec_decode"),
    )


class TokenTap:
    """Incremental observer of an engine's token output.

    Front ends (the HTTP server's pump thread, the supervised worker) call
    `poll()` after each `step()`; it diffs per-request `out_tokens` against
    what was already reported and returns
    `(token_events, finished_requests)` where `token_events` is a list of
    `(rid, new_tokens)` — including the final tokens of requests that
    retired this step, before their entry in `finished_requests`.

    With `consume=True`, reported entries are removed from
    `engine.finished` so a long-running server's memory stays bounded;
    leave it False when other code (e.g. `run_until_done`'s return) still
    reads the list.
    """

    def __init__(self, engine: "ServingEngine", *, consume: bool = False):
        self.engine = engine
        self.consume = consume
        self._emitted: dict[int, int] = {}
        self._drained = 0                 # index into engine.finished

    def _new_tokens(self, req: Request) -> list[int]:
        seen = self._emitted.get(req.rid, 0)
        fresh = req.out_tokens[seen:]
        if fresh:
            self._emitted[req.rid] = seen + len(fresh)
        return fresh

    def poll(self) -> tuple[list[tuple[int, list[int]]], list[Request]]:
        tokens: list[tuple[int, list[int]]] = []
        fin = self.engine.finished
        done = fin[self._drained:]
        for req in done:
            fresh = self._new_tokens(req)
            if fresh:
                tokens.append((req.rid, fresh))
            self._emitted.pop(req.rid, None)
        if self.consume:
            del fin[self._drained:]
        else:
            self._drained = len(fin)
        for req in self.engine.slots:
            if req is None:
                continue
            fresh = self._new_tokens(req)
            if fresh:
                tokens.append((req.rid, fresh))
        return tokens, done
