"""Speculative decoding over shared-table LUT plans (DESIGN.md §14).

LUT-NN's per-site K/V/bits dial means one set of learned centroids resolves
under *two* plans: an aggressive all-LUT **draft** and a higher-fidelity
**target** that keeps selected sites dense (`LUTPlan.keeping_dense`). Both
deploy from the same LUT_TRAIN checkpoint into one multi-plan artifact, and
every table they share is byte-identical — so the draft model costs ~zero
extra weight memory, unlike a conventional separate draft network.

`SpecDecoder` replaces the engine's `(n_slots, 1)` decode step with a
draft/verify round:

1. **draft**: up to γ greedy `(n_slots, 1)` forwards through the draft
   model propose d_1..d_γ per slot (d_0 is the slot's last emitted token).
   The draft keeps its OWN dense `(n_slots, max_seq)` KV caches even when
   the engine is paged — rollback on the draft side is then pure
   `cache_len` bookkeeping.
2. **verify**: ONE target forward over `(n_slots, γ+1)` tokens
   [d_0..d_γ] — the chunked-prefill row-masked shape, so the target's jit
   cache stays at O(1) entries (prefill chunk, width-1 decode, and this
   one fixed verify width).
3. **accept/emit**: at verify position j the engine samples t_j from the
   target logits with the slot's own sampling params and PRNG counter
   `len(out_tokens) + j` — the exact stream key non-speculative decode
   would use for that token. The round emits t_0..t_{m-1} where m is the
   longest run with d_j == t_{j-1}: every emitted token is conditioned on
   an accepted prefix and drawn from the target's distribution with the
   token's own stream key, so output is byte-identical to the
   non-speculative engine in BOTH greedy and sampled modes. (Trade-off vs
   classic min(1, p/q) rejection sampling: slightly lower sampled-mode
   acceptance, in exchange for the seeded-stream determinism the test
   suite and replay tooling rely on.)
4. **rollback**: target-side, positions beyond the accepted prefix are
   already invalid by `cache_len` masking (dense) and additionally have
   their pages rewound to the free list (paged, PR 7 pool unref); draft-
   side, `cache_len` rewinds, with one masked catch-up forward only for
   slots that accepted all γ drafts plus the bonus token.

Per-slot γ_eff adapts to each request's remaining token budget and cache
headroom (and to a per-request `spec_decode=False` opt-out: γ_eff=0 rides
the verify forward as a plain width-1 decode). Acceptance counters surface
in `engine.stats()` → `/metrics`; `target_forwards_per_token < 1` is the
whole point.

Spec decoding requires position-indexed caches on both sides: bundles with
per-slot recurrent state (mamba conv/ssm, encdec cross-KV) cannot roll
back by bookkeeping, so the engine auto-disables with a warning — the same
seam as PR 7's prefix-sharing probe.
"""

from __future__ import annotations

import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.sampling import GREEDY, batch_arrays, sample_tokens

# counters contributed to engine.stats() (zeroed by reset_counters)
_COUNTER_KEYS = (
    "spec_rounds",
    "spec_slot_rounds",
    "spec_draft_forwards",
    "spec_prefill_forwards",
    "spec_verify_forwards",
    "spec_catchup_forwards",
    "spec_tokens_proposed",
    "spec_tokens_accepted",
    "spec_bonus_tokens",
    "spec_tokens_emitted",
    "spec_pages_rewound",
)


class SpecDecoder:
    """Draft/verify decode scheduler bolted onto a ServingEngine.

    Owns the draft model (bundle + params + dense KV caches + its own
    jitted row-masked step) and the accept/rollback bookkeeping; the
    target forward, sampling streams, slot lifecycle, and paged pool stay
    with the engine. Self-draft (draft == target) is valid and useful for
    smoke tests: acceptance is ~1.0 and output parity is trivially exact.
    """

    def __init__(self, engine: Any, draft_bundle: Any, draft_params: Any,
                 *, gamma: int, compute_dtype, kv_dtype):
        if gamma < 1:
            raise ValueError(f"spec_gamma={gamma} must be >= 1")
        t_arch, d_arch = engine.bundle.arch, draft_bundle.arch
        if (draft_bundle.kind, d_arch.vocab) != (engine.bundle.kind, t_arch.vocab):
            raise ValueError(
                f"draft bundle ({draft_bundle.kind}, vocab={d_arch.vocab}) is "
                f"not interchangeable with the target "
                f"({engine.bundle.kind}, vocab={t_arch.vocab})"
            )
        self.eng = engine
        self.gamma = gamma
        self.draft_bundle = draft_bundle
        self.draft_params = draft_params
        # dense draft caches regardless of engine paging: rollback is then
        # cache_len bookkeeping only, and the draft never touches the pool
        self.draft_caches = draft_bundle.init_caches(
            engine.n_slots, engine.max_seq, dtype=kv_dtype
        )
        self.cache_len = np.zeros((engine.n_slots,), np.int32)
        n_slots = engine.n_slots

        def draft_step(params, tokens, cache_len, caches, slot_mask):
            logits, new_caches = draft_bundle.forward_step(
                params, {"tokens": tokens, "cache_len": cache_len}, caches,
                compute_dtype=compute_dtype,
            )

            def merge(old, new):
                shape = [1] * old.ndim
                shape[1] = n_slots            # every leaf is (L, B, ...)
                return jnp.where(slot_mask.reshape(shape), new, old)

            return logits, jax.tree_util.tree_map(merge, caches, new_caches)

        self._draft_fn = jax.jit(draft_step)
        self.reset_counters()

    # ------------------------------------------------------------------
    def reset_counters(self) -> None:
        self._c: dict[str, int] = {k: 0 for k in _COUNTER_KEYS}

    def counters(self) -> dict[str, Any]:
        """Spec counters + derived rates, merged into engine.stats()."""
        c: dict[str, Any] = dict(self._c)
        c["spec_gamma"] = self.gamma
        prop = c["spec_tokens_proposed"]
        c["spec_acceptance_rate"] = (
            c["spec_tokens_accepted"] / prop if prop else 0.0
        )
        em = c["spec_tokens_emitted"]
        # per-SLOT verify participations over emitted tokens: plain decode
        # is exactly 1.0 by this measure, so < 1.0 isolates the speculation
        # win from batching occupancy (each slot-round emits 1+accepted)
        c["target_forwards_per_token"] = (
            c["spec_slot_rounds"] / em if em else 0.0
        )
        return c

    def reset_slot(self, slot: int) -> None:
        """Called by the engine on slot admit/retire."""
        self.cache_len[slot] = 0

    # ------------------------------------------------------------------
    def _draft_forward(self, toks: np.ndarray, mask: np.ndarray) -> jax.Array:
        logits, self.draft_caches = self._draft_fn(
            self.draft_params, jnp.asarray(toks), jnp.asarray(self.cache_len),
            self.draft_caches, jnp.asarray(mask),
        )
        self.eng._record(toks, tag="draft")
        return logits

    def mirror_prefill(self, toks, cache_len, mask, write_len) -> None:
        """Feed the same prompt chunk the target just consumed through the
        draft model, so the draft's dense cache tracks every prompt token.
        Called by the engine's _prefill_step with the SAME pre-update
        arrays its own forward used."""
        logits, self.draft_caches = self._draft_fn(
            self.draft_params, jnp.asarray(toks), jnp.asarray(cache_len),
            self.draft_caches, jnp.asarray(mask),
        )
        jax.block_until_ready(logits)      # draft prefill rides the timed path
        self.eng._record(toks, tag="draft")
        self._c["spec_prefill_forwards"] += 1
        adv = np.asarray(mask)
        self.cache_len[adv] = cache_len[adv] + write_len[adv]

    def _sample_grid(self, logits: jax.Array) -> np.ndarray:
        """Sample every (slot, verify position) with the slot's sampling
        params and PRNG counter len(out_tokens)+j — the exact stream keys
        non-speculative decode would use for those tokens."""
        eng = self.eng
        params = [
            (eng.slots[i].sampling if eng.slots[i] is not None else GREEDY)
            for i in range(eng.n_slots)
        ]
        if all(p.greedy for p in params):
            return np.asarray(jnp.argmax(logits, axis=-1))
        width = logits.shape[1]
        row_params = [p for p in params for _ in range(width)]
        counters: list[int] = []
        for i in range(eng.n_slots):
            base = len(eng.slots[i].out_tokens) if eng.slots[i] is not None else 0
            counters.extend(base + j for j in range(width))
        flat = sample_tokens(
            logits.reshape(eng.n_slots * width, -1),
            *batch_arrays(row_params, counters),
        )
        return np.asarray(flat).reshape(eng.n_slots, width)

    def _rewind_pages(self, slot: int) -> None:
        """Drop pages wholly beyond the accepted prefix back to the pool.
        Decode-extended pages are never prefix-registered, so unref sends
        them straight to the free list; the kept partial page was COW'd
        private before the verify wrote it."""
        eng = self.eng
        ps = eng.pool.page_size
        keep = -(-int(eng.cache_len[slot]) // ps)
        pages = eng.slot_pages[slot]
        while len(pages) > keep:
            eng.pool.unref(pages.pop())
            eng.block_tables[slot, len(pages)] = 0
            self._c["spec_pages_rewound"] += 1

    # ------------------------------------------------------------------
    def decode_round(self) -> None:
        """One spec round for every DECODE-phase slot: γ draft forwards,
        one (n_slots, γ+1) target verify, accept/emit, rollback."""
        eng = self.eng
        dec = [
            (i, r) for i, r in enumerate(eng.slots)
            if r is not None and r.prefill_done
        ]
        if not dec:
            return
        t0 = time.perf_counter()
        # per-slot speculation depth: remaining token budget (a round may
        # emit γ_eff+1 tokens), cache headroom (verify writes positions
        # s..s+γ_eff), and the per-request opt-out (γ_eff=0 rides the
        # verify forward as plain width-1 decode)
        gam: dict[int, int] = {}
        for i, r in dec:
            g = self.gamma if r.spec_decode is not False else 0
            g = min(g, r.max_tokens - len(r.out_tokens) - 1,
                    eng.max_seq - 1 - int(eng.cache_len[i]))
            gam[i] = max(g, 0)
        if eng.paged:
            for i, r in dec:
                if eng.slots[i] is not r:
                    continue              # shed while preparing another slot
                eng._prepare_slot_writes(i, gam[i] + 1)
            dec = [(i, r) for i, r in dec if eng.slots[i] is r]
            eng._flush_copies()
            if not dec:
                return
        self._c["spec_rounds"] += 1
        self._c["spec_slot_rounds"] += len(dec)
        s0 = {i: int(eng.cache_len[i]) for i, _ in dec}

        # ---- draft: greedy chain d_1..d_γeff per slot, batched row-masked
        drafts = {
            i: [r.out_tokens[-1] if r.out_tokens else r.prompt[-1]]
            for i, r in dec
        }
        for j in range(max(gam.values())):
            toks = np.zeros((eng.n_slots, 1), np.int32)
            mask = np.zeros((eng.n_slots,), bool)
            for i, _ in dec:
                if gam[i] > j:
                    toks[i, 0] = drafts[i][j]
                    mask[i] = True
            logits = self._draft_forward(toks, mask)
            nxt = np.asarray(jnp.argmax(logits[:, 0, :], axis=-1))
            self._c["spec_draft_forwards"] += 1
            for i, _ in dec:
                if gam[i] > j:
                    drafts[i].append(int(nxt[i]))
                    self.cache_len[i] += 1

        # ---- verify: ONE target forward at the FIXED (n_slots, γ+1)
        # shape; per-slot γ_eff rides write_len (paged) / causal masking
        # (dense padding writes land above every valid query position)
        width = self.gamma + 1
        toks = np.zeros((eng.n_slots, width), np.int32)
        cache_len = np.zeros((eng.n_slots,), np.int32)
        mask = np.zeros((eng.n_slots,), bool)
        write_len = np.zeros((eng.n_slots,), np.int32)
        for i, _ in dec:
            row = drafts[i]
            toks[i, : len(row)] = row
            cache_len[i] = s0[i]
            mask[i] = True
            write_len[i] = gam[i] + 1
        logits = jax.block_until_ready(eng._forward(toks, cache_len, mask, write_len))
        eng._record(toks)
        self._c["spec_verify_forwards"] += 1
        eng._counters["decode_forwards"] += 1

        # ---- accept / emit / rollback
        t = self._sample_grid(logits)
        catchup: list[tuple[int, int, int]] = []       # (slot, token, pos)
        for i, r in dec:
            g, d = gam[i], drafts[i]
            m = 1
            while m <= g and d[m] == int(t[i, m - 1]):
                m += 1
            self._c["spec_tokens_proposed"] += g
            self._c["spec_tokens_accepted"] += m - 1
            if g and m == g + 1:
                self._c["spec_bonus_tokens"] += 1
            emitted = 0
            for j in range(m):
                eng.cache_len[i] = s0[i] + j + 1
                tok = int(t[i, j])
                r.out_tokens.append(tok)
                emitted += 1
                self._c["spec_tokens_emitted"] += 1
                eng._counters["decode_tokens"] += 1
                eng._check_done_after_token(i, r, tok)
                if eng.slots[i] is not r:
                    break                 # EOS/budget: drop later accepts
            if eng.slots[i] is not r:
                continue                  # retired: _retire reset the slot
            # draft prefix through s0+emitted-1 holds the emitted tokens
            # (d_j == t_{j-1} for every accepted j); full-accept slots need
            # one catch-up write of d_γ at position s0+γ
            if emitted == g + 1 and g:
                catchup.append((i, d[g], s0[i] + g))
            else:
                self.cache_len[i] = s0[i] + emitted
            if eng.paged:
                self._rewind_pages(i)
        if catchup:
            toks = np.zeros((eng.n_slots, 1), np.int32)
            mask = np.zeros((eng.n_slots,), bool)
            for i, tok, pos in catchup:
                toks[i, 0] = tok
                mask[i] = True
                self.cache_len[i] = pos
            self._draft_forward(toks, mask)           # logits discarded
            self._c["spec_catchup_forwards"] += 1
            for i, _, pos in catchup:
                self.cache_len[i] = pos + 1
        eng._counters["decode_s"] += time.perf_counter() - t0
