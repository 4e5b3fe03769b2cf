"""Whether what the timed engine served is correct.

After the window has closed, the reference (`bench/reference/`, the
configuration's `reference`) runs over two sets of sequences from the
engine's own output, its weights made again from the seed:

* served tokens: a sample of the requests the engine finished in the
  window (drawn from the seed, with the longest among them), each prompt
  with the tokens served for it. `gap` is the widest gap by which a served
  token's reference logit lies below the reference's best logit at that
  position (greedy serving).
* the cache: the slots still live when the window closed, as many
  positions as the engine had written for each. The family names the
  cached leaves (`cache`: keys and values, or one latent leaf), each
  (layers, slots, max_seq, rows, width); the reference's `layer` returns
  the same leaves. Per layer, each cached row of each leaf is compared
  with the reference's: relative error |program - reference| /
  |reference|.
  - `kv_dense` is the largest of these in the layers served dense (layer
    0 of the LUT model, every layer of the dense one): no centroid code
    lies upstream of them, so they agree to float32 rounding.
  - A LUT site's output is piecewise constant in its input: a cached row
    of a LUT layer equals the reference's to rounding unless one of its
    codes was decided the other way. A row further off than TAU is such
    a row. Float32 rounding decides a code the other way where an encode
    lands within rounding of a tie; the change spreads to later layers
    and positions, so the share of rows off grows with depth by at most a
    few hundredths per layer (see `weights.BRANCH_SCALE`). A wrong
    kernel, table or tiling at one kind of LUT site moves the input of
    every later layer and raises that share by a tenth or more in one
    layer; a fault confined to one site of one layer raises it by about
    half that (PERF.md).
    `kv1_share` is the share of layer 1's rows off (the first LUT layer:
    only rounding lies upstream); `kv_jump` the largest rise of that
    share from one layer to the next, over every layer.

The control (`control`) is the reference itself at the next precision
below the configuration's, read the same way against the reference: its
greedy token at every position of the same sequences, and its cached
rows.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights

ROWS = 512                   # logits are taken in blocks of this many rows
MIN_SERVED = 384             # served tokens the sample holds at least
MAX_SEQS = 24
KV_SLOTS = 4                 # live slots whose cache is compared
TAU = 1e-3                   # a cached row further off than this counts
HIGHEST = jax.lax.Precision.HIGHEST
DESCRIBE = {
    "gap": "widest gap of a served token's logit below the reference's best",
    "kv_dense": "largest relative error of a cached row in a dense layer",
    "kv1_share": f"share of layer-1 cached rows off by more than {TAU}",
    "kv_jump": f"largest rise from one layer to the next of the share of cached "
               f"rows off by more than {TAU}",
}


def sample(finished: list[dict[str, Any]], seed: int) -> list[dict[str, Any]]:
    """The longest finished request, then others in an order drawn from the
    seed, until MIN_SERVED served tokens (or MAX_SEQS requests)."""
    if not finished:
        return []
    by_len = sorted(finished, key=lambda r: (-(len(r["prompt"]) + len(r["tokens"])),
                                             r["idx"]))
    rest = by_len[1:]
    order = np.random.default_rng(seed ^ 0x5EED).permutation(len(rest))
    out, served = [by_len[0]], len(by_len[0]["tokens"])
    for j in order:
        if served >= MIN_SERVED or len(out) >= MAX_SEQS:
            break
        out.append(rest[j])
        served += len(rest[j]["tokens"])
    return out


class SlotLog:
    """Which request each engine slot last held (a slot's cache keeps that
    request's keys and values until the slot is admitted again)."""

    def __init__(self, engine, family):
        self.family = family
        self.last: dict[int, Any] = {}
        admit = engine._admit

        def logged():
            before = list(engine.slots)
            admit()
            for i, r in enumerate(engine.slots):
                if r is not None and r is not before[i]:
                    self.last[i] = r
        engine._admit = logged

    def cached(self, engine) -> tuple[list[list[int]], dict | None]:
        """Up to KV_SLOTS of the longest sequences the slots' caches hold,
        and their cached state as the engine wrote it, by the family's
        leaves: {name: (layers, slots, max_seq, rows, width)}."""
        held = []
        for i, r in self.last.items():
            n = r.n_prefilled + max(len(r.out_tokens) - 1, 0) if r.prefill_done \
                else r.n_prefilled
            if n > 0:
                held.append((n, r.rid, i, (r.prompt + r.out_tokens)[:n]))
        held = sorted(held, key=lambda x: (-x[0], x[1]))[:KV_SLOTS]
        if not held:
            return [], None
        idx = jnp.asarray([i for _, _, i, _ in held])
        segs = [self.family.cache(seg) for seg in engine.caches]
        kv = {name: jnp.concatenate([seg[name][:, idx] for seg in segs], axis=0)
              for name in segs[0]}
        return [seq for *_, seq in held], kv


class Reference:
    """The configuration's plain reference, run layer by layer over a few
    sequences padded to one length."""

    def __init__(self, model, seed: int, scale_shapes: dict, pad_len: int):
        cfg = self.cfg = model.cfg
        self.mod = model.reference
        self.key = weights.base_key(seed)
        self.pad_len = pad_len
        self.lut = weights.lut_layers(cfg)
        self.specs = model.layers()
        self._gen = {
            (spec, lut): jax.jit(lambda key, i, spec=spec, lut=lut: weights.gen_layer(
                spec, cfg, key, i, lut, scale_shapes))
            for spec, lut in set(zip(self.specs, self.lut))
        }
        self.g = jax.jit(model.gen_globals)(self.key)
        self._layer: dict = {}
        # ROWS rows of padding: a block of ROWS rows can start at any row
        self._final = jax.jit(lambda g, h: jnp.pad(self.mod.final_hidden(cfg, g, h),
                                                   ((0, ROWS), (0, 0))))

    def _layer_fn(self, precision):
        if precision not in self._layer:
            self._layer[precision] = jax.jit(
                lambda w, h: self.mod.layer(self.cfg, w, h, precision))
        return self._layer[precision]

    def forward(self, seqs: list[list[int]], precisions: tuple,
                on_layer: Callable | None = None) -> dict:
        """Final normalised hidden states of each sequence at each precision
        (padded by ROWS rows). `on_layer(i, j, {precision: {name: rows}})`
        sees each layer's cached leaves of sequence j."""
        hs = {}
        for p in precisions:
            hs[p] = []
            for s in seqs:
                toks = np.zeros((self.pad_len,), np.int32)
                toks[: len(s)] = s
                hs[p].append(self.mod.embed(self.g, jnp.asarray(toks)))
        for i, key in enumerate(zip(self.specs, self.lut)):
            w = self._gen[key](self.key, i)
            for j in range(len(seqs)):
                kv = {}
                for p in precisions:
                    hs[p][j], kv[p] = self._layer_fn(p)(w, hs[p][j])
                if on_layer is not None:
                    on_layer(i, j, kv)
            del w
        return {p: [self._final(self.g, h) for h in hs[p]] for p in precisions}


# ---------------------------------------------------------------------------
# the numbers
# ---------------------------------------------------------------------------

_FNS: dict = {}


def _jit(key, make):
    if key not in _FNS:
        _FNS[key] = jax.jit(make())
    return _FNS[key]


def _gaps(mod, g, hf, start, targets):
    """(best - logit of target) for ROWS rows of hf from `start`."""
    def make():
        def fn(g, hf, start, t):
            lg = mod.logits(g, jax.lax.dynamic_slice_in_dim(hf, start, ROWS), HIGHEST)
            return jnp.max(lg, axis=-1) - jnp.take_along_axis(lg, t[:, None], axis=-1)[:, 0]
        return fn
    return _jit(("gaps", mod), make)(g, hf, start, targets)


def _top(mod, g, hf, start, precision):
    """The first token at `precision` for ROWS rows of hf from `start`."""
    def make():
        def fn(g, hf, start):
            lg = mod.logits(g, jax.lax.dynamic_slice_in_dim(hf, start, ROWS), precision)
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)
        return fn
    return _jit(("top", mod, precision), make)(g, hf, start)


def widest_gap(mod, g, hf, targets: np.ndarray, first: int) -> float:
    """Widest (best - logit of target) over rows first..first+len(targets)-1."""
    worst = 0.0
    for a in range(0, len(targets), ROWS):
        b = min(a + ROWS, len(targets))
        t = np.zeros((ROWS,), np.int32)
        t[: b - a] = targets[a:b]
        gap = np.asarray(_gaps(mod, g, hf, np.int32(first + a), jnp.asarray(t)))
        worst = max(worst, float(gap[: b - a].max()))
    return worst


def _row_err(kp, kr, n):
    """Relative error of each valid cached row, (S, heads); rows >= n are 0."""
    def make():
        def fn(kp, kr, n):
            num = jnp.linalg.norm(kp - kr, axis=-1)
            den = jnp.maximum(jnp.linalg.norm(kr, axis=-1), 1e-30)
            valid = (jnp.arange(kp.shape[0]) < n)[:, None]
            return jnp.where(valid, num / den, 0.0)
        return fn
    return _jit(("rowerr", kp.shape), make)(kp, kr, n)


def _pick(a, i, j):
    """a[i, j] without a compile per index."""
    def make():
        def fn(a, i, j):
            return jax.lax.dynamic_index_in_dim(
                jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), j, 0, keepdims=False)
        return fn
    return _jit(("pick", a.shape), make)(a, np.int32(i), np.int32(j))


class KVStats:
    """Per layer: the largest row error and how many rows exceed TAU."""

    def __init__(self, lut: list[bool]):
        self.lut = list(lut)
        n_layers = len(lut)
        self.max = np.zeros(n_layers)
        self.over = np.zeros(n_layers, np.int64)
        self.rows = np.zeros(n_layers, np.int64)

    def add(self, i, err, n_valid):
        e = np.asarray(err)
        self.max[i] = max(self.max[i], float(e.max()))
        self.over[i] += int((e > TAU).sum())
        self.rows[i] += int(n_valid) * e.shape[1]

    def shares(self) -> np.ndarray:
        return self.over / np.maximum(self.rows, 1)

    def numbers(self) -> dict[str, float]:
        if not self.rows[0]:
            return {}
        out = {}
        dense = [m for m, lut in zip(self.max, self.lut) if not lut]
        if dense:
            out["kv_dense"] = float(max(dense))
        if any(self.lut):
            share = self.shares()
            if len(share) > 1 and self.lut[1]:
                out["kv1_share"] = float(share[1])
            out["kv_jump"] = float(max(np.diff(share, prepend=0.0)))
        return out

    def per_layer(self) -> list:
        return [[float(m), int(o), int(r)] for m, o, r in zip(self.max, self.over, self.rows)]


def check_served(ref: Reference, reqs: list[dict[str, Any]],
                 kv_seqs: list[list[int]], kv_prog: dict | None) -> tuple[dict, KVStats]:
    """The numbers of a run: `gap` over the served tokens of `reqs`, and the
    KV numbers of the live slots' sequences `kv_seqs` against the program's
    cached rows `kv_prog` ({name: (layers, slots, max_seq, rows, width)})."""
    seqs = [r["prompt"] + r["tokens"][:-1] for r in reqs]
    stats = KVStats(ref.lut)

    def on_layer(i, j, kv):
        j -= len(seqs)
        if j < 0:
            return
        n = len(kv_seqs[j])
        for name, r in kv[HIGHEST].items():
            stats.add(i, _row_err(_pick(kv_prog[name], i, j), r, n), n)

    hf = ref.forward(seqs + kv_seqs, (HIGHEST,), on_layer)[HIGHEST]
    out = {}
    if reqs:
        # position len(prompt)-1+j predicts served token j
        out["gap"] = max(widest_gap(ref.mod, ref.g, h, np.asarray(r["tokens"]),
                                    len(r["prompt"]) - 1) for r, h in zip(reqs, hf))
    out.update(stats.numbers())
    return out, stats


def control(ref: Reference, reqs: list[dict[str, Any]], kv_seqs: list[list[int]],
            precision=jax.lax.Precision.HIGH) -> tuple[dict, KVStats]:
    """The same numbers for the reference at `precision` in the program's
    place: the gap of its greedy token at every position of the sequences,
    and its cached rows against the reference's."""
    seqs = [r["prompt"] + r["tokens"][:-1] for r in reqs]
    stats = KVStats(ref.lut)

    def on_layer(i, j, kv):
        if j < len(seqs):
            return
        n = len(kv_seqs[j - len(seqs)])
        for name, r in kv[HIGHEST].items():
            stats.add(i, _row_err(kv[precision][name], r, n), n)

    hf = ref.forward(seqs + kv_seqs, (HIGHEST, precision), on_layer)
    out = {}
    if reqs:
        worst = 0.0
        for s, h, hc in zip(seqs, hf[HIGHEST], hf[precision]):
            for a in range(0, len(s), ROWS):
                b = min(a + ROWS, len(s))
                top = np.asarray(_top(ref.mod, ref.g, hc, np.int32(a), precision))
                gap = np.asarray(_gaps(ref.mod, ref.g, h, np.int32(a), jnp.asarray(top)))
                worst = max(worst, float(gap[: b - a].max()))
        out["gap"] = worst
    out.update(stats.numbers())
    return out, stats
