"""Faults planted in the timed path, underneath a run, to show that the
check catches them: the tests plant them on the CPU, `bench/control.py
--faults` on the chip to read where they land.

`drop_<site>` stands for a wrong kernel or tiling at one kind of linear
site: the program serves `<site>` with the first eighth of its codebooks
(of its input rows, on a dense model) left out of the sum, as a tiling
that skips one block of the reduction would, in every layer served as
LUTs (every layer, on a dense model). `deep_down` does the same at one
site only, the `down` projection of the last layer but one.
"""

from __future__ import annotations

import numpy as np

from harness import weights


def state_unchanged(sysm) -> None:
    """Every engine step returns the KV cache it was given."""
    eng = sysm.engine
    step = eng._step_fn

    def broken(params, tokens, cache_len, caches, mask, *rest):
        logits, _ = step(params, tokens, cache_len, caches, mask, *rest)
        return logits, caches
    eng._step_fn = broken


def token_altered(sysm) -> None:
    """Every sampled token moved to the next id where it is produced."""
    eng = sysm.engine
    sample = eng._sample
    vocab = sysm.bundle.arch.vocab
    eng._sample = lambda rows: (np.asarray(sample(rows)) + 1) % vocab


def _drop_block(site: str, layer_of=None):
    """A fault that zeroes the first eighth of `site`'s reduction (int8
    table rows of its first codebooks, or a dense weight's first input
    rows) in every layer of the kind, or in layer `layer_of(n_layers)`
    alone."""
    part = "attn" if site in ("q", "k", "v", "o") else "mlp"

    def plant(sysm) -> None:
        eng = sysm.engine
        n_layers = sysm.bundle.arch.n_layers
        only = None if layer_of is None else layer_of(n_layers)
        lut = any("table_q" in seg[part][site] for seg in eng.params["segments"])
        segments = []
        first = 0
        for seg in eng.params["segments"]:
            leaf = seg[part][site]
            key = "table_q" if "table_q" in leaf else "w"
            x = leaf[key]
            n, red = x.shape[0], x.shape[1]
            rows = np.arange(first, first + n)
            hit = rows == only if only is not None else np.ones(n, bool)
            cut = max(1, red // 8)
            if hit.any() and (key == "table_q" or not lut):
                x = x.at[np.flatnonzero(hit), :cut].set(0)
            seg = dict(seg, **{part: dict(seg[part], **{site: dict(leaf, **{key: x})})})
            segments.append(seg)
            first += n
        eng.params = dict(eng.params, segments=segments)
    return plant


FAULTS = {"state_unchanged": state_unchanged, "token_altered": token_altered}
FAULTS.update({f"drop_{s}": _drop_block(s) for s in weights.SITES})
FAULTS["deep_down"] = _drop_block("down", lambda n: n - 2)
