"""Faults planted in the timed path, underneath a run, to show that the
check catches them: the tests plant them on the CPU, `bench/control.py
--faults` on the chip to read where they land.

`drop_<site>` stands for a wrong kernel or tiling at one kind of linear
site: the program serves `<site>` with the first eighth of its codebooks
(of its input rows, on a dense model) left out of the sum, as a tiling
that skips one block of the reduction would, in every layer served as
LUTs (every layer, on a dense model). `deep_<site>` does the same at one
site only, the last LUT site of the last layer but one. The sites are
the family's (`Layer.sites` that a plan can replace by LUTs).
"""

from __future__ import annotations

from typing import Callable

import jax
import numpy as np

from harness import weights


def state_unchanged(sysm) -> None:
    """Every engine step returns the cache it was given."""
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


def _drop_block(name: str, layer_of=None):
    """A fault that zeroes the first eighth of site `name`'s reduction (int8
    table rows of its first codebooks, or a dense weight's first input
    rows; of every expert, where the site is stacked over experts) in every
    layer of the kind, or in layer `layer_of(n_layers)` alone."""

    def plant(sysm) -> None:
        eng, model = sysm.engine, sysm.model
        n_layers = sysm.bundle.arch.n_layers
        only = None if layer_of is None else layer_of(n_layers)
        found = []
        first = 0
        for seg in eng.params["segments"]:
            n = jax.tree.leaves(seg)[0].shape[0]
            site = {s.name: s for s in model.family.layer(model.cfg, first).sites}.get(name)
            leaf = weights.at(seg, site.path) if site else None
            found.append((first, n, site, leaf))
            first += n
        lut = any(leaf is not None and "table_q" in leaf for *_, leaf in found)
        segments = []
        for seg, (first, n, site, leaf) in zip(eng.params["segments"], found):
            if site is not None:
                key = "table_q" if "table_q" in leaf else "w"
                x = leaf[key]
                axis = 2 if site.experts else 1      # the reduction, after layer (and expert)
                hit = np.arange(first, first + n) == only if only is not None \
                    else np.ones(n, bool)
                cut = max(1, x.shape[axis] // 8)
                if hit.any() and (key == "table_q" or not lut):
                    idx = (np.flatnonzero(hit),) + (slice(None),) * (axis - 1) + (slice(0, cut),)
                    x = x.at[idx].set(0)
                seg = weights.replace(seg, site.path, dict(leaf, **{key: x}))
            segments.append(seg)
        eng.params = dict(eng.params, segments=segments)
    return plant


def for_model(model) -> dict[str, Callable]:
    """Every fault a cell of `model` can have, by name."""
    out = {"state_unchanged": state_unchanged, "token_altered": token_altered}
    layers = model.layers()
    for spec in layers:
        for s in spec.sites:
            if s.lut:
                out.setdefault(f"drop_{s.name}", _drop_block(s.name))
    if len(layers) >= 2:
        deep = [s for s in layers[-2].sites if s.lut][-1].name
        out[f"deep_{deep}"] = _drop_block(deep, lambda n: n - 2)
    return out
