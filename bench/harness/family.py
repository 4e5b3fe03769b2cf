"""Model families: the module that wires a configuration to the program,
and the plain reference that checks it, both found by name.

A configuration file's `model_type` names its family module,
`families/<model_type>.py`, and its `reference` key its reference,
`reference/<reference>.py`, both under the benchmark's data directory
(or, for a test's own data directory, under `bench/`).
Adding a family is adding those two files; nothing here changes.

A family module gives, for a configuration `cfg`:

    arch(cfg)                    the program's ArchSpec; raises for what the
                                 program cannot serve as the file states
    layer(cfg, i)                layer i's norms and linear sites
                                 (`weights.Layer`): widths, where the
                                 program keeps each, expert stacking
    program_layer(w)             one layer's weights in the program's tree
    cache(segment)               the cached state `correct` compares, from
                                 one segment of the engine's caches:
                                 {name: (layers, slots, max_seq, rows, width)}
    attention(cfg)               one layer's attention (`work.Attention`):
                                 heads, score and value widths, bytes cached
                                 a position; with `layer`, what
                                 `work.forward_work` counts for `mfu.*`

and, where its model holds more outside the layers than a tied embedding
and a final norm (`weights.gen_globals`, `weights.program_globals`):

    gen_globals(cfg, key)        those weights, as the reference reads them
    program_globals(g)           the rest of the program's tree

and its reference module:

    embed(g, tokens) -> h
    layer(cfg, w, h, precision) -> (h, {name: (S, rows, width)}): the same
                                 named leaves `cache` gives, as a cache
                                 holds them
    final_hidden(cfg, g, h), logits(g, hf, precision)

A reference imports nothing of the program and is given nothing it made.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import pathlib
import re
import sys
from types import ModuleType
from typing import Any

from harness import weights

_LOADED: dict[pathlib.Path, ModuleType] = {}


def module(path: pathlib.Path) -> ModuleType:
    """The Python file at `path`, imported once."""
    path = path.resolve()
    if path not in _LOADED:
        if not path.is_file():
            raise FileNotFoundError(f"no module {path}")
        name = f"bench_{path.parent.name}_{path.stem}_{len(_LOADED)}"
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        _LOADED[path] = mod
    return _LOADED[path]


@dataclasses.dataclass(frozen=True, eq=False)
class Model:
    """A configuration with its family module and its reference."""

    cfg: dict[str, Any]
    family: ModuleType
    reference: ModuleType

    def layers(self) -> list[weights.Layer]:
        return [self.family.layer(self.cfg, i)
                for i in range(self.cfg["num_hidden_layers"])]

    def gen_globals(self, key) -> dict[str, Any]:
        gen = getattr(self.family, "gen_globals", weights.gen_globals)
        return gen(self.cfg, key)

    def program_globals(self, g: dict[str, Any]) -> dict[str, Any]:
        return getattr(self.family, "program_globals", weights.program_globals)(g)

    def lut_site_names(self) -> dict[tuple[int, int], str]:
        """{(d_in, d_out): site name} of the sites a LUT kernel call may
        serve (not stacked over experts), the first layer's name first."""
        out: dict[tuple[int, int], str] = {}
        for spec in self.layers():
            for s in spec.sites:
                if s.lut and not s.experts:
                    out.setdefault((s.d_in, s.d_out), s.name)
        return out


def load(cfg: dict[str, Any], *dirs: pathlib.Path) -> Model:
    """The configuration's Model, its modules taken from the first of
    `dirs` that holds them."""
    found = []
    for key, kind in (("model_type", "families"), ("reference", "reference")):
        name = cfg.get(key)
        if not isinstance(name, str) or not re.fullmatch(r"[A-Za-z0-9_]+", name):
            raise ValueError(f"configuration {cfg.get('name')!r}: {key} {name!r} "
                             f"names no module")
        paths = [d / kind / f"{name}.py" for d in dirs]
        found.append(module(next((p for p in paths if p.is_file()), paths[0])))
    return Model(cfg, *found)
