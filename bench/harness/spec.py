"""`BENCHMARK.json` and the files it names, found by name.

A configuration is `configs/<name>.json`, wired by its family module
`families/<model_type>.py` and checked by `reference/<reference>.py`
(`harness.family`); a traffic mix is `traffic/<name>.json`, a cell's
engine and load settings `cells/<workload name>.json`, and a per-layer
metric `metrics/<name>.py`. Adding one of each is adding a file and an
entry; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
from typing import Any, Callable

from harness import family

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def check_name(name: Any, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ValueError(f"{what} {name!r}: a name is 1-64 of [A-Za-z0-9_.-], "
                         f"starting with a letter, a digit or _")
    return name


def check_unit(unit: Any, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise ValueError(f"{what} unit {unit!r}: 1-16 of [A-Za-z0-9_/%.-]")
    return unit


def _json(path: pathlib.Path) -> dict[str, Any]:
    if not path.is_file():
        raise FileNotFoundError(f"no file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    bound: float | None = None           # end-to-end metrics only
    layer: str | None = None             # per-layer metrics only
    moves: str | None = None
    workloads: tuple[str, ...] | None = None

    def applies_to(self, workload: str) -> bool:
        return self.workloads is None or workload in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    """One `workloads` entry with the files it names."""

    name: str
    chips: int
    config_name: str
    config: dict[str, Any]          # configs/<config>.json, as run
    traffic_name: str
    traffic: dict[str, Any]         # traffic/<traffic>.json
    settings: dict[str, Any]        # cells/<name>.json: engine and load
    model: family.Model             # the config with its family and reference


class Benchmark:
    """`BENCHMARK.json` at `root`; traffic, cell, family and reference files
    under `data_dir` (`bench/`). Tests hand in their own `data` and
    `data_dir`."""

    def __init__(self, root: pathlib.Path = ROOT, data: dict | None = None,
                 data_dir: pathlib.Path | None = None):
        self.root = root
        self.data_dir = data_dir or root / "bench"
        self.data = data if data is not None else _json(root / "BENCHMARK.json")
        self.end_to_end = [self._metric(m, e2e=True) for m in self.data["end_to_end"]]
        self.per_layer = [self._metric(m, e2e=False) for m in self.data["per_layer"]]
        names = [m.name for m in self.end_to_end + self.per_layer]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate metric names in {names}")
        self.configs = {check_name(c["name"], "config"): c for c in self.data["configs"]}
        self.workloads = {check_name(w["name"], "workload"): w
                          for w in self.data["workloads"]}

    @staticmethod
    def _metric(m: dict[str, Any], *, e2e: bool) -> Metric:
        if m["better"] not in ("lower", "higher"):
            raise ValueError(f"metric {m['name']}: better={m['better']!r}")
        wl = m.get("workloads")
        return Metric(
            name=check_name(m["name"], "metric"),
            unit=check_unit(m["unit"], f"metric {m['name']}"),
            better=m["better"], source=m["source"],
            bound=m.get("bound") if e2e else None,
            layer=None if e2e else m["layer"],
            moves=None if e2e else m["moves"],
            workloads=None if wl is None else tuple(wl),
        )

    def cell(self, name: str) -> Cell:
        if name not in self.workloads:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                           f"{sorted(self.workloads)}")
        w = self.workloads[name]
        cname = check_name(w["config"], "config")
        tname = check_name(w["traffic"], "traffic")
        config = _json(self.root / self.configs[cname]["file"])
        return Cell(
            name=name, chips=int(w["chips"]),
            config_name=cname,
            config=config,
            traffic_name=tname,
            traffic=_json(self.data_dir / "traffic" / f"{tname}.json"),
            settings=_json(self.data_dir / "cells" / f"{name}.json"),
            model=family.load(config, self.data_dir, self.root / "bench"),
        )

    def metrics_for(self, workload: str, *, trace: bool) -> list[Metric]:
        ms = self.per_layer if trace else self.end_to_end
        return [m for m in ms if m.applies_to(workload)]


def load_reader(name: str, root: pathlib.Path = ROOT) -> Callable[[Any], float | None]:
    """`read(ctx)` of `bench/metrics/<name>.py`: the metric's value, or None
    where the run has nothing to read it from."""
    path = root / "bench" / "metrics" / f"{check_name(name, 'metric')}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader {path.relative_to(root)}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
