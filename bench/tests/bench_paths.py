"""Puts the benchmark (`bench/`) and the program (`src/`) on sys.path for
the tests here, and builds `spec.Benchmark`s of tiny test cells."""

import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
FIXTURES = BENCH / "tests" / "fixtures"
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CELLS = {"tiny-lut.chat": ("tiny-lut", "tiny-chat"),
              "tiny-dense.chat": ("tiny-dense", "tiny-chat"),
              "tiny-lut.batch": ("tiny-lut", "tiny-batch")}


def tiny_benchmark():
    """BENCHMARK.json's metrics over the tiny cells under fixtures/."""
    from harness import spec

    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    data["configs"] = [
        {"name": n, "source": "test", "file": f"bench/tests/fixtures/configs/{n}.json",
         "reduced": [], "why": "test"} for n in ("tiny-lut", "tiny-dense")]
    data["workloads"] = [{"name": w, "config": c, "traffic": t, "chips": 1, "why": "test"}
                         for w, (c, t) in TINY_CELLS.items()]
    data["per_layer"] = [{k: v for k, v in m.items() if k != "workloads"}
                         for m in data["per_layer"]]
    return spec.Benchmark(ROOT, data=data, data_dir=FIXTURES)
