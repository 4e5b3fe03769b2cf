"""BENCHMARK.json and the files it names: found by name, names and units
checked."""

import json

import bench_paths  # noqa: F401
import pytest
from bench_paths import ROOT
from harness import spec

DATA = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys():
    assert set(DATA) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert DATA["paths"] == ["bench"] and DATA["command"] == ["python3", "bench/run.py"]
    assert 1 <= DATA["run_seconds"] <= 51


def test_every_cell_finds_its_files():
    b = spec.Benchmark()
    for name in b.workloads:
        cell = b.cell(name)
        assert cell.config["name"] == cell.config_name
        assert {"engine", "load", "check"} <= set(cell.settings)
        assert cell.traffic["arrivals"] in ("poisson", "closed")


def test_every_per_layer_metric_has_a_reader():
    b = spec.Benchmark()
    for m in b.per_layer:
        assert callable(spec.load_reader(m.name))
        assert m.moves in {e.name for e in b.end_to_end}
        assert m.workloads is None or set(m.workloads) <= set(b.workloads)


def test_each_cell_reports_setup_and_another_end_to_end_and_a_layer():
    b = spec.Benchmark()
    for w in b.workloads:
        e2e = {m.name for m in b.metrics_for(w, trace=False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert b.metrics_for(w, trace=True)


def test_bounds():
    for m in DATA["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("name", ["ok", "a.b-c_d", "_x", "9lives"])
def test_names_accepted(name):
    assert spec.check_name(name, "t") == name


@pytest.mark.parametrize("name", ["", "a b", "a/b", "a,b", "-x", ".x", "é", "x" * 65, 3])
def test_names_refused(name):
    with pytest.raises(ValueError):
        spec.check_name(name, "t")


@pytest.mark.parametrize("unit", ["ms", "tokens/s", "%", "GB", "s"])
def test_units_accepted(unit):
    assert spec.check_unit(unit, "t") == unit


@pytest.mark.parametrize("unit", ["", "tokens per s", "µs", "a" * 17, "ms;"])
def test_units_refused(unit):
    with pytest.raises(ValueError):
        spec.check_unit(unit, "t")


def test_a_bad_name_in_the_file_is_refused():
    data = json.loads(json.dumps(DATA))
    data["per_layer"][0]["name"] = "bad name"
    with pytest.raises(ValueError):
        spec.Benchmark(data=data)
    data = json.loads(json.dumps(DATA))
    data["end_to_end"][0]["unit"] = "tokens per second"
    with pytest.raises(ValueError):
        spec.Benchmark(data=data)


def test_unknown_workload_and_reader():
    b = spec.Benchmark()
    with pytest.raises(KeyError):
        b.cell("no-such-cell")
    with pytest.raises(FileNotFoundError):
        spec.load_reader("no_such_metric")
