"""Whole runs of the harness on the CPU at tiny sizes: a sound run is
correct, and the timed path broken underneath makes `correct` false."""

import json
import os
import subprocess
import sys

import bench_paths  # noqa: F401
import pytest
from bench_paths import BENCH, ROOT, tiny_benchmark
from harness import faults

import run

TINY = tiny_benchmark()
DENSE_FAULTS = faults.for_model(TINY.cell("tiny-dense.chat").model)


def _run(capsys, workload, *, trace=0, hook=None, seconds=2.0):
    rc = run.main(["--workload", workload, "--seed", str(2**31 + 5), "--seconds",
                   str(seconds), "--trace", str(trace)],
                  require_chip=False, engine_hook=hook, bench=TINY)
    out = capsys.readouterr()
    assert rc == 0, out.err
    return json.loads(out.out.strip().splitlines()[-1]), out


@pytest.fixture(autouse=True)
def _autotune_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))


def test_without_a_tpu_it_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "qwen3_1p7b-lut.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_a_sound_run_is_correct(capsys):
    res, out = _run(capsys, "tiny-lut.chat")
    assert res["correct"] is True, out.err
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(res)[-1] == "check"
    assert all(c["value"] <= c["limit"] for c in res["check"].values())
    # the CPU reports no memory: peak_hbm_gb stays out
    assert {"gap_p95_ms", "setup_s"} <= set(res["metrics"])
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "compilations in the window: 0" in out.out and "tokens/s, ttft p50/p90" in out.out
    assert out.err.strip().splitlines()[-1].startswith("check:")


@pytest.mark.parametrize("fault", sorted(DENSE_FAULTS))
def test_a_broken_timed_path_is_not_correct(capsys, fault):
    res, out = _run(capsys, "tiny-dense.chat", hook=DENSE_FAULTS[fault])
    assert res["correct"] is False, out.err
    assert any(c["value"] is not None and c["value"] > c["limit"]
               for c in res["check"].values())


# faults a test run shows on the tiny LUT model: its q, o, gate, up and
# down sites move too few codes at this size to be seen (the chip's
# readings of them at the cell's size are in PERF.md)
@pytest.mark.parametrize("workload", ["tiny-lut.chat", "tiny-lut.batch"])
@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered", "drop_k", "drop_v"])
def test_a_broken_lut_path_is_not_correct(capsys, fault, workload):
    res, out = _run(capsys, workload, hook=faults.for_model(TINY.cell(workload).model)[fault])
    assert res["correct"] is False, out.err
    assert any(c["value"] is not None and c["value"] > c["limit"]
               for c in res["check"].values())


def test_the_traced_run_reports_per_layer_metrics(capsys):
    res, out = _run(capsys, "tiny-lut.batch", trace=1)
    assert res["correct"] is True, out.err
    m = res["metrics"]
    assert {"decode_step_ms", "prefill_step_ms", "prefill_row_use"} <= set(m)
    assert 0 < m["prefill_row_use"]["value"] <= 100
    # the CPU has no device plane and no peaks: those readers stay silent
    assert not {"device_idle_share", "mfu.decode", "lut_roofline.decode"} & set(m)
