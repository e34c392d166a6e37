"""Every cell's path end to end on the CPU at a tiny size, and the
measuring path's refusal to run anywhere but on a TPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench import run as bench_run
from bench.tests.tiny import run_tiny

REPO = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
FAKE_TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_end_to_end(name):
    cell, res = run_tiny(name)
    assert res["correct"], res["checks"]
    line = bench_run.result_line(cell, SPEC, res, FAKE_TPU, trace=False)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    want = {m["name"] for m in SPEC["end_to_end"]
            if name in m.get("workloads", [name])}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    json.dumps(line)


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_per_layer_metrics(name, tmp_path):
    cell, res = run_tiny(name, trace=True, tmp=tmp_path)
    line = bench_run.result_line(cell, SPEC, res, FAKE_TPU, trace=True)
    assert res["correct"], res["checks"]
    names = {m["name"] for m in SPEC["per_layer"] if name in m["workloads"]}
    # no device plane on the CPU, so only host-side readers find something
    assert set(line["metrics"]) <= names
    assert line["metrics"], "no per-layer metric read on the CPU"
    assert list(line)[-1] == "checks"


def test_cpu_is_refused(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(REPO / "bench" / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_is_refused(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files has
    no system under test: the run fails and prints no result."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr


def test_wall_clock_sleeps_to_the_instant():
    c = harness.WallClock()
    c.jump_to(0.05)
    assert c.now() >= 0.05
    c.advance(1.0)  # the time has already passed: nothing to add
    assert c.now() < 1.0
    with pytest.raises(ValueError):
        c.advance(-1.0)
