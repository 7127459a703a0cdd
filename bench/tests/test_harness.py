"""A CPU rehearsal of whole runs at tiny sizes: control flow and the shape
of the last line. Nothing here is a measurement."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run as bench_run
from bench import spec

from _tiny import tiny

WORKLOADS = ["hd-batch-mag", "cam1080-moving", "cam1080-noisy"]


def _run(workload, trace, backend="xla", seconds=0.5):
    args = ["--workload", workload, "--seed", str(2**31 + 7),
            "--seconds", str(seconds), "--trace", str(trace)]
    return bench_run.run(args, require_tpu=False,
                         overrides=tiny(workload, backend))


@pytest.mark.parametrize("workload", WORKLOADS)
@pytest.mark.parametrize("trace", [0, 1])
def test_whole_run_on_the_cpu(workload, trace):
    res = _run(workload, trace)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    json.dumps(res)
    cell = spec.Cell(workload)
    if trace:
        # Device readers find nothing on the CPU; counters and spans do.
        names = {m["name"] for m in cell.per_layer}
        assert set(res["metrics"]) <= names and res["metrics"]
        assert "breakdown" in res and "window_s" in res["device"]
    else:
        assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
        assert all(v["value"] > 0 for v in res["metrics"].values())
    for name, row in res["checks"].items():
        assert row["value"] <= row["limit"], name


@pytest.mark.parametrize("workload", ["hd-batch-mag", "cam1080-moving"])
def test_pallas_interpret_path(workload):
    res = _run(workload, 0, backend="pallas-interpret", seconds=0.2)
    assert res["correct"] is True


def test_no_tpu_no_result():
    with pytest.raises(bench_run.RunError, match="no tpu"):
        bench_run.run(["--workload", "hd-batch-mag", "--seed", "1",
                       "--seconds", "1"], overrides=tiny("hd-batch-mag"))


def test_cli_without_a_tpu_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload",
         "hd-batch-mag", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "no tpu" in p.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hd-batch-mag",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
