"""Each one-chip cell's whole run, rehearsed on the CPU at a tiny size, and
the command's refusal to run without a TPU."""
import json
import shutil
import subprocess
import sys

import pytest

from bench.tests._proc import ROOT, cpu_env, rehearse

CELLS = {"syn16.pairs.e03": 3000, "syn64.count.e16": 1500, "syn64.pairs.e18": 1500}


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_run_and_last_line(tmp_path, cell, trace):
    (out,) = rehearse(tmp_path, cell, CELLS[cell], "--trace", str(trace))
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == out["window"]["joins"] >= 1
    assert out["window"]["compiles"] == 0
    assert out["setup"]["compiles"] + out["setup"]["cache_loads"] >= 1
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    assert list(out)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())
    names = set(out["metrics"])
    if trace:
        # counters and spans are read on the CPU; device numbers are not
        assert {"plan_s", "candidates_per_result", "dispatches_per_join"} <= names
        assert not names & {"tile_eval_device_s", "tile_eval_roofline", "device_idle_share"}
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert names == {"join_s", "setup_s"}
        assert out["metrics"]["join_s"]["unit"] == "s"


def test_refuses_to_run_without_a_tpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "syn64.count.e16",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=cpu_env(tmp_path), capture_output=True, text=True,
        timeout=120,
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_refuses_to_run_with_the_benchmark_alone(tmp_path):
    # a checkout that holds BENCHMARK.json and bench/ but not the program
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = cpu_env(tmp_path)
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "syn64.count.e16",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
    json.loads((tmp_path / "BENCHMARK.json").read_text())
