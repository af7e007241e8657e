"""A whole run of each cell at a tiny scale factor on the CPU, and the
refusals that keep the benchmark off any platform but the chip."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from perfbench import harness
from perfbench.tests.util import run_cell

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_is_correct_and_reports_its_metrics(tmp_path, workload):
    res = run_cell(tmp_path, workload)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    want = {m["name"] for m in harness.load_cell(workload).end_to_end}
    assert set(res["metrics"]) == want
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


def _run(cwd, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELLS[0], "--seed", "3",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no TPU" in p.stderr


def test_the_rehearsal_flag_refuses_a_cell_sized_scale():
    p = _run(ROOT, "--rehearsal-sf", "1")
    assert p.returncode != 0 and '"correct"' not in p.stdout


def test_benchmark_files_alone_fail(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns(".work", ".jax_cache", "__pycache__"))
    p = _run(tmp_path, "--rehearsal-sf", "0.01")
    assert p.returncode != 0 and '"correct"' not in p.stdout
