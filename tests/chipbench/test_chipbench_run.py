"""The command refuses to run where it cannot measure the chip."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from chipbench_testing import ROOT, spec


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    cell = spec()["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell, "--seed", "2147483659",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    for line in p.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")


def test_without_a_tpu_it_exits_1_and_prints_no_result():
    p = _run(ROOT, {"PYTHONPATH": str(ROOT / "src")})
    assert p.returncode == 1, p.stderr[-2000:]
    assert "TPU" in p.stderr
    _no_result(p)


def test_without_the_program_it_exits_1_and_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in spec()["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d, ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": "src"})
    assert p.returncode == 1, p.stderr[-2000:]
    _no_result(p)
