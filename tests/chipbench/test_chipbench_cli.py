"""Off the chip, or without the program beside it, a run prints no result."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import run

ARGS = ["--workload", "rim.solve", "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "-m", "chipbench.run", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def _no_result(proc):
    for line in proc.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_on_the_cpu_the_run_exits_non_zero_with_no_result():
    proc = _run(run.ROOT)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    _no_result(proc)


def test_without_the_program_the_run_exits_non_zero_with_no_result(tmp_path):
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    for p in spec["paths"]:
        shutil.copytree(run.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert "no program" in proc.stderr
    _no_result(proc)


def test_an_unknown_workload_is_refused():
    with pytest.raises(SystemExit, match="unknown workload"):
        run.load_cell("nope.solve")
