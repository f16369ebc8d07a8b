"""The entry point refuses to run without a chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT


def run_cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lra-train-n8k",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (json.JSONDecodeError, TypeError):
            pass
    return True


def test_no_tpu_exits_nonzero_without_a_result():
    p = run_cli(ROOT)
    assert p.returncode != 0
    assert no_result(p.stdout)
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("trace", ["0", "1"])
def test_benchmark_files_alone_exit_nonzero(tmp_path, trace):
    # a directory with BENCHMARK.json and the benchmark's own files only
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "lra-train-n8k",
         "--seed", "1", "--seconds", "1", "--trace", trace],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert no_result(r.stdout)


def test_unknown_workload_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "no-such-cell",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert no_result(r.stdout)
