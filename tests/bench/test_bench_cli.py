"""The harness refuses to run, and prints no result, without a chip or
without the program beside it."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ms150k.spread", "--seed",
         "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_exits_nonzero_without_a_tpu():
    proc = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0 and _no_result(proc.stdout)
    assert "TPU" in proc.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in man["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0 and _no_result(proc.stdout)
