"""``run.py`` refuses to measure without a chip or without the program."""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
ARGS = ["--workload", "wilson-24c48.light", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, run_py):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, run_py] + ARGS, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert "metrics" not in obj


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT, os.path.join(ROOT, "chipbench", "run.py"))
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    _no_result(p.stdout)


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run(tmp_path, str(tmp_path / "chipbench" / "run.py"))
    assert p.returncode != 0
    _no_result(p.stdout)
