"""``bench/run.py`` as a command: it refuses to measure without a chip or
without the program, and prints no result line then."""

import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ARGS = ["--workload", "rcv1.acpd.gap", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script] + ARGS, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _run(ROOT, os.path.join("bench", "run.py"))
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "TPU" in proc.stderr


def test_a_directory_of_only_the_benchmark_is_refused(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, os.path.join("bench", "run.py"))
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "src/repro" in proc.stderr
