"""The command refuses to run without a TPU, and refuses a checkout that
holds nothing but the benchmark."""

import os
import shutil
import subprocess
import sys

from yardstick.registry import ROOT


def _run(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(root / "bench" / "run.py"), *args], cwd=root, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_no_tpu_no_result():
    p = _run(ROOT, "--workload", "serve-flat.churn", "--seed", str(2**31 + 3),
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_alone_is_refused(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "--workload", "paper-task.mm", "--seed", "1", "--seconds", "1")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
