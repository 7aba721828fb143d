"""The command refuses to run without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

import bench_tiny as BT

CMD = [sys.executable, os.path.join("benchmarks", "chip", "run.py"),
       "--workload", "phi3-w16-sweep", "--seed", str(2 ** 31 + 5),
       "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(CMD, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    p = _run(BT.REPO)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(BT.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(BT.REPO, BT.DATA),
                    os.path.join(tmp_path, BT.DATA),
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert "correct" not in p.stdout
