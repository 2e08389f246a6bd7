"""``bench/run.py`` refuses, with no result line, where it cannot
measure: without a TPU, and in a copy that holds only the benchmark."""
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["--workload", "sage-dit-100m.themed-batch", "--seed", str(2 ** 33 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, **env):
    e = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    e.update(env)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=e, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run(ROOT, JAX_PLATFORMS="cpu")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, JAX_PLATFORMS="cpu")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr
