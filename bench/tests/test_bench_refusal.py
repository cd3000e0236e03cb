"""Without a TPU the benchmark exits non-zero and prints no result; so it
does in a tree that holds nothing but the benchmark's own files."""
import os
import shutil
import subprocess
import sys

from bench.tests.helpers import ROOT

ARGS = ["--workload", "rm1.nockpt", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "found no TPU" in p.stderr
    assert "{" not in p.stdout


def test_refuses_in_a_tree_of_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".scratch", ".jax_cache",
                                                  "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
