"""The run command prints no result and exits non-zero on a CPU platform,
and in a checkout that holds only the benchmark's own files."""
import os
import shutil
import subprocess
import sys

from conftest import ROOT

ARGS = ["--workload", "gpt2-small-zip2x.decode-heavy", "--seed", "3",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_a_cpu_platform():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert "{" not in p.stdout
