"""The run refuses any platform but a TPU before it prints a result."""

import os
import shutil
import subprocess
import sys

from chipbench import registry

ARGS = ["--workload", "job3.steady", "--seed", "1", "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join("chipbench", "run.py"), *ARGS],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_refuses_a_non_tpu_platform():
    proc = _run(registry.ROOT)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copytree(registry.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".traces", "__pycache__"))
    shutil.copy(os.path.join(registry.ROOT, "BENCHMARK.json"), tmp_path)
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
