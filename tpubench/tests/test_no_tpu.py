"""A run that finds no TPU, or no program, exits non-zero and prints no
result."""

import json
import os
import shutil
import subprocess
import sys

from tpubench import registry


def _run(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "tpubench/run.py", "--workload",
         "flat-serve.poisson", "--seed", "3000000000", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _printed_a_result(stdout: str) -> bool:
    lines = [l for l in stdout.strip().splitlines() if l.strip()]
    if not lines:
        return False
    try:
        return "correct" in json.loads(lines[-1])
    except ValueError:
        return False


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(registry.CHECKOUT)
    assert p.returncode != 0
    assert not _printed_a_result(p.stdout)
    assert "TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(registry.CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(registry.HERE, tmp_path / "tpubench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert not _printed_a_result(p.stdout)
