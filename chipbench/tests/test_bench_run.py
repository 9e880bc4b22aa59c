"""The command refuses to run without a TPU, and without the program."""
import json
import os
import shutil
import subprocess
import sys

from chipbench import run

ARGS = ["--workload", "mixed-1e4.plan", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, "-m", "chipbench.run", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _has_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(run.ROOT, {})
    assert p.returncode == run.NO_CHIP, p.stderr[-2000:]
    assert not _has_result(p.stdout)
    assert "TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not _has_result(p.stdout)
