"""``bench/run.py`` refuses, printing no result, where it cannot measure."""
import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT

ARGS = ["--workload", "paper-cg", "--seed", "4294967311", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(proc):
    assert proc.returncode != 0, proc.stdout
    for line in proc.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")


def test_refuses_without_a_tpu():
    proc = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    _no_result(proc)
    assert "program is missing" in proc.stderr
