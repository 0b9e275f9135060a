"""The command refuses, with no result line, where it cannot measure:
on a CPU, and in a checkout without the program."""
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return subprocess.run(
        [sys.executable, *spec["command"][1:], "--workload", "wiki-backlog",
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_a_cpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == "" or not p.stdout.strip().splitlines()[
        -1].startswith("{")
    assert "needs a TPU" in p.stderr


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "src/repro" in p.stderr
