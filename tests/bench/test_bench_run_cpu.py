"""bench/run.py refuses to measure without a TPU or without the program."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["bench/run.py", "--workload", "phi4-train", "--seed", "3000000019", "--seconds", "1", "--trace", "0"]


def _run(cwd, env):
    return subprocess.run([sys.executable, *ARGS], cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=600)


def _no_result(r):
    return r.returncode != 0 and not any(line.startswith("{") for line in r.stdout.splitlines())


def test_run_exits_nonzero_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    r = _run(ROOT, env)
    assert _no_result(r), r.stdout + r.stderr
    assert "needs a TPU" in r.stderr


def test_run_exits_nonzero_beside_only_the_benchmark(tmp_path):
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bm["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = _run(tmp_path, env)
    assert _no_result(r), r.stdout + r.stderr
    assert "sources are not" in r.stderr
