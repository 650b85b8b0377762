"""The run command refuses to measure where it cannot: no TPU, or no
system under test beside the benchmark."""

import os
import shutil
import subprocess
import sys

import benchtest

CMD = [sys.executable, "bench/run.py", "--workload", "gpt2s.chat",
       "--seed", "3000000019", "--seconds", "1", "--trace", "0"]


def _env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _no_result(out: str) -> bool:
    return not any(line.startswith("{") for line in out.splitlines())


def test_exits_nonzero_without_tpu():
    p = subprocess.run(CMD, cwd=benchtest.ROOT, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no result" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(benchtest.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(benchtest.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(CMD, cwd=tmp_path, env=_env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert _no_result(p.stdout)
