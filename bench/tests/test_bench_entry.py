"""The command as the driver runs it: it refuses a machine without a
TPU, and a checkout without the program, before any work."""

import os
import shutil
import subprocess
import sys

from bench.tests.tiny import ROOT

ARGS = ["--workload", "qwen2-0.5b.chat", "--seed", "4294967311",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _printed_result(stdout: str) -> bool:
    return any(l.strip().startswith("{") for l in stdout.splitlines())


def test_refuses_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert not _printed_result(p.stdout)
    assert "platform cpu" in p.stderr and "count" in p.stderr


def test_refuses_in_a_checkout_of_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert not _printed_result(p.stdout)
