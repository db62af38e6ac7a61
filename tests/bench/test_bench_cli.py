"""The command fails, printing no result, where it cannot measure."""
import os
import shutil
import subprocess
import sys

from _bench import ROOT

CMD = [sys.executable, "bench/run.py", "--workload", "g500-s20-vertical.wcc",
       "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


def test_exits_nonzero_without_a_tpu():
    p = subprocess.run(CMD, cwd=ROOT, env=_env(), capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    p = subprocess.run(CMD, cwd=tmp_path, env=_env(), capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_workload_fails():
    bad = CMD[:3] + ["no-such-cell"] + CMD[4:]
    p = subprocess.run(bad, cwd=ROOT, env=_env(), capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
