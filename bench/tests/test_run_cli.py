"""bench/run.py refuses to run, and prints no result, off the TPU."""
import os
import shutil
import subprocess
import sys

from bench import harness


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "yi-6b.chat",
         "--seed", str(2**31 + 3), "--seconds", "5", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    lines = [l for l in p.stdout.splitlines() if l.strip()]
    return not any(l.lstrip().startswith("{") for l in lines)


def test_exits_nonzero_on_cpu():
    p = _run(harness.ROOT)
    assert p.returncode != 0 and _no_result(p)
    assert "needs a TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0 and _no_result(p)
