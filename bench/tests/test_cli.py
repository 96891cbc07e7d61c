"""`bench/run.py` refuses to measure anything it cannot."""
import os
import shutil
import subprocess
import sys

from harness import BENCH

ROOT = BENCH.parent


def _run(cwd, env_extra):
    env = {**os.environ, **env_extra}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "internlm2-1.8b.prefill_offline", "--seed", "3", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
