"""`bench/run.py` refuses to run without the cell's TPU chips, and in a
directory that holds only the benchmark's own files."""

import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ARGS = ["--workload", "qwen2-1.5b.offline", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env_extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_COMPILATION_CACHE_DIR")}
    env.update(env_extra)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_refuses_a_cpu_device():
    p = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr and "'cpu'" in p.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout == ""
    assert not (tmp_path / ".jax_cache").exists() or not any(
        (tmp_path / ".jax_cache").iterdir())
