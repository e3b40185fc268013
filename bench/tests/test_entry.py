"""The command's contract around a run: no TPU, no program, no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent


def _run(cwd, env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "flat-ace-k16",
                        "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
                       cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p.returncode, lines


def _is_result(line):
    try:
        return isinstance(json.loads(line), dict)
    except ValueError:
        return False


def test_no_tpu_no_result(tmp_path):
    """JAX's first device is the CPU: non-zero exit, no result line."""
    rc, lines = _run(REPO, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert rc != 0
    assert not any(_is_result(ln) for ln in lines)


def test_benchmark_files_alone_no_result(tmp_path):
    """A directory with BENCHMARK.json and bench/ only: non-zero exit, no
    result line."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    rc, lines = _run(tmp_path, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "c")})
    assert rc != 0
    assert not any(_is_result(ln) for ln in lines)
