"""The command fails, printing no result, where JAX finds no accelerator, and
where the checkout holds only the benchmark's files."""
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
CMD = [sys.executable, "bench/run.py", "--workload", "yi-9b.rag",
       "--seed", str(2**33 + 1), "--seconds", "10", "--trace", "0"]


def no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return "tokens_per_s" not in stdout and "ttft_p95_ms" not in stdout


def test_exits_nonzero_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(CMD, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert no_result(p.stdout)
    assert "no accelerator" in p.stderr


def test_exits_nonzero_without_the_program(tmp_path):
    root = tmp_path / "bare"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(CMD, cwd=root, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert no_result(p.stdout)
