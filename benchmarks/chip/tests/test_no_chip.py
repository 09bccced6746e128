"""The harness refuses to report without a TPU, and without the program."""
import json
import os
import shutil
import subprocess
import sys

import tiny

RUN = ["--workload", "paper_mlp.torus256.sync", "--seed", "2147483711",
       "--seconds", "1", "--trace", "0"]


def _run(root):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    return subprocess.run(
        [sys.executable, str(root / "benchmarks" / "chip" / "run.py"), *RUN],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            continue
    return True


def test_refuses_on_cpu():
    p = _run(tiny.REPO_ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert "TPU" in p.stderr
    assert _no_result(p.stdout)


def test_refuses_without_the_program(tmp_path):
    shutil.copy(tiny.REPO_ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.CHIP_DIR, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
