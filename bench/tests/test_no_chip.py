"""Without a TPU the benchmark exits non-zero and prints no result: it never
falls back to the CPU.  Also so in a directory that holds only
``BENCHMARK.json`` and the benchmark's own files."""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import core

CELLS = [w["name"] for w in core.benchmark_spec()["workloads"]]


def _run(root: pathlib.Path, cell: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "3000000019",
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


@pytest.mark.parametrize("cell", CELLS)
def test_checkout_without_chip(cell):
    p = _run(core.ROOT, cell)
    assert p.returncode != 0 and _no_result(p.stdout)
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_benchmark_files_alone(tmp_path, cell):
    shutil.copy(core.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(core.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _run(tmp_path, cell)
    assert p.returncode != 0 and _no_result(p.stdout)
