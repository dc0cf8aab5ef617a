"""The harness refuses to run without a TPU, and without the program."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]


def _run(root: Path, tmp: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp),
               TMPDIR=str(tmp))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL["name"],
         "--seed", str(2 ** 31 + 11), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result(tmp_path):
    out = _run(ROOT, tmp_path)
    assert out.returncode == 3, out.stderr[-2000:]
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    for p in manifest["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path, tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
