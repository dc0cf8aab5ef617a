"""Fixtures for whole runs of the harness at a miniature size on the CPU:
a checkout root holding the miniature's manifest, configuration, traffic
and limits (``data/``) beside the benchmark's own readers and reference,
and the harness run in this process with its look for a chip skipped."""
from __future__ import annotations

import functools
import shutil
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT / "bench"))

PEAKS = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_root")
    (root / "BENCHMARK.json").write_text((DATA / "manifest.json").read_text())
    bench = root / "bench"
    bench.mkdir()
    for d in ("architectures", "metrics", "references"):
        shutil.copytree(ROOT / "bench" / d, bench / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for d in ("configs", "traffic", "limits"):
        shutil.copytree(DATA / d, bench / d)
    return root


@pytest.fixture
def harness_at(monkeypatch, capsys):
    """Run the harness in this process from a checkout root:
    ``go(root, *args)`` returns ``(rc, stdout lines)``."""
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)

    def go(root, *args):
        import run

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(root / ".c"))
        rc = run.main([str(a) for a in args], root=root,
                      require_tpu=False, peaks=PEAKS)
        return rc, capsys.readouterr().out.strip().splitlines()

    yield go
    jax.config.update("jax_compilation_cache_dir", before[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      before[1])


@pytest.fixture
def harness_run(tiny_root, harness_at):
    """The harness run from the miniature's root."""
    return functools.partial(harness_at, tiny_root)


@pytest.fixture
def kept_runs(monkeypatch):
    """Every ``readers.Run`` the harness builds, kept as it is read."""
    from harness import readers

    runs = []
    read_all = readers.read_all

    def keep(bench, specs, run):
        runs.append(run)
        return read_all(bench, specs, run)

    monkeypatch.setattr(readers, "read_all", keep)
    return runs
