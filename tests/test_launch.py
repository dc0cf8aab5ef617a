"""Launcher plumbing: the compile cache location, host meshes that
cannot be built, and launchers that change nothing when imported."""
import importlib
import os
import sys

import pytest

import jax
from jax.sharding import AxisType

from repro.launch import compile_cache
from repro.launch.mesh import make_host_mesh


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)
    compilation_cache.reset_cache()


def test_compile_cache_defaults_into_the_checkout(monkeypatch,
                                                  restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert compile_cache.enable_compile_cache() == path   # fixed, not fresh


def test_compile_cache_env_wins(monkeypatch, restore_cache_dir, tmp_path):
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "env"))
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path / "env"))
    assert compile_cache.enable_compile_cache() == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "env")


def test_host_mesh_is_auto_typed():
    mesh = make_host_mesh(1, 1)
    assert dict(mesh.shape) == {"data": 1, "model": 1}
    assert set(mesh.axis_types) == {AxisType.Auto}


def test_host_mesh_too_large_raises():
    n = len(jax.devices())
    with pytest.raises(ValueError, match="visible"):
        make_host_mesh(n + 1, 1)


def test_serve_mesh_flag_errors_instead_of_degrading(capsys):
    """``--mesh`` on too few devices stops the launcher; it never serves
    on one device in place of the requested mesh."""
    from repro.launch import serve

    n = len(jax.devices())
    ap = serve._build_parser()
    args = ap.parse_args(["--arch", "qwen3-0.6b", "--mesh", f"{n + 1},1"])
    with pytest.raises(SystemExit) as e:
        serve._main(ap, args, None)
    assert e.value.code == 2
    assert "--mesh" in capsys.readouterr().err


@pytest.mark.parametrize("mod", ["repro.launch.dryrun",
                                 "repro.launch.hillclimb"])
def test_launcher_import_leaves_xla_flags(monkeypatch, mod):
    monkeypatch.setenv("XLA_FLAGS", "--xla_cpu_use_thunk_runtime=true")
    sys.modules.pop(mod, None)
    importlib.import_module(mod)
    assert os.environ["XLA_FLAGS"] == "--xla_cpu_use_thunk_runtime=true"
