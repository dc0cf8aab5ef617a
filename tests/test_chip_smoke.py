"""``chip_smoke.py``'s phases at smoke size on the CPU (Pallas interpreted).

The script itself refuses to run without a TPU; these tests drive its
phase functions directly, so a broken path shows up here before it costs
chip time.  The four-chip phase runs in a child process that gives the CPU
backend four virtual devices.
"""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.configs import get_config, smoke_config

ROOT = Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cs():
    return _load_chip_smoke()


@pytest.fixture(scope="module")
def served(cs):
    cfg = smoke_config(get_config(cs.ARCH))
    params, batch = cs.setup(cfg, seed=0, batch_size=2, prompt_len=8)
    plans, sample, layer, _ = cs.calibrated_plans(
        cfg, params, seed=0, batch_size=2, seq_len=8)
    return cfg, params, batch, plans, sample, layer


def test_main_refuses_without_tpu(cs, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert cs.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err


def test_plain_gather_pallas_phases(cs, served):
    cfg, params, batch, plans, sample, layer = served
    plain = cs.phase_generate("plain", cfg, params, batch, 4)
    assert plain.tokens.shape == (2, 4)
    assert plain.prefill_compile_s > 0 and plain.decode_s > 0
    lut_cfg = plans.patched_config(cfg)
    tables_g = plans.tables_for_model(backend="gather")
    tables_p = plans.tables_for_model(backend="pallas")
    gather = cs.phase_generate("gather", lut_cfg, params, batch, 4, tables_g)
    pallas = cs.phase_generate("pallas", lut_cfg, params, batch, 4, tables_p)
    np.testing.assert_array_equal(pallas.tokens, gather.tokens)
    assert sample.shape[-1] == cfg.d_ff
    assert cs.site_outputs_identical(tables_g, tables_p, sample,
                                     layer) == sample.size
    # interpreted here, so the compiled program holds no Mosaic kernel
    with pytest.raises(cs.Failure, match="tpu_custom_call"):
        cs.require_kernel(pallas)


def test_site_comparison_catches_a_wrong_table(cs, served):
    """The bit-identity check fails when the Pallas table differs."""
    cfg, params, batch, plans, sample, layer = served
    tables_g = plans.tables_for_model(backend="gather")
    tables_p = plans.tables_for_model(backend="pallas")
    st = tables_p["sites"]["mlp"]["stacked"]
    bad = dict(st, meta_f=st["meta_f"] * 1.5)
    tables_bad = dict(tables_p, sites={"mlp": {"stacked": bad}})
    with pytest.raises(cs.Failure, match="differ from gather"):
        cs.site_outputs_identical(tables_g, tables_bad, sample, layer)


def test_batcher_phase(cs, served):
    cfg, params, batch, plans, _, _ = served
    lut_cfg = plans.patched_config(cfg)
    reqs = cs.request_mix(lut_cfg, seed=0, n=3, base_len=4, new_tokens=3)
    assert len({len(r.prompt) for r in reqs}) == 3
    outs, secs, m = cs.phase_batcher(
        lut_cfg, params, plans.tables_for_model(backend="pallas"),
        batch_size=2, requests=reqs)
    assert m["finished"] == 3 and secs > 0
    assert all(len(v) == 3 for v in outs.values())


def test_four_chip_phase_on_virtual_devices():
    """The sharded phase against device 0, on four virtual CPU devices."""
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {str(ROOT)!r})\n"
        "import chip_smoke as cs\n"
        "from repro.configs import get_config, smoke_config\n"
        "cfg = smoke_config(get_config(cs.ARCH))\n"
        "params, batch = cs.setup(cfg, seed=0, batch_size=4, prompt_len=8)\n"
        "plans, sample, layer, _ = cs.calibrated_plans(cfg, params, seed=0,"
        " batch_size=4, seq_len=8)\n"
        "gen, ref, gaps = cs.phase_four_chips(cfg, params, batch, 3, plans,"
        " sample, layer)\n"
        "print(json.dumps({'gaps': gaps, 'same': bool("
        "(gen.tokens == ref.tokens).all())}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert any(l.startswith("placement params: bytes per device")
               for l in lines)
    assert any(l.startswith("mesh site: ") for l in lines)
    res = json.loads(lines[-1])
    assert res["same"]
    rtol = _load_chip_smoke().LOGIT_RTOL
    assert max(res["gaps"]["sharded"].values()) <= rtol
    assert max(res["gaps"]["flat"].values()) > rtol
