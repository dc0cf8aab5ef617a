"""Compile the Pallas LUT kernels and the LUT decode step for a TPU v5e.

Nothing here runs on a chip: the TPU compiler compiles for a described
``v5e:2x2`` topology whose devices are not attached, which is what refuses
a kernel Mosaic cannot lower (an unsupported gather, an untileable block,
more VMEM than a kernel may use) before any chip time is spent.  Shapes
are qwen3-0.6b's: 28 layers, d_model 1024, d_ff 3072, 10-bit tables.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, so every pytest worker must collect
the same tests and only the worker that runs this file loads it.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import PlanArrays, runtime
from repro.kernels.fused_matmul_lut import fused_matmul_lut_pallas
from repro.kernels.lut_act import (
    lut_act_multisite_pallas,
    lut_act_pallas,
    lut_act_stacked_pallas,
)
from repro.nn import init_params
from repro.nn.lut_act import build_lut_activation
from repro.serve import decode_step, init_cache
from repro.serve.stacked import MultiSiteSlabs, StackedPlanArrays

N_LAYERS = 28          # qwen3-0.6b depth
D_MODEL, D_FF = 1024, 3072
ROWS = 256
W_IN = 10


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernel wrappers choose interpret mode from the CPU backend this
    process runs on; the compile targets the chip, so force Mosaic."""
    monkeypatch.setattr(runtime, "default_interpret", lambda: False)


@pytest.fixture(scope="module")
def stack():
    """28 per-layer silu tables, each compressed from its own calibration
    sample (so the layers' plans and pack widths differ as a captured
    model's do)."""
    rng = np.random.default_rng(0)
    entries = []
    for layer in range(N_LAYERS):
        lut = build_lut_activation(
            "silu", rng.normal(size=20000) * (0.5 + layer / N_LAYERS),
            w_in=W_IN, w_out=10)
        entries.append({"meta": lut.meta(),
                        "arrays": PlanArrays.from_plan(lut.plan).arrays})
    return StackedPlanArrays.from_entries(entries)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _tpu_kernel(compiled, name: str) -> bool:
    """A Mosaic kernel is in the program, and its HLO instruction carries
    the ``pallas_call``'s ``name``: the device trace's ``XLA Ops`` event
    for the kernel is that instruction, so the trace names it."""
    return any(line.strip().removeprefix("ROOT ").startswith(f"%{name}.")
               and 'custom_call_target="tpu_custom_call"' in line
               for line in compiled.as_text().splitlines())


def test_lut_act_pallas_compiles(one_chip, compiled_kernels):
    lut = build_lut_activation("silu", np.random.default_rng(1).normal(
        size=20000), w_in=W_IN, w_out=10)
    pa = PlanArrays.from_plan(lut.plan, packed=True)
    m = lut.meta()
    tabs = [pa.arrays[c] for c in ("t_ust", "t_idx", "t_rsh", "t_bias",
                                   "t_lb")]
    fn = lambda x, *t: lut_act_pallas(
        x, *t, l=m["l"], w_lb=m["w_lb"], w_hb=m["w_hb"], w_in=m["w_in"],
        w_out=m["w_out"], x_lo=m["x_lo"], x_hi=m["x_hi"], y_lo=m["y_lo"],
        y_hi=m["y_hi"], pack=pa.pack)
    args = [_spec((ROWS, D_FF), jnp.bfloat16, one_chip)] + [
        _spec(t.shape, t.dtype, one_chip) for t in tabs]
    assert _tpu_kernel(_compile(fn, *args), "lut_act")


@pytest.mark.parametrize("packed", [False, True], ids=["raw", "packed"])
def test_lut_act_stacked_pallas_compiles(one_chip, compiled_kernels, stack,
                                         packed):
    e = stack.entry(packed=packed)
    meta = e["meta"]
    tabs = [e["arrays"][c] for c in ("t_ust", "t_idx", "t_rsh", "t_bias",
                                     "t_lb")]
    fn = lambda x, lid, *t: lut_act_stacked_pallas(
        x, lid, *t, any_lb=meta["any_lb"], w_in=meta["w_in"],
        w_out=meta["w_out"], x_lo=meta["x_lo"], x_hi=meta["x_hi"],
        pack=meta.get("pack"))
    args = ([_spec((ROWS, D_FF), jnp.bfloat16, one_chip),
             _spec((1,), jnp.int32, one_chip)]
            + [_spec(t.shape, t.dtype, one_chip)
               for t in tabs + [e["meta_i"], e["meta_f"]]])
    assert _tpu_kernel(_compile(fn, *args), "lut_act_stacked")


@pytest.mark.parametrize("shape", [(8, 2048, 3072), (512, 1408),
                                   (32, 10944)],
                         ids=["qwen3_prefill", "expert_tile", "dense_decode"])
def test_lut_act_stacked_compiles_over_served_blocks(one_chip,
                                                     compiled_kernels,
                                                     stack, shape):
    """The wrapper's row blocks at served shapes (qwen3-0.6b's prefill
    layer, DeepSeek-V2-Lite's expert tile and layer-0 decode): one grid
    step walks a block of up to ``MAX_BLOCK_ROWS`` rows in bf16 tiles,
    within the kernel's VMEM."""
    from repro.kernels.ops import lut_act_stacked

    e = stack.entry(packed=True)
    fn = lambda x, lid: lut_act_stacked(x, e, lid)
    args = [_spec(shape, jnp.bfloat16, one_chip),
            _spec((), jnp.int32, one_chip)]
    assert _tpu_kernel(_compile(fn, *args), "lut_act_stacked")


def test_lut_act_multisite_pallas_compiles(one_chip, compiled_kernels, stack):
    e = MultiSiteSlabs.from_stacks({"mlp": stack, "expert": stack}).entry()
    a = e["arrays"]
    tabs = [a[c] for c in ("t_ust", "t_idx", "t_rsh", "t_bias", "t_lb")]
    metas = [e["meta_i"], e["meta_f"], e["meta_q"], e["meta_p"]]
    x = _spec((2 * ROWS, 128), jnp.bfloat16, one_chip)
    n_blocks = 2 * ROWS // 8
    fn = lambda x, bs, lid, *t: lut_act_multisite_pallas(
        x, bs, lid, *t, any_lb=e["meta"]["any_lb"])
    args = ([x, _spec((n_blocks,), jnp.int32, one_chip),
             _spec((1,), jnp.int32, one_chip)]
            + [_spec(t.shape, t.dtype, one_chip) for t in tabs + metas])
    assert _tpu_kernel(_compile(fn, *args), "lut_act_multisite")


def test_fused_matmul_lut_pallas_compiles(one_chip, compiled_kernels, stack):
    """Gated MLP epilogue at full width: the (1024, 2*3072) bf16 weights
    do not fit VMEM whole, so the kernel tiles the output columns."""
    e = stack.entry(packed=True)
    meta = e["meta"]
    tabs = [e["arrays"][c] for c in ("t_ust", "t_idx", "t_rsh", "t_bias",
                                     "t_lb")]
    fn = lambda x, w, lid, *t: fused_matmul_lut_pallas(
        x, w, lid, *t, gated=True, any_lb=meta["any_lb"],
        w_in=meta["w_in"], w_out=meta["w_out"], x_lo=meta["x_lo"],
        x_hi=meta["x_hi"], pack=meta["pack"])
    args = ([_spec((8, D_MODEL), jnp.bfloat16, one_chip),
             _spec((D_MODEL, 2 * D_FF), jnp.bfloat16, one_chip),
             _spec((1,), jnp.int32, one_chip)]
            + [_spec(t.shape, t.dtype, one_chip)
               for t in tabs + [e["meta_i"], e["meta_f"]]])
    assert _tpu_kernel(_compile(fn, *args), "lut_fused_matmul")


def _full_width_decode_step(one_chip, stack, donate: bool):
    """qwen3-0.6b's whole decode step, batch 8 against a 1024-deep cache,
    with the stacked Pallas MLP tables, compiled for one v5e chip (the
    cache donated, as ``generate()`` runs it, or not).  Returns the
    compiled step and the cache's spec."""
    import dataclasses

    cfg = dataclasses.replace(get_config("qwen3-0.6b"), lut_activation=True)
    tables = {"backend": "pallas", "kernel": "isolated",
              "sites": {"mlp": {"stacked": stack.entry(packed=True)}}}
    batch, max_seq = 8, 1024
    on_chip = lambda tree: jax.tree.map(
        lambda s: _spec(s.shape, s.dtype, one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, batch, max_seq)))
    tok = _spec((batch, 1), jnp.int32, one_chip)
    pos = _spec((), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda p, c, t, i: decode_step(p, cfg, c, t, i, lut_tables=tables),
        donate_argnums=1 if donate else ()).lower(
        params, cache, tok, pos).compile()
    return compiled, cache


def test_full_width_decode_step_with_pallas_tables_compiles(
        one_chip, compiled_kernels, stack):
    """The kernel is in the served decode step, and keeps its name once
    inlined into it."""
    compiled, _ = _full_width_decode_step(one_chip, stack, donate=False)
    assert _tpu_kernel(compiled, "lut_act_stacked")
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 16e9   # fits one v5e chip's HBM


def test_full_width_decode_step_writes_the_donated_cache_in_place(
        one_chip, compiled_kernels, stack):
    """With the cache donated, the step aliases the whole cache to its
    output and no op copies a whole-cache-shaped ``bf16[28,8,1024,8,128]``
    buffer: the token's entries are written in place, after the layers."""
    import re

    compiled, cache = _full_width_decode_step(one_chip, stack, donate=True)
    shape = "bf16[28,8,1024,8,128]"
    op = re.compile(r"^\s*(?:ROOT )?%(\S+) = (\S+?)\{[^}]*\} ([\w-]+)\(")
    copies = [m.group(1) for m in map(op.match,
                                      compiled.as_text().splitlines())
              if m and m.group(2) == shape
              and (m.group(3) == "copy" or m.group(1).startswith("copy"))]
    assert copies == []
    cache_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes
