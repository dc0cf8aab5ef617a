"""Tests for the loop-aware HLO cost extraction (roofline engine)."""
import os
import subprocess
import sys
import textwrap

import numpy as np

from repro.roofline.hlo_costs import analyze_hlo, parse_hlo
from repro.roofline.analysis import RooflineTerms, model_flops_per_step


SYNTH_HLO = """
HloModule jit_f, is_scheduled=true

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}

%body (p: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {
  %p = (s32[], f32[8,128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,128]{1,0} get-tuple-element(%p), index=1
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  %w = f32[128,128]{1,0} constant({...})
  %y = f32[8,128]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,128]{1,0} all-reduce(%y), channel_id=1, replica_groups=[2,4]<=[8], to_apply=%add
  ROOT %t = (s32[], f32[8,128]) tuple(%i2, %ar)
}

%cond (p: (s32[], f32[8,128])) -> pred[] {
  %p = (s32[], f32[8,128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(12)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (x: f32[8,128]) -> f32[8,128] {
  %x = f32[8,128]{1,0} parameter(0)
  %c0 = s32[] constant(0)
  %init = (s32[], f32[8,128]) tuple(%c0, %x)
  %w = (s32[], f32[8,128]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8,128]{1,0} get-tuple-element(%w), index=1
}
"""


def test_parse_hlo_structure():
    comps, entry = parse_hlo(SYNTH_HLO)
    assert entry == "main"
    assert set(comps) == {"add", "body", "cond", "main"}
    body = comps["body"]
    opcodes = [op.opcode for op in body.ops]
    assert "dot" in opcodes and "all-reduce" in opcodes


def test_loop_multipliers_and_costs():
    c = analyze_hlo(SYNTH_HLO)
    # dot: 2 * (8*128 out) * 128 contract * 12 trips
    assert c.flops == 2 * 8 * 128 * 128 * 12
    # all-reduce: 8*128*4B * 12 trips * ring factor 2
    assert c.per_op_coll["all-reduce"] == 8 * 128 * 4 * 12 * 2
    assert c.trip_counts.get("body") == 12
    assert c.hbm_bytes > 0


def test_comment_in_tuple_types_is_stripped():
    hlo = SYNTH_HLO.replace("(s32[], f32[8,128])",
                            "(s32[], /*index=1*/f32[8,128])")
    c = analyze_hlo(hlo)
    assert c.flops == 2 * 8 * 128 * 128 * 12


def test_roofline_terms_dominance():
    t = RooflineTerms(flops=197e12, hbm_bytes=1e9, coll_bytes=0,
                      per_op_coll={})
    assert t.compute_s == 1.0
    assert t.dominant == "compute"
    t2 = RooflineTerms(flops=1e9, hbm_bytes=819e9 * 2, coll_bytes=0,
                       per_op_coll={})
    assert t2.dominant == "memory"


def test_model_flops_dense_vs_moe():
    from repro.configs import get_config
    dense = get_config("qwen3-0.6b")
    moe = get_config("qwen3-moe-30b-a3b")
    f_d = model_flops_per_step(dense, 256, 4096, "train")
    assert f_d == 6.0 * dense.n_params() * 256 * 4096
    # MoE uses active params only
    f_m = model_flops_per_step(moe, 256, 4096, "train")
    assert f_m < 6.0 * moe.n_params() * 256 * 4096


def test_real_compile_roundtrip():
    """End-to-end on a real (8 fake devices) compiled module."""
    script = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, numpy as np, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_host_mesh
    from repro.roofline.hlo_costs import analyze_hlo
    mesh = make_host_mesh(2, 4)

    def f(x, w):
        def body(h, wl):
            h = jax.lax.with_sharding_constraint(
                jnp.tanh(h @ wl), NamedSharding(mesh, P("data", "model")))
            h = jax.lax.with_sharding_constraint(
                h @ wl.T, NamedSharding(mesh, P("data", None)))
            return h, ()
        h, _ = jax.lax.scan(body, x, w)
        return jnp.sum(h)

    xs = jax.ShapeDtypeStruct((64, 256), np.float32)
    ws = jax.ShapeDtypeStruct((7, 256, 256), np.float32)
    comp = jax.jit(f, in_shardings=(
        NamedSharding(mesh, P("data", None)),
        NamedSharding(mesh, P(None, None, "model")))).lower(xs, ws).compile()
    c = analyze_hlo(comp.as_text())
    expect = 2 * 2 * 32 * 64 * 256 * 7  # 2 dots, local shapes, 7 trips
    assert abs(c.flops - expect) / expect < 0.01, (c.flops, expect)
    assert c.per_op_coll.get("all-reduce", 0) > 0
    print("ROOFLINE_OK")
    """)
    proc = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=520,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "ROOFLINE_OK" in proc.stdout
