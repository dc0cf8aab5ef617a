"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

All kernels run in interpret mode (CPU container; TPU is the lowering
target — see kernels/*.py docstrings for the VMEM tiling contracts).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import CompressConfig, TableSpec, compress_table
from repro.core.plan import PlainPlan
from repro.kernels import PlanArrays, lut_act, lut_reconstruct, lutnn_layer
from repro.kernels.ref import lut_act_ref, lutnn_layer_ref


def _plan(w_in=10, w_out=6, frac=0.4, seed=0, exiguity=100, smooth=True):
    spec = TableSpec.random(w_in, w_out, frac, seed, smooth)
    return spec, compress_table(spec, CompressConfig(exiguity=exiguity))


# --------------------------------------------------------------------------
# lut_reconstruct
# --------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(5,), (8, 128), (3, 7, 11), (1000,), (1,)])
def test_lut_reconstruct_shapes(shape):
    spec, plan = _plan()
    pa = PlanArrays.from_plan(plan)
    x = np.random.default_rng(0).integers(0, spec.size, size=shape)
    out = lut_reconstruct(jnp.asarray(x), pa)
    assert out.shape == shape
    np.testing.assert_array_equal(np.asarray(out), plan.reconstruct()[x])


@pytest.mark.parametrize("w_in,w_out", [(6, 2), (8, 8), (12, 4), (9, 1)])
def test_lut_reconstruct_table_geometries(w_in, w_out):
    spec, plan = _plan(w_in=w_in, w_out=w_out, seed=w_in * 10 + w_out)
    pa = PlanArrays.from_plan(plan)
    x = np.arange(spec.size)  # exhaustive
    out = lut_reconstruct(jnp.asarray(x), pa)
    np.testing.assert_array_equal(np.asarray(out), plan.reconstruct())


def test_lut_reconstruct_plain_plan():
    spec = TableSpec.random(8, 5, 0.0, 3, smooth=False)
    plan = PlainPlan(spec.values, 8, 5)
    pa = PlanArrays.from_plan(plan)
    x = np.arange(256)
    out = lut_reconstruct(jnp.asarray(x), pa)
    np.testing.assert_array_equal(np.asarray(out), spec.values)


@given(
    w_in=st.integers(min_value=5, max_value=11),
    seed=st.integers(min_value=0, max_value=30),
    frac=st.floats(min_value=0.0, max_value=0.8),
)
@settings(max_examples=10, deadline=None)
def test_lut_reconstruct_property(w_in, seed, frac):
    """Kernel output == plan.reconstruct() for arbitrary plans/addresses."""
    spec, plan = _plan(w_in=w_in, w_out=6, frac=frac, seed=seed)
    pa = PlanArrays.from_plan(plan)
    x = np.random.default_rng(seed).integers(0, spec.size, size=257)
    out = lut_reconstruct(jnp.asarray(x), pa)
    np.testing.assert_array_equal(np.asarray(out), plan.reconstruct()[x])


# --------------------------------------------------------------------------
# lutnn_layer
# --------------------------------------------------------------------------
@pytest.mark.parametrize("b,p,n,f,bits", [
    (128, 32, 8, 3, 4),    # aligned blocks
    (100, 20, 13, 3, 3),   # ragged everything
    (1, 16, 5, 6, 2),      # single sample, MNIST-like geometry
    (257, 784, 16, 6, 2),  # wide parent layer
])
def test_lutnn_layer_sweep(b, p, n, f, bits):
    rng = np.random.default_rng(b + n)
    codes = rng.integers(0, 1 << bits, size=(b, p)).astype(np.int32)
    conn = rng.integers(0, p, size=(n, f)).astype(np.int32)
    tables = rng.integers(0, 1 << bits, size=(n, 1 << (bits * f))).astype(np.int32)
    out = lutnn_layer(jnp.asarray(codes), jnp.asarray(conn),
                      jnp.asarray(tables), bits=bits)
    want = lutnn_layer_ref(jnp.asarray(codes), jnp.asarray(conn),
                           jnp.asarray(tables), bits=bits)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_lutnn_layer_matches_network_inference():
    """Kernel agrees with the numpy table_forward used for accuracy evals."""
    from repro.lutnn.inference import pack_codes

    rng = np.random.default_rng(7)
    bits, f, p, n, b = 2, 6, 50, 10, 64
    codes = rng.integers(0, 1 << bits, size=(b, p)).astype(np.int32)
    conn = rng.integers(0, p, size=(n, f)).astype(np.int32)
    tables = rng.integers(0, 1 << bits, size=(n, 1 << (bits * f))).astype(np.int32)
    addr = pack_codes(codes[:, conn], bits)
    want = np.take_along_axis(tables, addr.T, axis=1).T
    out = lutnn_layer(jnp.asarray(codes), jnp.asarray(conn),
                      jnp.asarray(tables), bits=bits)
    np.testing.assert_array_equal(np.asarray(out), want)


# --------------------------------------------------------------------------
# lut_act
# --------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(64, 64), (7, 33), (2, 3, 5)])
def test_lut_act_dtypes_shapes(dtype, shape):
    spec, plan = _plan(w_in=8, w_out=8, frac=0.3, seed=5)
    if plan.kind != "decomposed":
        pytest.skip("search picked plain for this table")
    pa = PlanArrays.from_plan(plan)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=shape) * 2, dtype=dtype
    )
    kw = dict(x_lo=-4.0, x_hi=4.0, y_lo=-1.0, y_hi=1.0)
    out = lut_act(x, pa, **kw)
    want = lut_act_ref(
        x, pa.arrays["t_ust"], pa.arrays["t_idx"], pa.arrays["t_rsh"],
        pa.arrays["t_bias"], pa.arrays["t_lb"],
        l=pa.l, w_lb=pa.w_lb, w_hb=pa.w_hb, w_in=pa.w_in, w_out=pa.w_out,
        **kw,
    )
    assert out.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=1e-6, atol=1e-6,
    )


def test_lut_act_approximates_function():
    """A LUT-compressed GELU stays within quantization error of the real one."""
    w_in, w_out = 10, 10
    xs = np.linspace(-6, 6, 1 << w_in)
    ys = xs * 0.5 * (1 + np.tanh(np.sqrt(2 / np.pi) * (xs + 0.044715 * xs**3)))
    y_lo, y_hi = float(ys.min()), float(ys.max())
    codes = np.round((ys - y_lo) / (y_hi - y_lo) * ((1 << w_out) - 1))
    spec = TableSpec(codes.astype(np.int64), w_in, w_out)
    plan = compress_table(spec, CompressConfig(exiguity=None,
                                               m_candidates=(16, 64)))
    pa = PlanArrays.from_plan(plan)
    x = jnp.asarray(
        np.clip(np.random.default_rng(0).normal(size=(512,)) * 2, -5.9, 5.9),
        jnp.float32,
    )  # inputs outside the tabulated range are clipped by design
    out = lut_act(x, pa, x_lo=-6.0, x_hi=6.0, y_lo=y_lo, y_hi=y_hi)
    gelu = jax.nn.gelu(x, approximate=True)
    # quantization grid: |err| <~ table step + input step * max|gelu'|
    step_y = (y_hi - y_lo) / ((1 << w_out) - 1)
    step_x = 12.0 / ((1 << w_in) - 1)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(gelu),
        atol=step_y + 1.2 * step_x + 1e-3,
    )


# --------------------------------------------------------------------------
# wkv (chunked GLA) kernel
# --------------------------------------------------------------------------
@pytest.mark.parametrize("t,chunk,strong", [
    (64, 16, False), (64, 16, True), (48, 16, False),  # ragged pad path
    (32, 8, True), (16, 16, False),
])
def test_wkv_kernel_matches_scan_oracle(t, chunk, strong):
    from repro.kernels.ops import wkv
    from repro.nn.ssm import wkv_scan_ref

    rng = np.random.default_rng(t + chunk)
    b, h, n = 2, 3, 16
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, n)), jnp.float32)
               for _ in range(3))
    hi = 0.7 if strong else -1.0
    log_w = jnp.asarray(-np.exp(rng.uniform(-3, hi, size=(b, t, h, n))),
                        jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, n)), jnp.float32)
    y_ref, s_ref = wkv_scan_ref(q, k, v, log_w, u)
    y, s = wkv(q, k, v, log_w, u, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=3e-4, atol=3e-4)


def test_wkv_kernel_matches_jnp_chunked():
    """Kernel == the pure-JAX chunked implementation bit-for-bit-ish."""
    from repro.kernels.ops import wkv
    from repro.nn.ssm import wkv_chunked

    rng = np.random.default_rng(5)
    b, t, h, n = 1, 32, 2, 16
    q, k, v = (jnp.asarray(rng.normal(size=(b, t, h, n)), jnp.float32)
               for _ in range(3))
    log_w = jnp.asarray(-np.exp(rng.uniform(-3, 0, size=(b, t, h, n))),
                        jnp.float32)
    u = jnp.asarray(rng.normal(size=(h, n)), jnp.float32)
    y1, s1 = wkv_chunked(q, k, v, log_w, u, chunk=16)
    y2, s2 = wkv(q, k, v, log_w, u, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# lane_take: the one in-kernel table lookup (lane-row gather)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n,rows,cols", [
    (1024, 8, 128),     # 10-bit table, one lane chunk
    (300, 8, 384),      # ragged table, three lane chunks
    (128, 1, 128),      # one row of indices
    (77, 3, 40),        # narrow chunk (interpreter only on the TPU side)
])
def test_lane_take_equals_flat_take(n, rows, cols):
    from repro.kernels.packing import lane_rows, lane_take

    rng = np.random.default_rng(n + rows)
    table = jnp.asarray(rng.integers(-2**31, 2**31 - 1, n), jnp.int32)
    idx = jnp.asarray(rng.integers(0, n, (rows, cols)), jnp.int32)
    got = lane_take(lane_rows(table), idx)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.take(table, idx)))


def test_lane_rows_pads_to_whole_rows():
    from repro.kernels.packing import LANES, lane_rows

    a = jnp.arange(2 * 300, dtype=jnp.int32).reshape(2, 300)
    r = lane_rows(a)
    assert r.shape == (2, 3, LANES)
    np.testing.assert_array_equal(np.asarray(r).reshape(2, -1)[:, :300], a)
    assert not np.asarray(r).reshape(2, -1)[:, 300:].any()
