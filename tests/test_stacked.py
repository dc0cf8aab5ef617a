"""Stacked plan execution: padded (L, ...) table stacks served inside
``lax.scan`` — stacked-vs-unrolled token bit-identity across all six
families under per-site calibration, the ragged-padding round-trip
property, scan-compactness (no python-unroll in the lowered HLO), and the
ops-layer padding/blocking fast paths.

Runs under real hypothesis when installed, or the deterministic stub in
conftest.py.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st

from repro.calib import capture_calibration, model_batch, synthetic_batches
from repro.configs import get_config, smoke_config
from repro.nn import init_params
from repro.nn.lut_act import build_lut_activation
from repro.nn.mlp import (
    lut_act_jnp,
    lut_act_jnp_stacked,
    needs_layer_ids,
    tables_stacked,
)
from repro.serve import (
    StackedPlanArrays,
    build_serving_plans,
    decode_step,
    prefill,
    tables_nbytes,
)

RNG = np.random.default_rng(0)

# one arch per family (smoke-scale)
FAMILY_ARCHS = [
    "qwen3-0.6b",          # dense
    "deepseek-moe-16b",    # moe
    "phi-3-vision-4.2b",   # vlm
    "rwkv6-3b",            # ssm
    "recurrentgemma-9b",   # hybrid
    "whisper-small",       # encdec (per-layer via the scanned decoder)
]


def _per_site_plans(arch, n_layers=None):
    # float32: XLA fuses a lax.scan body and straight-line unrolled code
    # differently, which elides bf16 materialization rounding at
    # different points — a pre-existing scan-vs-unroll property of the
    # *surrounding* model math (it shows up with lut_tables=None too).
    # In f32 both lowerings are bit-exact, so any cross-exec divergence
    # here is a real stacked-tables bug, not fusion noise.
    cfg = dataclasses.replace(smoke_config(get_config(arch)),
                              dtype="float32")
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batches = synthetic_batches(cfg, 1, batch_size=2, seq_len=8, seed=1)
    calib = capture_calibration(params, cfg, batches, w_in=8)
    plans = build_serving_plans(cfg, calib, w_out=8)
    return cfg, params, plans


def _decode_tokens(cfg, params, tables, batch, n_new):
    """Greedy prefill + decode; returns the (n_new, B) token grid."""
    t = batch["tokens"].shape[1]
    if cfg.family == "vlm":
        t += cfg.n_patches
    max_seq = t + n_new
    lg, cache = jax.jit(lambda p, x: prefill(
        p, cfg, x, max_seq=max_seq, lut_tables=tables))(params, batch)
    step = jax.jit(lambda p, c, tk, pos: decode_step(
        p, cfg, c, tk, pos, lut_tables=tables))
    tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
    out = []
    for i in range(n_new):
        out.append(np.asarray(tok)[:, 0].tolist())
        lg, cache = step(params, cache, tok, jnp.asarray(t + i))
        tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
    return out


# =========================================================================
# stacked-vs-unrolled token bit-identity, all six families
# =========================================================================
@pytest.mark.parametrize("arch", FAMILY_ARCHS)
def test_stacked_matches_unrolled_all_families(arch):
    """Per-site calibrated serving under lax.scan (stacked) is
    token-for-token bit-identical to the python-unrolled per-layer path
    and to the fused Pallas kernel on the stacked form."""
    cfg, params, plans = _per_site_plans(arch)
    assert plans.per_layer  # every family, encdec included
    cfg_lut = plans.patched_config(cfg)
    rng = np.random.default_rng(3)
    batch = {k: jnp.asarray(v)
             for k, v in model_batch(cfg, rng, 2, 5).items()}

    unrolled = plans.tables_for_model(backend="gather",
                                      plan_exec="unrolled")
    stacked = plans.tables_for_model(backend="gather", plan_exec="stacked")
    assert needs_layer_ids(unrolled) and not needs_layer_ids(stacked)
    assert tables_stacked(stacked) and not tables_stacked(unrolled)

    toks_unrolled = _decode_tokens(cfg_lut, params, unrolled, batch, 3)
    toks_stacked = _decode_tokens(cfg_lut, params, stacked, batch, 3)
    assert toks_stacked == toks_unrolled
    toks_pallas = _decode_tokens(
        cfg_lut, params,
        plans.tables_for_model(backend="pallas", plan_exec="stacked"),
        batch, 3)
    assert toks_pallas == toks_unrolled


def test_encdec_captures_per_layer_masks():
    """The scanned encdec decoder now owns per-layer observed-pattern
    masks (the old ROADMAP fallback case): distinct keys per decoder
    layer, and the serving plans materialize one table per layer."""
    cfg, params, plans = _per_site_plans("whisper-small")
    assert cfg.family == "encdec"
    sp = plans.sites["mlp"]
    assert sp.per_layer and len(sp.luts) == cfg.n_layers
    entry = plans.tables_for_model()["sites"]["mlp"]
    assert entry["stacked"]["meta"]["n_layers"] == cfg.n_layers


def test_stacked_decode_hlo_is_depth_compact():
    """The whole point of stacking: the lowered decode HLO stops growing
    O(L).  At 2x the depth the stacked program grows by only the carried
    (L, ...) shapes, while the unrolled program roughly doubles."""
    sizes = {}
    for n_layers in (2, 4):
        cfg, params, plans = _per_site_plans("qwen3-0.6b",
                                             n_layers=n_layers)
        cfg_lut = plans.patched_config(cfg)
        rng = np.random.default_rng(0)
        batch = {k: jnp.asarray(v)
                 for k, v in model_batch(cfg, rng, 1, 4).items()}
        lg, cache = jax.jit(lambda p, x: prefill(
            p, cfg_lut, x, max_seq=6, lut_tables=None))(params, batch)
        tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
        for exec_ in ("unrolled", "stacked"):
            tables = plans.tables_for_model(backend="gather",
                                            plan_exec=exec_)
            hlo = jax.jit(lambda p, c, tk, pos: decode_step(
                p, cfg_lut, c, tk, pos, lut_tables=tables)).lower(
                params, cache, tok, jnp.asarray(4)).as_text()
            sizes[(exec_, n_layers)] = len(hlo.splitlines())
    assert sizes[("stacked", 4)] < sizes[("unrolled", 4)]
    # doubling depth: unrolled ~2x, stacked stays within a small margin
    assert sizes[("stacked", 4)] < 1.35 * sizes[("stacked", 2)]
    assert sizes[("unrolled", 4)] > 1.6 * sizes[("unrolled", 2)]


# =========================================================================
# ragged-padding round-trip property
# =========================================================================
def _ragged_luts(seed, n_layers=3):
    """Per-layer LUTActivations engineered to land on different plan
    shapes (different care masks -> different m / w_lb splits)."""
    rng = np.random.default_rng(seed)
    luts = []
    for i in range(n_layers):
        lo, hi = sorted(rng.uniform(-6.0, 6.0, size=2))
        calib = rng.uniform(lo, max(hi, lo + 0.5), size=4000)
        luts.append(build_lut_activation(
            "silu", calib, w_in=8, w_out=8,
            m_candidates=(8, 16, 32), lb_candidates=(0, 1, 2)))
    return luts


def _entries(luts):
    from repro.kernels import PlanArrays

    return [{"meta": l.meta(), "arrays": PlanArrays.from_plan(l.plan).arrays}
            for l in luts]


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000))
def test_ragged_padding_roundtrip_lossless(seed):
    """Layers with different m / w_lb plan shapes round-trip through
    StackedPlanArrays losslessly: unstacking returns each layer's exact
    arrays and metas, and the stacked evaluator bit-matches the per-layer
    evaluator on every layer."""
    luts = _ragged_luts(seed)
    entries = _entries(luts)
    st_arr = StackedPlanArrays.from_entries(entries)
    rng = np.random.default_rng(seed + 1)
    x = jnp.asarray(rng.uniform(-9.0, 9.0, size=257), jnp.float32)
    entry = st_arr.entry()
    for i, orig in enumerate(entries):
        back = st_arr.layer_entry(i)
        assert back["meta"] == orig["meta"]
        for name, a in orig["arrays"].items():
            np.testing.assert_array_equal(np.asarray(back["arrays"][name]),
                                          np.asarray(a))
        got = lut_act_jnp_stacked(x, entry, i)
        want = lut_act_jnp(x, orig["arrays"], **orig["meta"])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_stacked_pallas_matches_gather_per_layer():
    """The layer-indexed scalar-prefetch kernel bit-matches the stacked
    gather evaluator layer by layer (ragged shapes included)."""
    from repro.kernels.ops import lut_act_stacked

    entries = _entries(_ragged_luts(7))
    entry = StackedPlanArrays.from_entries(entries).entry()
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.uniform(-9.0, 9.0, size=(3, 130)), jnp.float32)
    for i in range(len(entries)):
        got = lut_act_stacked(x, entry, i, interpret=True)
        # jit the reference too (entry/layer closed over, so the metas
        # stay static): both sides then lower through XLA with the same
        # fusion choices, as they do on the serving path
        want = jax.jit(
            lambda v, _i=i: lut_act_jnp_stacked(v, entry, _i))(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       n_layers=st.integers(min_value=2, max_value=5))
def test_split_concat_layers_roundtrip(seed, n_layers):
    """Re-chunking property (the shape a layer-sharding placement hands
    each device): ``concat_layers(split_layers(sizes))`` is lossless for
    every partition of the layer range — identical metas, true lengths,
    and padded arrays — and each chunk's local padding never exceeds the
    global pad width."""
    st_arr = StackedPlanArrays.from_entries(
        _entries(_ragged_luts(seed, n_layers=n_layers)))
    rng = np.random.default_rng(seed)
    sizes, left = [], n_layers
    while left:
        s = int(rng.integers(1, left + 1))
        sizes.append(s)
        left -= s
    parts = st_arr.split_layers(tuple(sizes))
    assert [p.n_layers for p in parts] == sizes
    from repro.serve.stacked import COMPONENTS

    for p in parts:
        for c in COMPONENTS:
            assert p.arrays[c].shape[1] <= st_arr.arrays[c].shape[1]
    back = StackedPlanArrays.concat_layers(parts)
    assert back.n_layers == st_arr.n_layers
    assert back.metas == st_arr.metas
    assert back.lens == st_arr.lens
    for c in COMPONENTS:
        np.testing.assert_array_equal(np.asarray(back.arrays[c]),
                                      np.asarray(st_arr.arrays[c]))
    np.testing.assert_array_equal(np.asarray(back.meta_i),
                                  np.asarray(st_arr.meta_i))
    np.testing.assert_array_equal(np.asarray(back.meta_f),
                                  np.asarray(st_arr.meta_f))


def test_split_layers_rejects_bad_partition():
    st_arr = StackedPlanArrays.from_entries(_entries(_ragged_luts(2)))
    for sizes in ((st_arr.n_layers + 1,), (st_arr.n_layers, 0), ()):
        with pytest.raises(ValueError, match="sum to"):
            st_arr.split_layers(sizes)


def test_stacked_rejects_mixed_quantizers():
    luts = _ragged_luts(3, n_layers=2)
    entries = _entries(luts)
    entries[1]["meta"]["w_in"] = 9
    with pytest.raises(ValueError, match="disagree"):
        StackedPlanArrays.from_entries(entries)


def test_stacked_accounting():
    st_arr = StackedPlanArrays.from_entries(_entries(_ragged_luts(5)))
    assert st_arr.nbytes > 0
    assert 0.0 <= st_arr.padding_frac < 1.0
    # tables_nbytes prices a full lut_tables dict (used by serve_bench)
    tabs = {"backend": "gather", "sites": {"mlp": {"stacked":
                                                   st_arr.entry()}}}
    assert tables_nbytes(tabs) == st_arr.nbytes


# =========================================================================
# ops-layer fast paths (satellites)
# =========================================================================
def test_lut_act_exact_tiling_and_small_batch_blocking():
    """The ops wrapper skips the zero-fill copy on exact (rows, 128)
    tilings and picks its row block from the shape: the largest multiple
    of 8 rows that divides the rows, at most ``MAX_BLOCK_ROWS``, with one
    exact-fit step for small decode batches — all bit-identical to the
    padded path."""
    lut = build_lut_activation("silu", RNG.normal(size=20000) * 2,
                               w_in=8, w_out=8)
    pa = lut.plan_arrays()
    from repro.kernels.ops import MAX_BLOCK_ROWS, _pick_block_rows, lut_act

    kw = dict(x_lo=lut.x_lo, x_hi=lut.x_hi, y_lo=lut.y_lo, y_hi=lut.y_hi,
              interpret=True)
    # jitted reference: both sides lower through XLA with the same
    # fusion choices (as on the serving path, where decode is jitted)
    ref_fn = jax.jit(lambda x: lut_act_jnp(
        jnp.asarray(x), pa.arrays, l=pa.l, w_lb=pa.w_lb, w_hb=pa.w_hb,
        w_in=pa.w_in, w_out=pa.w_out, x_lo=lut.x_lo, x_hi=lut.x_hi,
        y_lo=lut.y_lo, y_hi=lut.y_hi))
    # exact tiling (2*8*128), small decode batch (2*128), ragged tail
    for n in (2048, 256, 130, 1300):
        x = RNG.uniform(-9, 9, size=n).astype(np.float32)
        got = np.asarray(lut_act(jnp.asarray(x), pa, **kw))
        np.testing.assert_array_equal(got, np.asarray(ref_fn(x)))
    rows = lambda r: r * 128
    assert MAX_BLOCK_ROWS % 8 == 0
    assert _pick_block_rows(rows(393_216)) == MAX_BLOCK_ROWS  # qwen3 prefill
    assert _pick_block_rows(rows(1024)) == 1024    # phi4 decode, 16 x 8192
    assert _pick_block_rows(rows(5632)) == 704     # deepseek expert tile
    assert _pick_block_rows(rows(2736)) == 912     # deepseek layer-0 decode
    assert _pick_block_rows(2048) == 16            # one step, two tiles
    assert _pick_block_rows(1300) == 16            # 11 rows padded to 16
    assert _pick_block_rows(256) == 2   # one exact-fit grid step
    assert _pick_block_rows(1) == 1
    assert _pick_block_rows(rows(8 * 1031)) == 8   # 8 x a prime: 8 rows


def test_lut_grid_steps_gauge_records_each_launch_shape():
    """Tracing a stacked launch under an entered Telemetry sets the
    ``lut_grid_steps`` gauge for its kernel, rows and block; with no
    Telemetry nothing is recorded and nothing is added to the program."""
    from repro import obs
    from repro.kernels.ops import lut_act_stacked

    entry = StackedPlanArrays.from_entries(
        _entries(_ragged_luts(7))).entry(packed=True)
    fn = lambda v: lut_act_stacked(v, entry, 1, interpret=True)
    x = jax.ShapeDtypeStruct((4, 3072), jnp.bfloat16)   # 96 rows
    with obs.Telemetry() as tel:
        jax.eval_shape(fn, x)
        jax.eval_shape(fn, jax.ShapeDtypeStruct((2, 128), jnp.bfloat16))
    gauge = tel.registry.gauge("lut_grid_steps")
    assert len(gauge.data) == 2, gauge.data
    for rows in (96, 2):
        assert gauge.value(kernel="lut_act_stacked", rows=rows,
                           block_rows=rows) == 1.0, gauge.data
    with obs.Telemetry() as tel:
        pass
    jax.eval_shape(fn, x)
    assert "lut_grid_steps" not in tel.registry.snapshot()


def _lb_luts(any_lb: bool, n_layers: int = 2):
    """Per-layer tables whose plans all split off low bits (``w_lb`` > 0)
    or none do."""
    rng = np.random.default_rng(11)
    return [build_lut_activation(
        "silu", rng.normal(size=4000) * (2.0 + i), w_in=8, w_out=8,
        m_candidates=(8, 16), lb_candidates=(3,) if any_lb else (0,))
        for i in range(n_layers)]


def _large_block_x(dtype):
    # 96 rows: the picked block (96, below the cap) is one grid step of
    # several tiles; 48 and 24 split it into several multi-tile steps
    # (24 rows of a 16-bit input walk 8-row tiles)
    x = np.random.default_rng(12).uniform(-9.0, 9.0, size=(96, 128))
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("any_lb", [False, True], ids=["no_lb", "lb"])
@pytest.mark.parametrize("packed", [False, True], ids=["raw", "packed"])
def test_stacked_kernel_large_blocks_equal_the_8_row_grid(packed, any_lb,
                                                          dtype):
    """A grid step over a multi-tile row block (the in-kernel tile loop)
    gives the 8-row grid's output bit for bit, and the stacked gather
    evaluator's, on every layer."""
    from repro.kernels.lut_act import lut_act_stacked_pallas
    from repro.kernels.ops import _pick_block_rows

    st_arr = StackedPlanArrays.from_entries(_entries(_lb_luts(any_lb)))
    entry = st_arr.entry(packed=packed)
    assert entry["meta"]["any_lb"] is any_lb
    a = entry["arrays"]
    x = _large_block_x(dtype)
    block = _pick_block_rows(x.size)
    assert block == 96

    def kernel(block_rows):
        return jax.jit(lambda v, lid: lut_act_stacked_pallas(
            v, lid.reshape(1), a["t_ust"], a["t_idx"], a["t_rsh"],
            a["t_bias"], a["t_lb"], entry["meta_i"], entry["meta_f"],
            any_lb=entry["meta"]["any_lb"], w_in=entry["meta"]["w_in"],
            w_out=entry["meta"]["w_out"], x_lo=entry["meta"]["x_lo"],
            x_hi=entry["meta"]["x_hi"], pack=entry["meta"].get("pack"),
            block_rows=block_rows, interpret=True))

    raw = st_arr.entry()
    for layer in range(2):
        lid = jnp.asarray(layer, jnp.int32)
        grid8 = np.asarray(kernel(8)(x, lid))
        want = np.asarray(jax.jit(
            lambda v, _i=layer: lut_act_jnp_stacked(v, raw, _i))(x))
        np.testing.assert_array_equal(grid8, want)
        for block_rows in (block, 48, 24):
            np.testing.assert_array_equal(
                np.asarray(kernel(block_rows)(x, lid)), grid8)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("any_lb", [False, True], ids=["no_lb", "lb"])
@pytest.mark.parametrize("packed", [False, True], ids=["raw", "packed"])
def test_isolated_kernel_large_blocks_equal_the_8_row_grid(packed, any_lb,
                                                           dtype):
    """The per-plan kernel's tile loop: multi-tile row blocks give the
    8-row grid's output bit for bit, and the gather evaluator's."""
    from repro.kernels import PlanArrays
    from repro.kernels.lut_act import lut_act_pallas

    lut = _lb_luts(any_lb, n_layers=1)[0]
    assert (lut.plan.w_lb > 0) is any_lb
    pa = PlanArrays.from_plan(lut.plan, packed=packed)
    m = lut.meta()
    x = _large_block_x(dtype)

    def kernel(block_rows):
        return jax.jit(lambda v: lut_act_pallas(
            v, *(pa.arrays[c] for c in ("t_ust", "t_idx", "t_rsh",
                                        "t_bias", "t_lb")),
            **m, pack=pa.pack, block_rows=block_rows, interpret=True))

    grid8 = np.asarray(kernel(8)(x))
    raw = PlanArrays.from_plan(lut.plan).arrays
    want = np.asarray(jax.jit(lambda v: lut_act_jnp(v, raw, **m))(x))
    np.testing.assert_array_equal(grid8, want)
    for block_rows in (96, 48, 24):
        np.testing.assert_array_equal(np.asarray(kernel(block_rows)(x)),
                                      grid8)
