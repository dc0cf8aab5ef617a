"""Pallas TPU kernels: fused LUT-approximated activation.

The transformer-integration hot path (DESIGN.md SS2): quantize a float
tensor onto the table's input grid, reconstruct the (ReducedLUT-compressed)
table output via Eq. (1), dequantize — one VMEM round-trip instead of
quantize/gather/dequant as three HBM-bound ops.  The compressed component
tables stay resident in VMEM across the whole grid.

Three variants:

* :func:`lut_act_pallas` — one plan's tables closed over as whole-array
  inputs (the shared-table / unrolled-per-layer form; ``l``/``w_lb``/
  ``w_hb`` are Python statics baked into the kernel).
* :func:`lut_act_stacked_pallas` — the layer-indexed form for per-layer
  tables served inside ``lax.scan``: every component table comes in as a
  padded ``(L, n)`` stack and the in-scan layer id arrives as a
  scalar-prefetch operand, so the BlockSpec index maps pull **only layer
  i's slab** into VMEM per grid step (instead of re-staging L layers'
  tables every block), and the per-layer scalar metas (``l``, ``w_lb``,
  ``w_hb``, output dequant range) are read from ``(L, k)`` side tables.
  Bit-identical to running :func:`lut_act_pallas` with layer i's arrays.
* :func:`lut_act_multisite_pallas` — the single-grid **multi-site** form:
  all of a model's per-layer site families ride in one ``(S, L, n)``
  super-slab, the grid iterates row-blocks whose site id is a second
  scalar-prefetch side table, and *every* per-site scalar (quantizer
  levels, domain, pack widths) is traced from ``(S, …)`` meta tables —
  one compiled kernel serves every site instead of S isolated launches
  re-staging their own slabs.

Every variant accepts bit-packed component slabs (``pack`` —
:mod:`repro.kernels.packing`): sub-int32 codes share int32 words and are
unpacked in-kernel with one extra take + shift/mask, which keeps the
VMEM-resident table bytes at the width the autotuner actually picked
instead of 4 bytes per entry.

The first two take one large row block per grid step
(:func:`repro.kernels.ops._pick_block_rows`) and walk it in native tiles
inside the kernel (:func:`_for_each_tile`): each grid step has a fixed
cost of its own, so a launch runs as few steps as its shape allows,
while each tile's arithmetic is what an 8-row block step computed.

The wrappers lay every component slab out as ``(…, R, 128)`` lane rows
(:func:`~repro.kernels.packing.lane_rows`) and the kernels read them with
:func:`~repro.kernels.packing.lane_take`, the one table lookup Mosaic
compiles for the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .packing import lane_rows, lane_take, unpack_take, unpack_take_traced
from .runtime import resolve_interpret


# Whole-array operand in scalar memory: the per-layer / per-site meta
# tables, indexed in-kernel by the scalar-prefetch ids (a one-row VMEM
# block of an (L, k) table is not a legal TPU tiling).
SMEM_WHOLE = pl.BlockSpec(memory_space=pltpu.SMEM)

# Tiles the in-kernel loop of the per-site kernels handles per loop step.
TILE_UNROLL = 4


def _take(ref0, comp: str, idx, pack):
    """Component gather: direct take on raw int32 slabs, shift/mask unpack
    on bit-packed ones (``pack`` maps component -> static pack meta)."""
    if not pack or comp not in pack:
        return lane_take(ref0, idx)
    p = pack[comp]
    return unpack_take(ref0, idx, width=p["width"], offset=p["offset"],
                       per_word=p["per_word"])


def _for_each_tile(x_ref, body) -> None:
    """Run ``body(rows)`` over the grid step's row block one native tile
    at a time: ``rows`` is a ``pl.ds`` slice of one ``(8, lanes)`` f32
    tile, or ``(16, lanes)`` for a 16-bit input (its packed tile).  A
    large block then costs one grid step but keeps each tile's
    intermediates in vector registers; ``TILE_UNROLL`` tiles per loop
    step let Mosaic interleave independent tiles' table reads.  A block
    that is a single tile (or smaller, the exact-fit decode block) runs
    ``body`` once on the whole block."""
    block_rows = x_ref.shape[0]
    tile = 16 if x_ref.dtype.itemsize == 2 and block_rows % 16 == 0 else 8
    if block_rows <= tile or block_rows % tile:
        body(slice(None))
        return
    n_tiles = block_rows // tile
    unroll = next(u for u in (TILE_UNROLL, 2, 1) if n_tiles % u == 0)

    def step(j, carry):
        for u in range(unroll):
            start = pl.multiple_of((j * unroll + u) * tile, tile)
            body(pl.ds(start, tile))
        return carry

    jax.lax.fori_loop(0, n_tiles // unroll, step, 0)


def _kernel(x_ref, ust_ref, idx_ref, rsh_ref, bias_ref, lb_ref, out_ref, *,
            l, w_lb, w_hb, w_in, w_out, x_lo, x_hi, y_lo, y_hi, pack):
    levels_in = (1 << w_in) - 1
    levels_out = (1 << w_out) - 1
    m = 1 << l

    def tile(rows):
        x = x_ref[rows, :]
        xn = jnp.clip((x.astype(jnp.float32) - x_lo) / (x_hi - x_lo),
                      0.0, 1.0)
        code = jnp.round(xn * levels_in).astype(jnp.int32)
        c_hb = code >> l
        c_lb = code & (m - 1)
        idx = _take(idx_ref[...], "t_idx", c_hb, pack)
        val = _take(ust_ref[...], "t_ust", idx * m + c_lb, pack)
        val = val >> _take(rsh_ref[...], "t_rsh", c_hb, pack)
        val = val + _take(bias_ref[...], "t_bias", c_hb, pack)
        val = val & ((1 << max(w_hb, 1)) - 1)
        if w_lb > 0:
            val = (val << w_lb) | _take(lb_ref[...], "t_lb", code, pack)
        y = val.astype(jnp.float32) / levels_out * (y_hi - y_lo) + y_lo
        out_ref[rows, :] = y.astype(out_ref.dtype)

    _for_each_tile(x_ref, tile)


def lut_act_pallas(
    x: jax.Array,        # (rows, lanes) float
    t_ust: jax.Array,
    t_idx: jax.Array,
    t_rsh: jax.Array,
    t_bias: jax.Array,
    t_lb: jax.Array,
    *,
    l: int,
    w_lb: int,
    w_hb: int,
    w_in: int,
    w_out: int,
    x_lo: float,
    x_hi: float,
    y_lo: float,
    y_hi: float,
    pack: dict | None = None,
    block_rows: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    rows, lanes = x.shape
    if rows % block_rows != 0:
        raise ValueError(
            f"lut_act_pallas: rows={rows} not a multiple of "
            f"block_rows={block_rows}; trailing rows would be dropped by "
            f"the grid — pad the input (ops.lut_act does this)")
    tabs = [lane_rows(t) for t in (t_ust, t_idx, t_rsh, t_bias, t_lb)]
    full = lambda a: pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)
    return pl.pallas_call(
        functools.partial(
            _kernel, l=l, w_lb=w_lb, w_hb=w_hb, w_in=w_in, w_out=w_out,
            x_lo=x_lo, x_hi=x_hi, y_lo=y_lo, y_hi=y_hi, pack=pack,
        ),
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
            *(full(t) for t in tabs),
        ],
        out_specs=pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), x.dtype),
        interpret=interpret,
        name="lut_act",
    )(x, *tabs)


def lut_eval_traced(x, ust, idx_t, rsh, bias, lb, l, w_lb, w_hb,
                    y_lo, y_span, *, any_lb, w_in, w_out, x_lo, x_hi, pack,
                    out_dtype):
    """Shared layer-indexed LUT evaluation body: quantize ``x`` onto the
    input grid, reconstruct via Eq. (1) with **traced** per-layer scalars
    (``l``/``w_lb``/``w_hb`` int32, dequant range float32) over one
    layer's component slabs, dequantize.  Used by the stacked kernel and
    by the fused matmul epilogue (kernels/fused_matmul_lut.py) so both
    run literally the same math."""
    levels_in = (1 << w_in) - 1
    levels_out = (1 << w_out) - 1
    xn = jnp.clip((x.astype(jnp.float32) - x_lo) / (x_hi - x_lo), 0.0, 1.0)
    code = jnp.round(xn * levels_in).astype(jnp.int32)

    m = jnp.left_shift(jnp.int32(1), l)
    c_hb = jnp.right_shift(code, l)
    c_lb = code & (m - 1)
    idx = _take(idx_t, "t_idx", c_hb, pack)
    val = _take(ust, "t_ust", idx * m + c_lb, pack)
    val = jnp.right_shift(val, _take(rsh, "t_rsh", c_hb, pack))
    val = val + _take(bias, "t_bias", c_hb, pack)
    val = val & (jnp.left_shift(jnp.int32(1), jnp.maximum(w_hb, 1)) - 1)
    if any_lb:
        lb_val = _take(lb, "t_lb", code, pack)
        val = jnp.where(w_lb > 0,
                        jnp.left_shift(val, w_lb) | lb_val, val)

    y = val.astype(jnp.float32) / levels_out * y_span + y_lo
    return y.astype(out_dtype)


def _stacked_kernel(lid_ref, x_ref, ust_ref, idx_ref, rsh_ref, bias_ref,
                    lb_ref, mi_ref, mf_ref, out_ref, *,
                    any_lb, w_in, w_out, x_lo, x_hi, pack):
    """Layer-indexed body: the table refs hold ONE layer's slab (selected
    by the scalar-prefetch layer id through the BlockSpec index maps) and
    the per-layer scalars are traced values read from the layer's row of
    the SMEM meta tables — same integer reconstruction math as
    :func:`_kernel`."""
    lid = lid_ref[0]
    l, w_lb, w_hb = mi_ref[lid, 0], mi_ref[lid, 1], mi_ref[lid, 2]
    y_lo, y_span = mf_ref[lid, 0], mf_ref[lid, 1]

    def tile(rows):
        out_ref[rows, :] = lut_eval_traced(
            x_ref[rows, :], ust_ref[0], idx_ref[0], rsh_ref[0], bias_ref[0],
            lb_ref[0], l, w_lb, w_hb, y_lo, y_span,
            any_lb=any_lb, w_in=w_in, w_out=w_out, x_lo=x_lo, x_hi=x_hi,
            pack=pack, out_dtype=out_ref.dtype)

    _for_each_tile(x_ref, tile)


def lut_act_stacked_pallas(
    x: jax.Array,         # (rows, lanes) float
    layer: jax.Array,     # (1,) int32 — in-scan layer id
    t_ust: jax.Array,     # (L, n_ust) int32, padded to the per-site max
    t_idx: jax.Array,     # (L, n_sub) int32
    t_rsh: jax.Array,     # (L, n_sub) int32
    t_bias: jax.Array,    # (L, n_sub) int32
    t_lb: jax.Array,      # (L, n_lb) int32 (dummy rows where w_lb == 0)
    meta_i: jax.Array,    # (L, 3) int32   [l, w_lb, w_hb]
    meta_f: jax.Array,    # (L, 2) float32 [y_lo, y_hi - y_lo]
    *,
    any_lb: bool,
    w_in: int,
    w_out: int,
    x_lo: float,
    x_hi: float,
    pack: dict | None = None,
    block_rows: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    rows, lanes = x.shape
    if rows % block_rows != 0:
        raise ValueError(
            f"lut_act_stacked_pallas: rows={rows} not a multiple of "
            f"block_rows={block_rows}; trailing rows would be dropped by "
            f"the grid — pad the input (ops.lut_act_stacked does this)")
    tabs = [lane_rows(t) for t in (t_ust, t_idx, t_rsh, t_bias, t_lb)]
    row = lambda a: pl.BlockSpec((1,) + a.shape[1:],
                                 lambda i, lid: (lid[0],) + (0,) * (a.ndim - 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, lanes), lambda i, lid: (i, 0)),
            *(row(t) for t in tabs),
            SMEM_WHOLE, SMEM_WHOLE,
        ],
        out_specs=pl.BlockSpec((block_rows, lanes), lambda i, lid: (i, 0)),
    )
    return pl.pallas_call(
        functools.partial(
            _stacked_kernel, any_lb=any_lb, w_in=w_in, w_out=w_out,
            x_lo=x_lo, x_hi=x_hi, pack=pack,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, lanes), x.dtype),
        interpret=interpret,
        name="lut_act_stacked",
    )(layer, x, *tabs, meta_i, meta_f)


def _multisite_kernel(sid_ref, lid_ref, x_ref, ust_ref, idx_ref, rsh_ref,
                      bias_ref, lb_ref, mi_ref, mf_ref, mq_ref, mp_ref,
                      out_ref, *, any_lb):
    """Single-grid multi-site body.  The slab refs hold ONE (site, layer)
    row — the site picked per row-block from the scalar-prefetch side
    table, the layer from the scalar-prefetch layer id — and *every*
    scalar is traced: per-(site, layer) plan meta from ``mi``/``mf``,
    per-site quantizer levels from ``mq``, per-(site, component) pack
    parameters from ``mp``.  The packed unpack runs with traced
    width/offset (``unpack_take_traced``), so one compiled kernel serves
    every site family."""
    sid = sid_ref[pl.program_id(0)]
    lid = lid_ref[0]
    l = mi_ref[sid, lid, 0]
    w_lb = mi_ref[sid, lid, 1]
    w_hb = mi_ref[sid, lid, 2]
    y_lo = mf_ref[sid, lid, 0]
    y_span = mf_ref[sid, lid, 1]
    x_lo = mf_ref[sid, lid, 2]
    # reciprocals, not divisors: the static kernels' constant divisions
    # are strength-reduced by XLA into multiplies by the f32 reciprocal,
    # so the traced math multiplies by the same host-rounded reciprocals
    # (serve/stacked.py MultiSiteSlabs) to stay bit-identical
    x_inv_span = mf_ref[sid, lid, 3]
    levels_in = mq_ref[sid, 0]
    inv_levels_out = mq_ref[sid, 1]

    # component order matches packing.COMPONENTS
    take = lambda ci, ref, idx: unpack_take_traced(
        ref[0, 0], idx, mp_ref[sid, ci, 0], mp_ref[sid, ci, 1],
        mp_ref[sid, ci, 2])

    x = x_ref[...]
    xn = jnp.clip((x.astype(jnp.float32) - x_lo) * x_inv_span, 0.0, 1.0)
    code = jnp.round(xn * levels_in).astype(jnp.int32)

    m = jnp.left_shift(jnp.int32(1), l)
    c_hb = jnp.right_shift(code, l)
    c_lb = code & (m - 1)
    idx = take(1, idx_ref, c_hb)
    val = take(0, ust_ref, idx * m + c_lb)
    val = jnp.right_shift(val, take(2, rsh_ref, c_hb))
    val = val + take(3, bias_ref, c_hb)
    val = val & (jnp.left_shift(jnp.int32(1), jnp.maximum(w_hb, 1)) - 1)
    if any_lb:
        lb_val = take(4, lb_ref, code)
        val = jnp.where(w_lb > 0,
                        jnp.left_shift(val, w_lb) | lb_val, val)

    # coefficient product FIRST: XLA rewrites the static kernels'
    # `val / levels * y_span + y_lo` into `fma(val, f32(1/levels *
    # y_span), y_lo)` — one scalar product, one fused multiply-add — so
    # the traced math must associate the same way to stay bit-identical
    y = val.astype(jnp.float32) * (inv_levels_out * y_span) + y_lo
    out_ref[...] = y.astype(out_ref.dtype)


def lut_act_multisite_pallas(
    x: jax.Array,         # (rows, lanes) float — concatenated site blocks
    block_sites: jax.Array,  # (rows // block_rows,) int32 site id per block
    layer: jax.Array,     # (1,) int32 — in-scan layer id
    t_ust: jax.Array,     # (S, L, n_ust_words) int32, bit-packed
    t_idx: jax.Array,     # (S, L, n_sub_words) int32
    t_rsh: jax.Array,
    t_bias: jax.Array,
    t_lb: jax.Array,
    meta_i: jax.Array,    # (S, L, 3) int32   [l, w_lb, w_hb]
    meta_f: jax.Array,    # (S, L, 4) float32 [y_lo, y_span, x_lo, 1/x_span]
    meta_q: jax.Array,    # (S, 2) float32    [levels_in, 1/levels_out]
    meta_p: jax.Array,    # (S, C, 3) int32   [width, offset, per_word]
    *,
    any_lb: bool,
    block_rows: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """One grid over every site's row-blocks: grid step ``i`` stages the
    ``(block_sites[i], layer)`` slab row of each component through the
    scalar-prefetch index maps, so S sites × L layers of tables live in
    one kernel launch with exactly one (site, layer) slab in VMEM per
    step."""
    interpret = resolve_interpret(interpret)
    rows, lanes = x.shape
    if rows % block_rows != 0:
        raise ValueError(
            f"lut_act_multisite_pallas: rows={rows} not a multiple of "
            f"block_rows={block_rows} (ops.lut_act_multi pads per site)")
    n_blocks = rows // block_rows
    if block_sites.shape != (n_blocks,):
        raise ValueError(
            f"lut_act_multisite_pallas: block_sites {block_sites.shape} "
            f"must be ({n_blocks},) — one site id per row-block")
    tabs = [lane_rows(t) for t in (t_ust, t_idx, t_rsh, t_bias, t_lb)]
    slab = lambda a: pl.BlockSpec(
        (1, 1) + a.shape[2:],
        lambda i, bs, lid: (bs[i], lid[0]) + (0,) * (a.ndim - 2))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((block_rows, lanes), lambda i, bs, lid: (i, 0)),
            *(slab(t) for t in tabs),
            SMEM_WHOLE, SMEM_WHOLE, SMEM_WHOLE, SMEM_WHOLE,
        ],
        out_specs=pl.BlockSpec((block_rows, lanes),
                               lambda i, bs, lid: (i, 0)),
    )
    return pl.pallas_call(
        functools.partial(_multisite_kernel, any_lb=any_lb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, lanes), x.dtype),
        interpret=interpret,
        name="lut_act_multisite",
    )(block_sites, layer, x, *tabs, meta_i, meta_f, meta_q, meta_p)
