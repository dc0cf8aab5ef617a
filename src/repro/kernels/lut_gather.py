"""Pallas TPU kernel: batched Eq. (1) reconstruction.

TPU adaptation of the paper's FPGA recombination wiring (DESIGN.md SS2):
the decomposed component tables are small *by construction* — that is what
the compression optimizes — so they are pinned whole in VMEM while the
input batch streams through the grid.  All ops are vectorized int32
gathers/shifts/adds on (8, 128)-aligned tiles, so the kernel is
memory-bound on the HBM read of ``x`` alone — the roofline optimum for a
table evaluator.

Layout contract (enforced by ops.py):
  x       (rows, 128) int32  — flattened/padded query addresses
  t_ust   (n_ust * M,) padded to 128 | t_idx/t_rsh/t_bias (n_sub,) padded
  t_lb    (2^w_in,) padded to 128 (always passed; dummy zeros when w_lb=0)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .runtime import resolve_interpret


def _kernel(x_ref, ust_ref, idx_ref, rsh_ref, bias_ref, lb_ref, out_ref,
            *, l, w_lb, w_hb):
    x = x_ref[...]
    m = 1 << l
    x_hb = x >> l
    x_lb = x & (m - 1)
    idx = jnp.take(idx_ref[...], x_hb, axis=0)
    val = jnp.take(ust_ref[...], idx * m + x_lb, axis=0)
    val = val >> jnp.take(rsh_ref[...], x_hb, axis=0)
    val = val + jnp.take(bias_ref[...], x_hb, axis=0)
    val = val & ((1 << max(w_hb, 1)) - 1)
    if w_lb > 0:
        val = (val << w_lb) | jnp.take(lb_ref[...], x, axis=0)
    out_ref[...] = val


def lut_reconstruct_pallas(
    x: jax.Array,        # (rows, 128) int32
    t_ust: jax.Array,
    t_idx: jax.Array,
    t_rsh: jax.Array,
    t_bias: jax.Array,
    t_lb: jax.Array,
    *,
    l: int,
    w_lb: int,
    w_hb: int,
    block_rows: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    rows, lanes = x.shape
    if rows % block_rows != 0:
        raise ValueError(
            f"lut_reconstruct_pallas: rows={rows} not a multiple of "
            f"block_rows={block_rows}; trailing rows would be dropped by "
            f"the grid — pad the input (ops.lut_reconstruct does this)")
    grid = (rows // block_rows,)
    full = lambda a: pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)
    return pl.pallas_call(
        functools.partial(_kernel, l=l, w_lb=w_lb, w_hb=w_hb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
            full(t_ust), full(t_idx), full(t_rsh), full(t_bias), full(t_lb),
        ],
        out_specs=pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
        interpret=interpret,
        name="lut_reconstruct",
    )(x, t_ust, t_idx, t_rsh, t_bias, t_lb)


def _plain_kernel(x_ref, table_ref, out_ref):
    out_ref[...] = jnp.take(table_ref[...], x_ref[...], axis=0)


def plain_lookup_pallas(
    x: jax.Array, table: jax.Array, *, block_rows: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    rows, lanes = x.shape
    if rows % block_rows != 0:
        raise ValueError(
            f"plain_lookup_pallas: rows={rows} not a multiple of "
            f"block_rows={block_rows}; trailing rows would be dropped by "
            f"the grid — pad the input (ops.lut_reconstruct does this)")
    return pl.pallas_call(
        _plain_kernel,
        grid=(rows // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
            pl.BlockSpec(table.shape, lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.int32),
        interpret=interpret,
        name="lut_plain_lookup",
    )(x, table)
