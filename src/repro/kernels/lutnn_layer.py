"""Pallas TPU kernel: evaluate one layer of LUT-NN neurons.

The serving hot-path of the paper's workload (a NeuraLUT network is just
layers of table lookups).  Grid tiles (batch x neurons); each step holds a
neuron block's truth tables in VMEM plus the full parent-code block, packs
addresses with shifts/ors, and gathers per-neuron outputs.

VMEM budget per step: ``BLOCK_N * 2^(bits*F) * 4B`` for tables (e.g. 32
neurons x 4096-entry tables = 512 KB) + ``BLOCK_B * P * 4B`` codes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .runtime import resolve_interpret


def _kernel(codes_ref, conn_ref, tables_ref, out_ref, *, bits, fanin):
    codes = codes_ref[...]        # (BB, P)
    conn = conn_ref[...]          # (BN, F)
    tables = tables_ref[...]      # (BN, T)
    bb = codes.shape[0]
    bn = conn.shape[0]
    # gather parent codes: (BB, BN, F)
    gathered = jnp.take(codes, conn.reshape(-1), axis=1).reshape(
        bb, bn, fanin
    )
    addr = jnp.zeros((bb, bn), dtype=jnp.int32)
    for k in range(fanin):
        addr = addr | (gathered[..., k] << (bits * (fanin - 1 - k)))
    # per-neuron table gather: out[b, n] = tables[n, addr[b, n]]
    out = jnp.take_along_axis(tables, addr.T.astype(jnp.int32), axis=1)
    out_ref[...] = out.T


def lutnn_layer_pallas(
    codes: jax.Array,    # (B, P) int32
    conn: jax.Array,     # (N, F) int32
    tables: jax.Array,   # (N, T) int32
    *,
    bits: int,
    block_b: int = 128,
    block_n: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    b, p = codes.shape
    n, f = conn.shape
    t = tables.shape[1]
    grid = (b // block_b, n // block_n)
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits, fanin=f),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, p), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, f), lambda i, j: (j, 0)),
            pl.BlockSpec((block_n, t), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.int32),
        interpret=interpret,
        name="lutnn_layer",
    )(codes, conn, tables)
