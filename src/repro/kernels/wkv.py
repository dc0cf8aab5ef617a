"""Pallas TPU kernel: chunked RWKV6 WKV (gated linear attention).

Motivated directly by EXPERIMENTS.md §Perf H1: the pure-JAX chunked WKV
materializes the (C, C, N) pairwise-decay block in HBM every scan step —
the dominant HBM term of the rwkv train cell. This kernel keeps that
block in VMEM: the grid walks (batch*heads, time-chunks); the recurrent
state lives in a VMEM scratch that persists across the sequential chunk
dimension, so HBM traffic is exactly q/k/v/log_w in + y out (the roofline
floor).

Math identical to nn/ssm.py (all exponents provably <= 0):
    y_i   = sum_{j<i} (q_i . k_j e^{Lc_{i-1}-Lc_j}) v_j
          + (q_i . (u*k_i)) v_i  +  (q_i e^{Lc_{i-1}}) @ S
    S'    = e^{Lc_last} * S + sum_j (k_j e^{Lc_last - Lc_j})^T v_j
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .runtime import resolve_interpret


def _kernel(q_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, s_out_ref, s_ref,
            *, chunk, n, n_chunks):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    qc = q_ref[0]          # (C, N)
    kc = k_ref[0]
    vc = v_ref[0]
    lw = lw_ref[0]
    u = u_ref[0]           # (1, N)
    s = s_ref[...]         # (N, N)

    lc = jnp.cumsum(lw, axis=0)                     # (C, N)
    # pairwise decay in VMEM: (C, C, N), exponents <= 0
    diff = (lc - lw)[:, None, :] - lc[None, :, :]
    mask = jnp.tril(jnp.ones((chunk, chunk), bool), k=-1)
    dec = jnp.where(mask[..., None], jnp.exp(diff), 0.0)
    a = jnp.einsum("in,jn,ijn->ij", qc, kc, dec)
    y = a @ vc
    # u-bonus diagonal
    diag = jnp.sum(qc * (u * kc), axis=1, keepdims=True)
    y = y + diag * vc
    # state contribution
    q_t = qc * jnp.exp(lc - lw)
    y = y + q_t @ s
    y_ref[0] = y
    # state update
    ltot = lc[-1:]
    k_dec = kc * jnp.exp(ltot - lc)
    s_new = jnp.exp(ltot[0])[:, None] * s + k_dec.T @ vc
    s_ref[...] = s_new

    @pl.when(c_idx == n_chunks - 1)
    def _final():
        s_out_ref[0] = s_new


def wkv_pallas(
    q: jax.Array,       # (BH, T, N) f32 — batch*heads flattened
    k: jax.Array,
    v: jax.Array,
    log_w: jax.Array,
    u: jax.Array,       # (BH, 1, N)
    *,
    chunk: int = 16,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (BH, T, N), final state (BH, N, N))."""
    interpret = resolve_interpret(interpret)
    bh, t, n = q.shape
    assert t % chunk == 0, (t, chunk)
    n_chunks = t // chunk
    grid = (bh, n_chunks)
    blk = lambda: pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0))
    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n=n, n_chunks=n_chunks),
        grid=grid,
        in_specs=[blk(), blk(), blk(), blk(),
                  pl.BlockSpec((1, 1, n), lambda b, c: (b, 0, 0))],
        out_specs=[blk(), pl.BlockSpec((1, n, n), lambda b, c: (b, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, n), jnp.float32),
            jax.ShapeDtypeStruct((bh, n, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
        name="wkv",
    )(q, k, v, log_w, u)
