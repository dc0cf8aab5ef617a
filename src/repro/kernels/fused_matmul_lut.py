"""Matmul-epilogue LUT fusion: GEMM + quantize/Eq.(1)/dequantize in one
Pallas kernel.

The serving hot path computes ``h = x @ w`` and immediately feeds ``h``
(or its gate half) through the LUT-approximated activation — as two
kernels, the GEMM output round-trips HBM just to be re-read by the
lookup.  This kernel applies the stacked LUT activation *in the matmul
epilogue* while the output tile is still in VMEM: the grid blocks over
output rows and, where the weights would not fit VMEM whole, over output
columns (full K per step, so every output element comes from the same
full contraction the reference ``jnp.einsum`` performs), the layer's
bit-packed component slab is
staged by the scalar-prefetch layer id exactly like
:func:`~repro.kernels.lut_act.lut_act_stacked_pallas`, and the gated form
(``swiglu``-style ``act(gate) * up`` over a fused ``[gate|up]`` weight)
multiplies the halves before the tile leaves VMEM.

Wired behind ``cfg.lut_fuse`` (``nn/mlp.py`` / ``nn/ssm.py`` pick this
path for the MLP / FFN sites on the Pallas backend, single device, no
active capture) and asserted token-for-token bit-identical to the gather
reference by ``verify_backend_equivalence`` and
tests/test_kernels_fused.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .lut_act import SMEM_WHOLE, lut_eval_traced
from .packing import lane_rows
from .runtime import resolve_interpret


def _fused_kernel(lid_ref, x_ref, *refs, gated, any_lb, w_in, w_out, x_lo,
                  x_hi, pack):
    n_w = 2 if gated else 1
    w_refs, refs = refs[:n_w], refs[n_w:]
    ust_ref, idx_ref, rsh_ref, bias_ref, lb_ref, mi_ref, mf_ref, out_ref = refs
    lid = lid_ref[0]
    x = x_ref[...]
    # accumulate in f32 and round to the model dtype explicitly: the
    # unfused reference materializes the einsum output (one rounding to
    # x.dtype) before the LUT quantizer, and a dtype-out dot may legally
    # keep the f32 accumulation alive into the epilogue — which moves
    # values across quantization-bin edges and breaks bit-identity
    dot = lambda w_ref: jnp.dot(
        x, w_ref[...], preferred_element_type=jnp.float32
    ).astype(out_ref.dtype)
    gate = dot(w_refs[0])
    y = lut_eval_traced(
        gate, ust_ref[0], idx_ref[0], rsh_ref[0], bias_ref[0], lb_ref[0],
        mi_ref[lid, 0], mi_ref[lid, 1], mi_ref[lid, 2],
        mf_ref[lid, 0], mf_ref[lid, 1],
        any_lb=any_lb, w_in=w_in, w_out=w_out, x_lo=x_lo, x_hi=x_hi,
        pack=pack, out_dtype=out_ref.dtype)
    out_ref[...] = y * dot(w_refs[1]) if gated else y


# Bytes of weight blocks the fused kernel may hold in VMEM (double-buffered
# gate and up blocks included), well inside the TPU's default scoped VMEM
# limit.  Full-width weights of a 1024 x 2*3072 bf16 MLP would take ~24 MiB.
_WEIGHT_VMEM_BUDGET = 8 << 20


def _pick_block_n(k: int, n_out: int, itemsize: int, gated: bool) -> int:
    """Widest output-column block whose weight tiles fit the VMEM budget:
    all of ``n_out`` when it fits, else the widest 128-multiple divisor.
    Every output element still contracts the full K in one dot."""
    per_col = k * itemsize * (2 if gated else 1) * 2
    if n_out * per_col <= _WEIGHT_VMEM_BUDGET:
        return n_out
    for bn in range(n_out - n_out % 128, 0, -128):
        if n_out % bn == 0 and bn * per_col <= _WEIGHT_VMEM_BUDGET:
            return bn
    raise ValueError(
        f"fused_matmul_lut: no 128-multiple column block of N={n_out} fits "
        f"{_WEIGHT_VMEM_BUDGET} B of weight tiles at K={k}")


def fused_matmul_lut_pallas(
    x: jax.Array,         # (M, K) float — flattened tokens
    w: jax.Array,         # (K, N) float — N = 2*features when gated
    layer: jax.Array,     # (1,) int32 — in-scan layer id
    t_ust: jax.Array,     # (L, n) int32 slabs (bit-packed or raw)
    t_idx: jax.Array,
    t_rsh: jax.Array,
    t_bias: jax.Array,
    t_lb: jax.Array,
    meta_i: jax.Array,    # (L, 3) int32   [l, w_lb, w_hb]
    meta_f: jax.Array,    # (L, 2) float32 [y_lo, y_span]
    *,
    gated: bool,
    any_lb: bool,
    w_in: int,
    w_out: int,
    x_lo: float,
    x_hi: float,
    pack: dict | None = None,
    block_m: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    if gated and n % 2:
        raise ValueError(f"fused_matmul_lut: gated needs even N, got {n}")
    if m % block_m != 0:
        raise ValueError(
            f"fused_matmul_lut: M={m} not a multiple of block_m={block_m} "
            f"(ops.fused_matmul_lut pads the token rows)")
    n_out = n // 2 if gated else n
    bn = _pick_block_n(k, n_out, w.dtype.itemsize, gated)
    n_blk = n_out // bn
    tabs = [lane_rows(t) for t in (t_ust, t_idx, t_rsh, t_bias, t_lb)]
    row = lambda a: pl.BlockSpec(
        (1,) + a.shape[1:], lambda i, j, lid: (lid[0],) + (0,) * (a.ndim - 1))
    # gate columns [j*bn, (j+1)*bn) and, gated, their up partners n_out on
    w_specs = [pl.BlockSpec((k, bn), lambda i, j, lid: (0, j))]
    if gated:
        w_specs.append(pl.BlockSpec((k, bn), lambda i, j, lid: (0, j + n_blk)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // block_m, n_blk),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i, j, lid: (i, 0)),
            *w_specs,
            *(row(t) for t in tabs),
            SMEM_WHOLE, SMEM_WHOLE,
        ],
        out_specs=pl.BlockSpec((block_m, bn), lambda i, j, lid: (i, j)),
    )
    return pl.pallas_call(
        functools.partial(
            _fused_kernel, gated=gated, any_lb=any_lb, w_in=w_in,
            w_out=w_out, x_lo=x_lo, x_hi=x_hi, pack=pack,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n_out), x.dtype),
        interpret=interpret,
        name="lut_fused_matmul",
    )(layer, x, *([w] * len(w_specs)), *tabs, meta_i, meta_f)


def _as_stacked_parts(tab: dict):
    """Normalize a resolved site entry to the stacked-slab form the fused
    kernel consumes: ``(arrays, meta_i, meta_f, layer, statics)``.

    Three entry shapes arrive here (see ``repro.nn.mlp.site_tables``):
    the stacked per-layer form, the multi-site marker (statically sliced
    out of the shared super-slab), and the shared/unrolled per-plan form
    (wrapped as a one-layer stack at layer 0)."""
    if "multi_entry" in tab:
        from repro.serve.stacked import multi_site_stacked_entry

        st = multi_site_stacked_entry(tab["multi_entry"], tab["site"])
        return (st["arrays"], st["meta_i"], st["meta_f"], tab["layer"],
                st["meta"])
    if "stacked" in tab:
        st = tab["stacked"]
        return (st["arrays"], st["meta_i"], st["meta_f"], tab["layer"],
                st["meta"])
    meta, arrays = tab["meta"], tab["arrays"]
    stacked = {c: a[None] for c, a in arrays.items()}
    meta_i = jnp.asarray(
        np.array([[meta["l"], meta["w_lb"], meta["w_hb"]]], np.int32))
    # span rounded f64 -> f32 host-side, same as StackedPlanArrays
    meta_f = jnp.asarray(
        np.array([[meta["y_lo"], meta["y_hi"] - meta["y_lo"]]], np.float32))
    statics = {"w_in": meta["w_in"], "w_out": meta["w_out"],
               "x_lo": meta["x_lo"], "x_hi": meta["x_hi"],
               "any_lb": meta["w_lb"] > 0, "pack": meta.get("pack")}
    return stacked, meta_i, meta_f, 0, statics


def fused_matmul_lut(
    x: jax.Array,         # (B, T, K) float
    w: jax.Array,         # (K, N) float
    tab: dict,            # resolved site entry (stacked / multi / shared)
    *,
    gated: bool,
    interpret: bool | None = None,
) -> jax.Array:
    """``act(x @ w)`` — or ``act(gate) * up`` over a fused ``[gate|up]``
    weight — with the LUT activation applied in the matmul epilogue.
    Bit-identical to ``jnp.einsum`` followed by ``apply_lut_act`` on the
    same entry (the in-kernel dot contracts full K per output element,
    identical accumulation order)."""
    arrays, meta_i, meta_f, layer, statics = _as_stacked_parts(tab)
    b, t, k = x.shape
    m = b * t
    block_m = 8 if m >= 8 else m
    m_pad = -(-m // block_m) * block_m
    x2d = x.reshape(m, k)
    if m_pad != m:
        x2d = jnp.pad(x2d, ((0, m_pad - m), (0, 0)))
    out = fused_matmul_lut_pallas(
        x2d, w, jnp.asarray(layer, jnp.int32).reshape(1),
        arrays["t_ust"], arrays["t_idx"], arrays["t_rsh"],
        arrays["t_bias"], arrays["t_lb"], meta_i, meta_f,
        gated=gated, any_lb=statics["any_lb"], w_in=statics["w_in"],
        w_out=statics["w_out"], x_lo=statics["x_lo"], x_hi=statics["x_hi"],
        pack=statics.get("pack"), block_m=block_m, interpret=interpret,
    )
    return out[:m].reshape(b, t, -1)
