"""Jit'd public wrappers around the Pallas kernels.

Handles plan-array packing/padding, plain/decomposed dispatch and the
interpret-mode default (interpret=True everywhere off-TPU; the kernels are
written against TPU BlockSpec tiling and validated in interpret mode).
"""
from __future__ import annotations

import dataclasses
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import DecomposedPlan, Plan, PlainPlan

from . import ref
from .lut_act import (
    lut_act_multisite_pallas,
    lut_act_pallas,
    lut_act_stacked_pallas,
)
from .lut_gather import lut_reconstruct_pallas, plain_lookup_pallas
from .lutnn_layer import lutnn_layer_pallas
from .packing import COMPONENTS, pack_component_dict
from .runtime import default_interpret, resolve_interpret

LANES = 128

# Largest row block (of 128 lanes) one grid step of the per-site LUT
# kernels covers.  A grid step has a fixed cost (starting and awaiting
# its DMAs, pipeline bookkeeping) of ~0.3 us on a TPU v5e, 60x what an
# 8-row block's bytes take, so a launch should run few, large steps; the
# value comes from a chip sweep over {256, 512, 1024, 2048} at
# qwen3-0.6b's prefill shape (PERF.md).
MAX_BLOCK_ROWS = 1024


def _fault_point(point: str) -> None:
    """Serving-control-plane fault injection (repro.serve.faults): the
    wrapper bodies run at trace time inside jitted steps — exactly where
    real lowering/launch failures surface — so armed injectors can stage
    kernel faults deterministically.  Resolved lazily through
    ``sys.modules`` so the kernels package never imports the serving
    layer, and free when no injector is active."""
    faults = sys.modules.get("repro.serve.faults")
    if faults is not None and faults._ACTIVE:
        faults.fault_point(point)


def _pad_to(a: np.ndarray, mult: int) -> np.ndarray:
    n = a.shape[0]
    pad = (-n) % mult
    if pad:
        a = np.concatenate([a, np.zeros(pad, a.dtype)])
    return a


@dataclasses.dataclass
class PlanArrays:
    """Device-ready, lane-padded arrays for one compression plan.

    ``pack`` (component -> static unpack meta, :mod:`.packing`) marks the
    arrays as bit-packed int32 words; ``None`` means raw int32 lanes (the
    gather backend's form).
    """

    kind: str
    w_in: int
    w_out: int
    l: int = 0
    w_lb: int = 0
    w_hb: int = 0
    arrays: dict = dataclasses.field(default_factory=dict)
    pack: dict | None = None

    @staticmethod
    def from_plan(plan: Plan, packed: bool = False) -> "PlanArrays":
        """Device slabs for ``plan``, memoized by plan *content* so
        repeated builds (every ``tables_for_model`` call used to re-pad
        and re-upload the same numpy arrays) reuse one device copy — the
        ``PlanCache`` content-key idiom from ``core/engine.py`` applied
        to the materialization layer."""
        key = _plan_key(plan) + (packed,)
        hit = _FROM_PLAN_CACHE.get(key)
        if hit is not None:
            return hit
        pa = PlanArrays._build(plan, packed)
        _FROM_PLAN_CACHE[key] = pa
        return pa

    @staticmethod
    def _build(plan: Plan, packed: bool) -> "PlanArrays":
        if isinstance(plan, PlainPlan):
            return PlanArrays(
                kind="plain", w_in=plan.w_in, w_out=plan.w_out,
                arrays={"table": jnp.asarray(
                    _pad_to(plan.values.astype(np.int32), LANES))},
            )
        assert isinstance(plan, DecomposedPlan)
        lb = plan.t_lb if plan.t_lb is not None else np.zeros(1, np.int64)
        host = {
            "t_ust": _pad_to(plan.t_ust.astype(np.int32), LANES),
            "t_idx": _pad_to(plan.t_idx.astype(np.int32), LANES),
            "t_rsh": _pad_to(plan.t_rsh.astype(np.int32), LANES),
            "t_bias": _pad_to(plan.t_bias.astype(np.int32), LANES),
            "t_lb": _pad_to(lb.astype(np.int32), LANES),
        }
        pack = None
        if packed:
            host, pack = pack_component_dict(host)
        return PlanArrays(
            kind="decomposed", w_in=plan.w_in, w_out=plan.w_out,
            l=plan.l, w_lb=plan.w_lb, w_hb=plan.w_hb,
            arrays={c: jnp.asarray(a) for c, a in host.items()},
            pack=pack,
        )


def _plan_key(plan: Plan) -> tuple:
    """Content identity of a plan's device slabs (cf. engine._spec_key):
    two plans with the same key materialize bit-identical arrays."""
    if isinstance(plan, PlainPlan):
        return ("plain", plan.w_in, plan.w_out, plan.values.tobytes())
    lb = plan.t_lb.tobytes() if plan.t_lb is not None else b""
    return ("decomposed", plan.w_in, plan.w_out, plan.l, plan.w_lb,
            plan.w_hb, plan.t_ust.tobytes(), plan.t_idx.tobytes(),
            plan.t_rsh.tobytes(), plan.t_bias.tobytes(), lb)


_FROM_PLAN_CACHE: dict[tuple, PlanArrays] = {}


def _shape_2d(n: int, block_rows: int) -> tuple[int, int]:
    rows = -(-n // LANES)
    rows += (-rows) % block_rows
    return rows, LANES


def _pick_block_rows(n: int) -> int:
    """Row block of one grid step for ``n`` elements in ``(rows, 128)``
    lane rows: the largest multiple of 8 that divides ``rows`` (padded to
    a multiple of 8) and is at most :data:`MAX_BLOCK_ROWS`.  Dividing the
    rows keeps :func:`_to_2d` a free reshape wherever the shape tiles
    exactly.  Small decode batches (fewer than 8 rows) run as one
    exact-fit grid step instead of padding up to 8 rows."""
    rows = -(-n // LANES)
    if rows < 8:
        return max(1, rows)
    units = -(-rows // 8)
    return 8 * next(b for b in range(min(units, MAX_BLOCK_ROWS // 8), 0, -1)
                    if units % b == 0)


def _record_grid(kernel: str, rows: int, block_rows: int) -> None:
    """Trace-time gauge ``lut_grid_steps`` (labels ``kernel``, ``rows``,
    ``block_rows``): the grid steps one launch of that shape runs.  Set
    while the wrapper is traced, so it costs nothing at run time."""
    from repro import obs

    obs.gauge("lut_grid_steps", rows // block_rows,
              "grid steps a LUT kernel launch runs", kernel=kernel,
              rows=rows, block_rows=block_rows)


def _to_2d(x: jax.Array, block_rows: int) -> tuple[jax.Array, int]:
    """Flatten ``x`` to a ``(rows, LANES)`` tile grid with ``rows`` a
    multiple of ``block_rows``.  When ``x`` already tiles exactly the
    reshape is free — no zero-fill + copy round-trip."""
    n = int(np.prod(x.shape))
    rows, lanes = _shape_2d(n, block_rows)
    if rows * lanes == n:
        return x.reshape(rows, lanes), n
    flat = jnp.zeros(rows * lanes, x.dtype).at[:n].set(x.reshape(-1))
    return flat.reshape(rows, lanes), n


@functools.partial(jax.jit, static_argnames=("pa_static", "interpret"))
def _reconstruct_jit(x2d, arrays, pa_static, interpret):
    kind, l, w_lb, w_hb = pa_static
    if kind == "plain":
        return plain_lookup_pallas(x2d, arrays["table"], interpret=interpret)
    return lut_reconstruct_pallas(
        x2d, arrays["t_ust"], arrays["t_idx"], arrays["t_rsh"],
        arrays["t_bias"], arrays["t_lb"],
        l=l, w_lb=w_lb, w_hb=w_hb, interpret=interpret,
    )


def lut_reconstruct(
    x: jax.Array, pa: PlanArrays, interpret: bool | None = None
) -> jax.Array:
    """Evaluate the compressed table at int addresses ``x`` (any shape)."""
    _fault_point("pallas:lut_reconstruct")
    interpret = resolve_interpret(interpret)
    shape = x.shape
    x2d, n = _to_2d(x.reshape(-1).astype(jnp.int32), 8)
    out = _reconstruct_jit(
        x2d, pa.arrays, (pa.kind, pa.l, pa.w_lb, pa.w_hb), interpret,
    )
    return out.reshape(-1)[:n].reshape(shape)


def lutnn_layer(
    codes: jax.Array,      # (B, P) int32
    conn: jax.Array,       # (N, F) int32
    tables: jax.Array,     # (N, T) int32
    *,
    bits: int,
    interpret: bool | None = None,
    block_b: int = 128,
    block_n: int = 8,
) -> jax.Array:
    """Evaluate one LUT-NN layer; pads batch/neurons to block multiples."""
    if interpret is None:
        interpret = default_interpret()
    b, p = codes.shape
    n, f = conn.shape
    bp = (-b) % block_b
    np_ = (-n) % block_n
    codes_p = jnp.pad(codes, ((0, bp), (0, 0)))
    conn_p = jnp.pad(conn, ((0, np_), (0, 0)))
    tables_p = jnp.pad(tables, ((0, np_), (0, 0)))
    out = lutnn_layer_pallas(
        codes_p.astype(jnp.int32), conn_p.astype(jnp.int32),
        tables_p.astype(jnp.int32), bits=bits,
        block_b=block_b, block_n=block_n, interpret=interpret,
    )
    return out[:b, :n]


def lut_act(
    x: jax.Array,
    pa: PlanArrays,
    *,
    x_lo: float,
    x_hi: float,
    y_lo: float,
    y_hi: float,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused LUT-approximated activation over a float tensor of any shape."""
    _fault_point("pallas:lut_act")
    interpret = resolve_interpret(interpret)
    assert pa.kind == "decomposed", "lut_act expects a decomposed plan"
    shape = x.shape
    block_rows = _pick_block_rows(int(np.prod(shape)))
    x2d, n = _to_2d(x, block_rows)
    _record_grid("lut_act", x2d.shape[0], block_rows)
    out = lut_act_pallas(
        x2d,
        pa.arrays["t_ust"], pa.arrays["t_idx"], pa.arrays["t_rsh"],
        pa.arrays["t_bias"], pa.arrays["t_lb"],
        l=pa.l, w_lb=pa.w_lb, w_hb=pa.w_hb, w_in=pa.w_in, w_out=pa.w_out,
        x_lo=x_lo, x_hi=x_hi, y_lo=y_lo, y_hi=y_hi, pack=pa.pack,
        block_rows=block_rows, interpret=interpret,
    )
    return out.reshape(-1)[:n].reshape(shape)


def lut_act_stacked(
    x: jax.Array,
    stacked: dict,        # a StackedPlanArrays.entry(): meta/arrays/meta_*
    layer: jax.Array | int,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Layer-indexed fused LUT activation for per-layer tables served
    inside ``lax.scan``: ``layer`` may be a traced in-scan layer id; it is
    fed to the kernel as a scalar-prefetch operand so only that layer's
    table slab is staged into VMEM per grid step."""
    _fault_point("pallas:lut_act_stacked")
    interpret = resolve_interpret(interpret)
    meta = stacked["meta"]
    a = stacked["arrays"]
    # Layer-sharded slabs (placement policy, serve/sharded.py) cannot feed
    # the kernel directly — pallas_call wants the whole stack resident.
    # Under a GSPMD mesh, constrain the table operands back to replicated
    # so the partitioner inserts one all-gather at the point of use (the
    # pallas-backend analogue of the gather backend's jnp.take
    # gather-at-use).  Manual regions skip this: shard_map serving
    # replicates table slabs by construction.
    from repro.nn.sharding import current_manual_axes, current_mesh

    mesh = current_mesh()
    if mesh is not None and not current_manual_axes():
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(mesh, PartitionSpec())
        constrain = lambda t: jax.lax.with_sharding_constraint(t, rep)
        a = {k: constrain(v) for k, v in a.items()}
        stacked = dict(stacked, meta_i=constrain(stacked["meta_i"]),
                       meta_f=constrain(stacked["meta_f"]))
    shape = x.shape
    block_rows = _pick_block_rows(int(np.prod(shape)))
    x2d, n = _to_2d(x, block_rows)
    _record_grid("lut_act_stacked", x2d.shape[0], block_rows)
    out = lut_act_stacked_pallas(
        x2d, jnp.asarray(layer, jnp.int32).reshape(1),
        a["t_ust"], a["t_idx"], a["t_rsh"], a["t_bias"], a["t_lb"],
        stacked["meta_i"], stacked["meta_f"],
        any_lb=meta["any_lb"], w_in=meta["w_in"], w_out=meta["w_out"],
        x_lo=meta["x_lo"], x_hi=meta["x_hi"], pack=meta.get("pack"),
        block_rows=block_rows, interpret=interpret,
    )
    return out.reshape(-1)[:n].reshape(shape)


def lut_act_multi(
    xs: dict,             # site key -> float tensor (any shape)
    entry: dict,          # a MultiSiteSlabs.entry() (serve/stacked.py)
    layer: jax.Array | int,
    *,
    block_rows: int = 8,
    interpret: bool | None = None,
) -> dict:
    """Evaluate several sites' stacked LUT activations in ONE kernel
    launch: each tensor is flattened to ``block_rows``-aligned row blocks,
    the blocks are concatenated into one grid, and a per-block site-id
    side table (scalar prefetch) steers every grid step to its site's
    ``(S, L, n)`` super-slab row.  Returns ``{site: y}`` with each output
    bit-identical to the isolated per-site stacked kernel on the same
    tensor (asserted in tests/test_kernels_fused.py).

    A single-site dict is the serving form: every ``apply_lut_act`` call
    under ``kernel="fused"`` tables runs through this one compiled kernel
    against the shared super-slab instead of per-site programs with
    per-site table uploads.
    """
    _fault_point("pallas:lut_act_multi")
    interpret = resolve_interpret(interpret)
    meta = entry["meta"]
    site_order = meta["sites"]
    a = entry["arrays"]
    parts, sids, dims = [], [], []
    for site, x in xs.items():
        sid = site_order.index(site)
        x2d, n = _to_2d(x, block_rows)
        parts.append(x2d)
        sids.extend([sid] * (x2d.shape[0] // block_rows))
        dims.append((site, x.shape, n, x2d.shape[0]))
    big = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    block_sites = jnp.asarray(np.asarray(sids, np.int32))
    out = lut_act_multisite_pallas(
        big, block_sites, jnp.asarray(layer, jnp.int32).reshape(1),
        a["t_ust"], a["t_idx"], a["t_rsh"], a["t_bias"], a["t_lb"],
        entry["meta_i"], entry["meta_f"], entry["meta_q"], entry["meta_p"],
        any_lb=meta["any_lb"], block_rows=block_rows, interpret=interpret,
    )
    ys, start = {}, 0
    for site, shape, n, rows in dims:
        y = out[start:start + rows]
        ys[site] = y.reshape(-1)[:n].reshape(shape)
        start += rows
    return ys


def wkv(q, k, v, log_w, u, *, chunk: int = 16, interpret: bool | None = None):
    """Chunked WKV via the Pallas kernel. q/k/v/log_w: (B, T, H, N) f32;
    u: (H, N). Returns (y (B,T,H,N), state (B,H,N,N))."""
    from .wkv import wkv_pallas

    if interpret is None:
        interpret = default_interpret()
    b, t, h, n = q.shape
    pad = (-t) % chunk
    zpad = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if pad:
        q, k, v, log_w = map(zpad, (q, k, v, log_w))
    fl = lambda a: a.transpose(0, 2, 1, 3).reshape(b * h, t + pad, n)
    u_fl = jnp.broadcast_to(u[None], (b, h, n)).reshape(b * h, 1, n)
    y, s = wkv_pallas(
        fl(q.astype(jnp.float32)), fl(k.astype(jnp.float32)),
        fl(v.astype(jnp.float32)), fl(log_w.astype(jnp.float32)),
        u_fl.astype(jnp.float32), chunk=chunk, interpret=interpret)
    y = y.reshape(b, h, t + pad, n).transpose(0, 2, 1, 3)[:, :t]
    return y, s.reshape(b, h, n, n)
