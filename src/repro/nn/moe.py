"""Mixture-of-Experts with expert parallelism over the model axis.

Dispatch strategy (DESIGN.md SS4): activations are data-sharded and
replicated across the model axis, experts are sharded over the model axis.
Every device routes the *same* local tokens (deterministic), gathers the
tokens bound for its resident experts into a fixed-capacity buffer
(sort-based, no (S, E, C) one-hot), runs the expert GEMMs, scatters partial
outputs, and a single all-reduce over the model axis combines them — the
same collective cost as one TP MLP, with experts' memory truly sharded.

Outside a mesh the same routine runs with all experts local (e0=0,
no psum) — used by smoke tests and as the numerical reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import sites
from repro.compat import shard_map

from .layers import activation_fn
from .sharding import DP_AXES, TP_AXIS, current_manual_axes, current_mesh


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def moe_ffn_local(
    x: jax.Array,           # (S, d) local tokens
    router_w: jax.Array,    # (d, E)
    w_in: jax.Array,        # (E_loc, d, 2*f) fused gate|up
    w_out: jax.Array,       # (E_loc, f, d)
    *,
    n_experts: int,
    top_k: int,
    capacity: int,
    e0,                     # first resident expert id (traced or 0)
    act_name: str = "silu",
    act_fn=None,            # override (e.g. LUT-compressed expert act)
    norm_topk_prob: bool = True,
):
    """Route + gather + expert GEMM + weighted scatter for local experts."""
    s, d = x.shape
    e_loc = w_in.shape[0]
    logits = jnp.einsum("sd,de->se", x.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_ids = jax.lax.top_k(probs, top_k)          # (S, k)
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    flat_ids = top_ids.reshape(-1)                        # (S*k,)
    order = jnp.argsort(flat_ids)                         # stable
    sorted_ids = flat_ids[order]
    counts = jnp.bincount(flat_ids, length=n_experts)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(s * top_k) - starts[sorted_ids]
    local = (sorted_ids >= e0) & (sorted_ids < e0 + e_loc) & (rank < capacity)
    slot = jnp.where(local, (sorted_ids - e0) * capacity + rank,
                     e_loc * capacity)
    src = order // top_k                                  # token index

    buf = jnp.zeros((e_loc * capacity + 1, d), x.dtype)
    buf = buf.at[slot].set(x[src], mode="drop")
    tokens = buf[:-1].reshape(e_loc, capacity, d)

    act = act_fn if act_fn is not None else activation_fn(act_name)
    h = jnp.einsum("ecd,edf->ecf", tokens, w_in)
    gate, up = jnp.split(h, 2, axis=-1)
    h = act(gate) * up
    y_exp = jnp.einsum("ecf,efd->ecd", h, w_out)
    y_flat = jnp.concatenate(
        [y_exp.reshape(e_loc * capacity, d),
         jnp.zeros((1, d), y_exp.dtype)], axis=0
    )

    gathered = y_flat[slot]                                # (S*k, d)
    weights = top_p.reshape(-1)[order]
    contrib = gathered * (weights[:, None] * local[:, None]).astype(x.dtype)
    y = jnp.zeros((s, d), x.dtype).at[src].add(contrib)

    # Switch-style load-balance auxiliary (local estimate)
    frac = counts.astype(jnp.float32) / (s * top_k)
    imp = probs.mean(axis=0)
    aux = n_experts * jnp.sum(frac * imp)
    return y, aux


def moe_block(params: dict, x: jax.Array, cfg, shared_mlp=None,
              lut_tables=None, layer: int | None = None):
    """(B, T, d) -> ((B, T, d), aux_loss). Uses shard_map EP under a mesh
    with a model axis; plain local compute otherwise.  With serving plans
    carrying the expert site, the per-expert nonlinearity evaluates
    the ReducedLUT-compressed table for this ``layer`` — the table arrays
    and the (possibly traced, in-scan) layer id ride into the
    expert-parallel shard_map as *explicit mapped operands*
    (:func:`repro.nn.mlp.entry_operands`), replicated across the region,
    instead of being closed over; only the python-scalar meta stays a
    closure.  Inside an already-manual region (the top-level serving
    shard_map, :mod:`repro.serve.sharded`) no nested shard_map may open:
    expert parallelism then runs inline against the enclosing region's
    axis bindings.  make_activation also hooks the expert site into any
    active calibration capture."""
    from repro.calib import capture as calib_capture

    from .mlp import apply_lut_act, entry_operands, make_activation, \
        site_tables

    b, t, d = x.shape
    m = cfg.moe
    mesh = current_mesh()
    s_local_tokens = b * t
    act_name = "silu"
    act_fn = make_activation(cfg, lut_tables, site=sites.EXPERT,
                             fallback=act_name, layer=layer)

    tab = None
    backend = "gather"
    if (cfg.lut_activation and lut_tables is not None
            and not calib_capture.capture_active()):
        tab = site_tables(lut_tables, sites.EXPERT, layer)
        backend = lut_tables.get("backend", "gather")

    manual = current_manual_axes()
    if mesh is not None and TP_AXIS in manual:
        # Inside a manual shard_map over the model axis: operands arrived
        # as local shards, axis_index/psum bind to the enclosing region.
        n_tp = mesh.shape[TP_AXIS]
        e_loc = params["w_in"].shape[0]
        ep = n_tp > 1 and e_loc * n_tp == m.n_experts
        capacity = _round_up(
            max(int(s_local_tokens * m.top_k / m.n_experts
                    * m.capacity_factor), m.top_k), 8)
        e0 = jax.lax.axis_index(TP_AXIS) * e_loc if ep else 0
        y, aux = moe_ffn_local(
            x.reshape(-1, d), params["router"], params["w_in"],
            params["w_out"], n_experts=m.n_experts, top_k=m.top_k,
            capacity=capacity, e0=e0, act_name=act_name, act_fn=act_fn,
            norm_topk_prob=m.norm_topk_prob,
        )
        if ep:
            y = jax.lax.psum(y, TP_AXIS)
            aux = jax.lax.psum(aux, TP_AXIS) / n_tp
        y = y.reshape(b, t, d)
        if shared_mlp is not None:
            y = y + shared_mlp(x)
        return y, aux

    tp = (mesh is not None and TP_AXIS in mesh.axis_names
          and m.n_experts % mesh.shape[TP_AXIS] == 0)
    if tp:
        n_tp = mesh.shape[TP_AXIS]
        dp_axes = tuple(a for a in DP_AXES if a in mesh.axis_names)
        n_dp = 1
        for a in dp_axes:
            n_dp *= mesh.shape[a]
        s_shard = max(1, s_local_tokens // n_dp)
        capacity = _round_up(
            max(int(s_shard * m.top_k / m.n_experts * m.capacity_factor),
                m.top_k), 8)
        tab_ops, rebuild = (entry_operands(tab) if tab is not None
                            else ({}, None))

        def mapped(xl, router_w, w_in, w_out, tab_ops):
            e_loc = w_in.shape[0]
            e0 = jax.lax.axis_index(TP_AXIS) * e_loc
            act = (act_fn if rebuild is None else
                   (lambda z: apply_lut_act(z, rebuild(tab_ops), backend)))
            y, aux = moe_ffn_local(
                xl.reshape(-1, d), router_w, w_in, w_out,
                n_experts=m.n_experts, top_k=m.top_k, capacity=capacity,
                e0=e0, act_name=act_name, act_fn=act,
                norm_topk_prob=m.norm_topk_prob,
            )
            y = jax.lax.psum(y, TP_AXIS)
            aux = jax.lax.psum(aux, TP_AXIS) / n_tp
            if dp_axes:
                aux = jax.lax.pmean(aux, dp_axes)
            return y.reshape(xl.shape), aux

        dspec = dp_axes if dp_axes else None
        y, aux = shard_map(
            mapped, mesh=mesh,
            in_specs=(P(dspec, None, None), P(None, None),
                      P(TP_AXIS, None, None), P(TP_AXIS, None, None),
                      jax.tree.map(lambda _: P(), tab_ops)),
            out_specs=(P(dspec, None, None), P()),
            check_vma=False,
        )(x, params["router"], params["w_in"], params["w_out"], tab_ops)
    else:
        capacity = _round_up(
            max(int(s_local_tokens * m.top_k / m.n_experts
                    * m.capacity_factor), m.top_k), 8)
        y, aux = moe_ffn_local(
            x.reshape(-1, d), params["router"], params["w_in"],
            params["w_out"], n_experts=m.n_experts, top_k=m.top_k,
            capacity=capacity, e0=0, act_name=act_name, act_fn=act_fn,
            norm_topk_prob=m.norm_topk_prob,
        )
        y = y.reshape(b, t, d)

    if shared_mlp is not None:
        y = y + shared_mlp(x)
    return y, aux


def moe_ffn_held(
    x: jax.Array,           # (S, d) tokens
    router_w: jax.Array,    # (d, E): the router over every expert
    w_in: jax.Array,        # (E_held, d, 2*f) fused gate|up of the held
    w_out: jax.Array,       # (E_held, f, d)
    *,
    top_k: int,
    first_expert: int = 0,
    norm_topk_prob: bool = True,
    act_fn=None,
    tile: int = 512,
):
    """A dropless expert layer that holds experts ``first_expert ..
    first_expert + E_held - 1`` of the router's ``E``.

    Routes every token over all ``E`` experts (softmax, top ``k``,
    renormalised only with ``norm_topk_prob``), then computes the held
    experts' part of the result for every (token, expert) pair routed
    here, and no other: the pairs are sorted by held expert and taken
    ``tile`` rows at a time in a loop whose trip count is the number of
    tiles the pairs fill.  So the work follows the pairs routed here,
    padded by fewer than ``tile`` rows an expert, and no pair is ever
    dropped.  Returns ``(y (S, d), counts (E_held + 1,) int32)``:
    ``counts[e]`` pairs routed to held expert ``e``, the last entry those
    routed to experts held elsewhere.
    """
    s, d = x.shape
    e_loc = w_in.shape[0]
    tile = min(tile, _round_up(s, 8))     # fewer rows for a small batch
    logits = jnp.einsum("sd,de->se", x.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_ids = jax.lax.top_k(probs, top_k)           # (S, k)
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    flat_ids = top_ids.reshape(-1)                         # (S*k,)
    local = (flat_ids >= first_expert) & (flat_ids < first_expert + e_loc)
    key = jnp.where(local, flat_ids - first_expert, e_loc)
    order = jnp.argsort(key, stable=True)                  # held first
    counts = jnp.bincount(key, length=e_loc + 1).astype(jnp.int32)
    held = counts[:e_loc]
    starts = jnp.cumsum(held) - held
    tiles = (held + tile - 1) // tile
    tile_end = jnp.cumsum(tiles)
    n_tiles = -(-s * top_k // tile) + e_loc               # at most
    t = jnp.arange(n_tiles)
    tile_e = jnp.minimum(jnp.searchsorted(tile_end, t, side="right"),
                         e_loc - 1)
    first_row = starts[tile_e] + (t - (tile_end - tiles)[tile_e]) * tile
    rows_left = starts[tile_e] + held[tile_e] - first_row
    order = jnp.concatenate([order, jnp.zeros((tile,), order.dtype)])
    weights = top_p.reshape(-1)
    act = act_fn if act_fn is not None else jax.nn.silu

    def one_tile(i, y):
        e = tile_e[i]
        pairs = jax.lax.dynamic_slice(order, (first_row[i],), (tile,))
        tok = pairs // top_k
        h = jnp.einsum("td,df->tf", x[tok], w_in[e])
        gate, up = jnp.split(h, 2, axis=-1)
        out = jnp.einsum("tf,fd->td", act(gate) * up, w_out[e],
                         preferred_element_type=jnp.float32)
        wt = jnp.where(jnp.arange(tile) < rows_left[i], weights[pairs], 0.0)
        return y.at[tok].add(out * wt[:, None])

    y = jax.lax.fori_loop(0, tile_end[-1], one_tile,
                          jnp.zeros((s, d), jnp.float32))
    return y.astype(x.dtype), counts


def held_moe_block(params: dict, x: jax.Array, cfg, lut_tables=None,
                   layer=None):
    """(B, T, d) -> ((B, T, d), counts): :func:`moe_ffn_held` with the
    ``expert`` LUT site's activation for ``layer``; the shared experts
    are the caller's."""
    from .mlp import make_activation

    b, t, d = x.shape
    m = cfg.moe
    act = make_activation(cfg, lut_tables, site=sites.EXPERT,
                          fallback="silu", layer=layer)
    y, counts = moe_ffn_held(
        x.reshape(-1, d), params["router"], params["w_in"],
        params["w_out"], top_k=m.top_k, first_expert=m.first_expert,
        norm_topk_prob=m.norm_topk_prob, act_fn=act)
    return y.reshape(b, t, d), counts
