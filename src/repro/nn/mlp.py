"""Feed-forward blocks (dense), with optional LUT-approximated activation.

The LUT activation is the paper-technique integration point for the LM
architectures (DESIGN.md SS2/SS5): the elementwise nonlinearity is replaced
by a quantize -> compressed-table-lookup -> dequantize evaluated from
ReducedLUT plan arrays.  Inside distributed train/serve steps the lookup is
expressed with ``jnp.take`` (gather) so GSPMD can shard it; the fused
Pallas kernel (kernels/lut_act.py) is the single-device serving fast path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import sites
from repro.calib import capture as calib_capture
from repro.obs import drift as obs_drift

from .layers import activation_fn, is_gated, logits_projection
from .sharding import layer_scan, shard


def _stored(x):
    """``x`` exactly as a stored tensor of its dtype holds it.

    The Pallas kernels read their input from memory and write their output
    to memory, so they see and produce values rounded to the tensor's
    dtype.  Inside one XLA fusion a bf16 intermediate may instead be
    carried at f32 (``xla_allow_excess_precision``), so the gather
    evaluators would quantize the unrounded matmul output and hand an
    unrounded activation to the next op.  On a TPU v5e that changed nearly
    every MLP output of a 28-layer decode; the barrier keeps the gather
    form's boundary where the kernel's is."""
    return jax.lax.optimization_barrier(x)


def lut_act_jnp(x, arrays, *, l, w_lb, w_hb, w_in, w_out,
                x_lo, x_hi, y_lo, y_hi):
    """GSPMD-friendly (gather-based) LUT activation, same math as the
    Pallas kernel / ref oracle."""
    x = _stored(x)
    levels_in = (1 << w_in) - 1
    levels_out = (1 << w_out) - 1
    xn = jnp.clip((x.astype(jnp.float32) - x_lo) / (x_hi - x_lo), 0.0, 1.0)
    code = jnp.round(xn * levels_in).astype(jnp.int32)
    m = 1 << l
    c_hb = code >> l
    c_lb = code & (m - 1)
    idx = jnp.take(arrays["t_idx"], c_hb, axis=0)
    val = jnp.take(arrays["t_ust"], idx * m + c_lb, axis=0)
    val = val >> jnp.take(arrays["t_rsh"], c_hb, axis=0)
    val = val + jnp.take(arrays["t_bias"], c_hb, axis=0)
    val = val & ((1 << max(w_hb, 1)) - 1)
    if w_lb > 0:
        val = (val << w_lb) | jnp.take(arrays["t_lb"], code, axis=0)
    y = val.astype(jnp.float32) / levels_out * (y_hi - y_lo) + y_lo
    return _stored(y.astype(x.dtype))


def lut_act_jnp_stacked(x, stacked: dict, layer):
    """GSPMD-friendly layer-indexed LUT activation over a stacked
    ``(L, …)`` table family (:mod:`repro.serve.stacked`).

    ``layer`` may be the traced in-scan layer id: the per-layer component
    arrays and scalar metas are selected with ``jnp.take`` along axis 0,
    and the reconstruction runs with traced shift amounts/masks.  The
    integer math — and the float32 dequant expression, whose per-layer
    span is pre-rounded host-side — is bit-identical to
    :func:`lut_act_jnp` on that layer's unstacked arrays.
    """
    x = _stored(x)
    meta = stacked["meta"]
    layer = jnp.asarray(layer, jnp.int32)
    take_l = lambda a: jnp.take(a, layer, axis=0)
    mi = take_l(stacked["meta_i"])
    mf = take_l(stacked["meta_f"])
    l, w_lb, w_hb = mi[0], mi[1], mi[2]
    y_lo, y_span = mf[0], mf[1]
    arrays = {k: take_l(a) for k, a in stacked["arrays"].items()}

    levels_in = (1 << meta["w_in"]) - 1
    levels_out = (1 << meta["w_out"]) - 1
    xn = jnp.clip((x.astype(jnp.float32) - meta["x_lo"])
                  / (meta["x_hi"] - meta["x_lo"]), 0.0, 1.0)
    code = jnp.round(xn * levels_in).astype(jnp.int32)
    m = jnp.left_shift(jnp.int32(1), l)
    c_hb = jnp.right_shift(code, l)
    c_lb = code & (m - 1)
    idx = jnp.take(arrays["t_idx"], c_hb, axis=0)
    val = jnp.take(arrays["t_ust"], idx * m + c_lb, axis=0)
    val = jnp.right_shift(val, jnp.take(arrays["t_rsh"], c_hb, axis=0))
    val = val + jnp.take(arrays["t_bias"], c_hb, axis=0)
    val = val & (jnp.left_shift(jnp.int32(1), jnp.maximum(w_hb, 1)) - 1)
    if meta["any_lb"]:
        lb_val = jnp.take(arrays["t_lb"], code, axis=0)
        val = jnp.where(w_lb > 0, jnp.left_shift(val, w_lb) | lb_val, val)
    y = val.astype(jnp.float32) / levels_out * y_span + y_lo
    return _stored(y.astype(x.dtype))


def tables_per_layer(lut_tables: dict | None) -> bool:
    """True when any site entry carries *unrolled* per-layer tables (the
    legacy ``"layers"`` list) — each layer closes over its own arrays, so
    the layer stack must python-unroll with concrete indices."""
    if not lut_tables or "sites" not in lut_tables:
        return False
    return any(isinstance(e, dict) and "layers" in e
               for e in lut_tables["sites"].values())


def tables_stacked(lut_tables: dict | None) -> bool:
    """True when any site entry carries stacked per-layer tables — the
    ``"stacked"`` ``(L, …)`` form (:mod:`repro.serve.stacked`) or a
    ``"multi"`` marker into the shared multi-site super-slab — so the
    layer stack keeps ``lax.scan`` and resolves each layer's table slab
    with the traced in-scan layer id."""
    if not lut_tables or "sites" not in lut_tables:
        return False
    return any(isinstance(e, dict) and ("stacked" in e or "multi" in e)
               for e in lut_tables["sites"].values())


def needs_layer_ids(lut_tables: dict | None) -> bool:
    """True when the layer loop must python-unroll so every call site has
    a *concrete* layer index: legacy unrolled per-layer tables, or an
    active activation-capture context (per-site histogram keys are
    strings).  Stacked per-layer tables do NOT unroll — they consume a
    traced layer id inside the scan."""
    return tables_per_layer(lut_tables) or calib_capture.capture_active()


def run_layers(body, carry, xs, *, lut_tables=None, remat=False):
    """Run a layer stack: ``body(carry, inp, layer) -> (carry, y)``.

    Scans (``layer_scan``, compact O(1)-in-depth HLO) by default, with
    ``layer=None``.  Stacked per-layer tables also scan — the body then
    receives the *traced* in-scan layer id, which the stacked table forms
    resolve with ``jnp.take`` / scalar prefetch.  Only the legacy unrolled
    table form and activation capture still python-unroll with concrete
    indices (see :func:`needs_layer_ids`); the unrolled output pytree is
    stacked to match the scan's exactly.  Each unrolled layer ends at an
    optimization barrier, as each scan iteration ends at its stored carry:
    otherwise XLA may fuse across layers and carry bf16 values at f32
    into the next one, and a LUT bin edge then flips a token between the
    two forms.
    """
    if needs_layer_ids(lut_tables):
        fn = jax.checkpoint(body, static_argnums=(2,)) if remat else body
        length = jax.tree.leaves(xs)[0].shape[0]
        ys = []
        for i in range(length):
            carry, y = fn(carry, jax.tree.map(lambda a: a[i], xs), i)
            carry, y = jax.lax.optimization_barrier((carry, y))
            ys.append(y)
        stacked = jax.tree.map(lambda *vs: jnp.stack(vs), *ys)
        return carry, stacked
    if tables_stacked(lut_tables):
        length = jax.tree.leaves(xs)[0].shape[0]
        fn = lambda c, inp: body(c, inp[0], inp[1])
        if remat:
            fn = jax.checkpoint(fn)
        return layer_scan(fn, carry,
                          (xs, jnp.arange(length, dtype=jnp.int32)))
    fn = lambda c, inp: body(c, inp, None)
    if remat:
        fn = jax.checkpoint(fn)
    return layer_scan(fn, carry, xs)


def site_tables(lut_tables: dict | None, site: str | None = None,
                layer=None) -> dict | None:
    """Resolve one site's table entry (default: the MLP activation site).

    Four shapes are accepted: the legacy bare single-table dict (routed
    through :func:`repro.sites.coerce_site_tables`, which maps it to the
    MLP site with a DeprecationWarning), the serving-plans multi-site
    dict ``{"sites": {site: {...}}, "backend": ...}``, the unrolled
    per-layer form ``{"layers": [...]}`` (one entry per layer, resolved
    by a *concrete* ``layer`` index), and the stacked per-layer form
    ``{"stacked": {...}}`` (``(L, …)`` padded stacks,
    :mod:`repro.serve.stacked`), whose ``layer`` may be a **traced**
    in-scan id — resolution is deferred to the evaluators.
    """
    lut_tables = sites.coerce_site_tables(lut_tables)
    if lut_tables is None:
        return None
    site = sites.MLP if site is None else site
    entry = lut_tables["sites"].get(site)
    if entry is None or not any(
            k in entry for k in ("layers", "stacked", "multi")):
        return entry
    if layer is None:
        raise ValueError(
            f"per-layer LUT tables for site {site!r} need a layer index — "
            f"run the forward through run_layers (this family's loop may "
            f"not support per-layer tables)")
    if entry.get("first_layer"):
        layer = layer - entry["first_layer"]
    if "multi" in entry:
        return {"multi_entry": lut_tables["multi"], "site": entry["multi"],
                "layer": layer}
    if "stacked" in entry:
        return {"stacked": entry["stacked"], "layer": layer}
    return entry["layers"][layer]


def entry_operands(tab: dict):
    """Split a resolved site entry into ``(array_operands, rebuild)``.

    ``shard_map`` regions may not close over traced values (the in-scan
    layer id) and should not close over table slabs whose placement the
    mesh policy controls — both must ride in as explicit mapped
    operands.  ``array_operands`` is the pytree of device arrays to pass
    through the shard_map (layer id included, as int32); ``rebuild``
    recreates the entry the evaluators consume from that pytree inside
    the region (the python-scalar meta is closed over — it is static).
    """
    if "multi_entry" in tab:
        raise ValueError(
            "entry_operands: multi-site fused tables are the single-device "
            "fast path — build mesh tables with kernel='isolated'")
    if "stacked" in tab:
        st = tab["stacked"]
        meta = st["meta"]
        ops = {"arrays": st["arrays"], "meta_i": st["meta_i"],
               "meta_f": st["meta_f"],
               "layer": jnp.asarray(tab["layer"], jnp.int32)}

        def rebuild(ops):
            return {"stacked": {"meta": meta, "arrays": ops["arrays"],
                                "meta_i": ops["meta_i"],
                                "meta_f": ops["meta_f"]},
                    "layer": ops["layer"]}

        return ops, rebuild
    meta = tab["meta"]
    ops = {"arrays": tab["arrays"]}

    def rebuild(ops):
        return {"meta": meta, "arrays": ops["arrays"]}

    return ops, rebuild


def apply_lut_act(x, tab: dict, backend: str = "gather"):
    """Evaluate one compressed-table activation entry on ``x``.

    ``backend="gather"`` is the GSPMD-shardable ``jnp.take`` form used
    inside distributed steps; ``backend="pallas"`` routes through the fused
    quantize/reconstruct/dequantize kernel (single-device serving fast
    path).  Both compute the identical quantize -> Eq. (1) -> dequantize
    math and bit-match each other (tests/test_serve_plans.py), in the
    per-plan form and the layer-indexed stacked form alike
    (tests/test_stacked.py).
    """
    if "multi_entry" in tab:
        if backend != "pallas":
            raise ValueError(
                "apply_lut_act: multi-site super-slab entries are "
                "Pallas-only (bit-packed, traced-meta kernel); build "
                "gather tables with kernel='isolated'")
        from repro.kernels.ops import lut_act_multi

        site = tab["site"]
        return lut_act_multi({site: x}, tab["multi_entry"],
                             tab["layer"])[site]
    if "stacked" in tab:
        if backend == "pallas":
            from repro.kernels.ops import lut_act_stacked

            return lut_act_stacked(x, tab["stacked"], tab["layer"])
        return lut_act_jnp_stacked(x, tab["stacked"], tab["layer"])
    meta, arrays = tab["meta"], tab["arrays"]
    if backend == "pallas":
        from repro.kernels import PlanArrays
        from repro.kernels.ops import lut_act as lut_act_fused

        pa = PlanArrays(
            kind="decomposed", w_in=meta["w_in"], w_out=meta["w_out"],
            l=meta["l"], w_lb=meta["w_lb"], w_hb=meta["w_hb"],
            arrays=arrays, pack=meta.get("pack"),
        )
        return lut_act_fused(
            x, pa, x_lo=meta["x_lo"], x_hi=meta["x_hi"],
            y_lo=meta["y_lo"], y_hi=meta["y_hi"],
        )
    return lut_act_jnp(x, arrays, **meta)


def fused_matmul_tab(cfg, lut_tables: dict | None, site: str,
                     layer=None) -> dict | None:
    """Resolve the site entry for the matmul-epilogue fused path, or
    ``None`` when the unfused composition must run.

    The fused kernel (:mod:`repro.kernels.fused_matmul_lut`) is the
    single-device Pallas serving fast path: it requires ``cfg.lut_fuse``,
    the Pallas backend, an active site with served tables, no GSPMD mesh
    (the gather backend's sharding constraints must shape the distributed
    program) and no activation capture (the capture wrapper must see the
    pre-activation tensor).  Every ``None`` here falls back to a path
    already asserted bit-identical, so flipping ``lut_fuse`` never
    changes served tokens."""
    if not (getattr(cfg, "lut_fuse", False) and cfg.lut_activation
            and lut_tables is not None):
        return None
    if lut_tables.get("backend") != "pallas":
        return None
    if calib_capture.capture_active():
        return None
    if obs_drift.monitor_active():
        # The drift monitor's wrapper must see the pre-activation tensor
        # (make_activation), which the matmul-epilogue kernel consumes
        # in-VMEM; the unfused composition it falls back to is
        # bit-identical, so monitoring never changes served tokens.
        return None
    from .sharding import current_mesh

    if current_mesh() is not None:
        return None
    spec = sites.site_spec(site)
    if not spec.active(cfg):
        return None
    return site_tables(lut_tables, site, layer if spec.per_layer else None)


def make_activation(cfg, lut_tables: dict | None, site: str | None = None,
                    fallback: str | None = None, layer: int | None = None):
    """Returns act(x) for the configured nonlinearity.

    ``site`` is a registered site key (:mod:`repro.sites`; default the
    MLP activation site).  With ``cfg.lut_activation``, the site active
    under the config's ``lut_sites`` scope, and compiled plan arrays
    available (per-layer arrays resolved via ``layer``), the activation
    evaluates the ReducedLUT-compressed table; otherwise the exact
    ``fallback`` (default ``cfg.activation``) runs.  While an activation
    capture is active — and the site is active — the returned callable
    additionally streams its input into the capture's ``(layer, site)``
    histogram.
    """
    site = sites.MLP if site is None else site
    spec = sites.site_spec(site)
    act = None
    cap = None
    if spec.active(cfg):
        if cfg.lut_activation and lut_tables is not None:
            tab = site_tables(lut_tables, site, layer)
            if tab is not None:
                backend = lut_tables.get("backend", "gather")
                act = lambda x: apply_lut_act(x, tab, backend)
        cap = calib_capture.current()
    if act is None:
        act = activation_fn(fallback or cfg.activation)
    mon = obs_drift.current()
    if mon is not None and spec.active(cfg):
        # Drift monitor: counts this site's don't-care lookups on device
        # and ships one scalar per call through a debug callback — the
        # traced in-scan ``layer`` is a callback operand, so (unlike
        # capture) monitoring never forces the layer stack to unroll.
        act = mon.wrap(site, layer, act)
    if cap is not None:
        act = cap.wrap(site, layer, act, domain=spec.domain())
    return act


def site_act(cfg, lut_tables: dict | None, site: str, layer=None):
    """Resolve one non-default scalar site to a callable, or ``None``.

    Returns ``None`` whenever the site is inactive for this config (not
    hosted, or outside the ``lut_sites`` scope) *and* no capture is
    running — callers keep their exact inline math on the ``None`` path,
    byte-identical to the pre-registry forward.  Otherwise the callable
    evaluates the site's compressed table (when plan arrays are served)
    or the exact scalar function, wrapped to stream capture histograms
    while a capture context is active.
    """
    spec = sites.site_spec(site)
    if not spec.active(cfg):
        return None
    lyr = layer if spec.per_layer else None
    fn = None
    if cfg.lut_activation and lut_tables is not None:
        tab = site_tables(lut_tables, site, lyr)
        if tab is not None:
            backend = lut_tables.get("backend", "gather")
            fn = lambda x: apply_lut_act(x, tab, backend)
    cap = calib_capture.current()
    # The drift monitor observes *served LUT lookups*: it wraps only
    # sites actually evaluating a compressed table (fn is not None), so
    # it never forces the exact-math inline path through a callable —
    # the None path stays byte-identical to the unmonitored forward.
    mon = obs_drift.current()
    if fn is None and cap is None:
        return None
    if fn is None:
        fn = sites.exact_fn(spec, cfg)
    elif mon is not None:
        fn = mon.wrap(site, lyr, fn)
    if cap is not None:
        fn = cap.wrap(site, lyr, fn, domain=spec.domain())
    return fn


def project_logits(x, lm_head, cfg, lut_tables: dict | None = None):
    """Final logits projection, with optional tanh soft-capping.

    Without ``cfg.logit_softcap`` this is exactly
    :func:`repro.nn.layers.logits_projection`.  With it, the logits are
    scaled, tanh-capped and rescaled — and the tanh is the registered
    softcap site, so under an active scope it evaluates the compressed
    table (network-global: one table, no layer index).
    """
    logits = logits_projection(x, lm_head)
    cap_scale = getattr(cfg, "logit_softcap", None)
    if not cap_scale:
        return logits
    scaled = logits.astype(jnp.float32) / cap_scale
    tanh = site_act(cfg, lut_tables, sites.LOGIT_SOFTCAP)
    capped = tanh(scaled) if tanh is not None else jnp.tanh(scaled)
    return (cap_scale * capped).astype(logits.dtype)


def mlp_block(params: dict, x: jax.Array, cfg, lut_tables=None,
              layer: int | None = None) -> jax.Array:
    """(B, T, d) -> (B, T, d). swiglu uses fused [gate|up] in w_in.

    Under ``cfg.lut_fuse`` (Pallas backend, single device, no capture)
    the up-projection GEMM and the LUT activation run as ONE Pallas
    kernel — the gated form multiplies ``act(gate) * up`` before the
    tile leaves VMEM (:mod:`repro.kernels.fused_matmul_lut`)."""
    ftab = fused_matmul_tab(cfg, lut_tables, sites.MLP, layer)
    if ftab is not None:
        from repro.kernels.fused_matmul_lut import fused_matmul_lut

        h = fused_matmul_lut(x, params["w_in"], ftab,
                             gated=is_gated(cfg.activation))
        out = jnp.einsum("btf,fd->btd", h, params["w_out"])
        return shard(out, "dp", "sp", None)
    act = make_activation(cfg, lut_tables, layer=layer)
    if is_gated(cfg.activation):
        gate_up = jnp.einsum("btd,df->btf", x, params["w_in"])
        gate_up = shard(gate_up, "dp", None, "tp")
        gate, up = jnp.split(gate_up, 2, axis=-1)
        h = act(gate) * up
    else:
        h = jnp.einsum("btd,df->btf", x, params["w_in"])
        h = shard(h, "dp", None, "tp")
        h = act(h)
    out = jnp.einsum("btf,fd->btd", h, params["w_out"])
    return shard(out, "dp", "sp", None)
