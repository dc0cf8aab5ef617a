"""Stacked plan execution: per-layer compressed tables as one (L, …) family.

Per-site calibration (PR 3) gives every ``(layer, site)`` its own
ReducedLUT plan — but plans differ in shape (the engine picks a different
``m``/``w_lb`` split per table), so the first integration python-unrolled
every layer stack to let each layer close over its own arrays.  That
unroll costs O(L) compile time, exactly the wrong direction for deep
models (ROADMAP: "per-layer tables inside ``lax.scan`` via padded stacked
arrays would drop the unroll").

:class:`StackedPlanArrays` is the scanned-serving data structure:

* each component array (``t_ust``/``t_idx``/``t_rsh``/``t_bias``/``t_lb``)
  is zero-padded to the per-site maximum length across layers and stacked
  to one ``(L, n_max)`` int32 device array — padding is dead weight the
  runtime never addresses (a layer's reconstruction only indexes its own
  true region), and the true per-layer lengths are kept for accounting
  and lossless unstacking;
* the per-layer scalar metas become ``(L, 3)`` int32 (``l``, ``w_lb``,
  ``w_hb``) and ``(L, 2)`` float32 (``y_lo``, ``y_hi - y_lo``) side
  tables, read with the in-scan layer id.  The dequant span is
  precomputed host-side in float64 and rounded once to float32 — the same
  rounding the unrolled path's ``y_hi - y_lo`` constant gets — so the
  stacked evaluators stay bit-identical to the per-layer ones.

The quantizer statics (``w_in``/``w_out``/``x_lo``/``x_hi``) must agree
across layers (one capture grid per site kind produces exactly that), and
``any_lb`` records statically whether *any* layer carries a low-bit
table, so all-``w_lb=0`` stacks skip the recombination branch entirely.

The runtime consumers are :func:`repro.nn.mlp.lut_act_jnp_stacked`
(gather backend, ``jnp.take`` along axis 0 inside ``layer_scan``) and
:func:`repro.kernels.ops.lut_act_stacked` (layer-id scalar-prefetch
Pallas kernel); both receive the plain-dict :meth:`entry` form so the nn
layer never imports this module.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.packing import (
    COMPONENTS,
    MAX_PACK_WIDTH,
    pack_array,
    unpack_array,
)

# Meta keys that must be constant across a site's layers: they describe
# the input quantizer (shared by construction — one capture grid per site
# kind) and the output bit-width the engine searched under.
SHARED_META = ("w_in", "w_out", "x_lo", "x_hi")


@dataclasses.dataclass
class StackedPlanArrays:
    """Padded ``(L, …)`` stacks of one site's per-layer plan arrays."""

    n_layers: int
    w_in: int
    w_out: int
    x_lo: float
    x_hi: float
    any_lb: bool
    arrays: dict                 # component -> (L, n_max) jnp.int32
    meta_i: jax.Array            # (L, 3) int32   [l, w_lb, w_hb]
    meta_f: jax.Array            # (L, 2) float32 [y_lo, y_hi - y_lo]
    lens: dict                   # component -> per-layer true lengths
    metas: tuple                 # original per-layer scalar metas

    @staticmethod
    def from_entries(entries: list[dict]) -> "StackedPlanArrays":
        """Stack per-layer ``{"meta", "arrays"}`` entries (the unrolled
        serving form) into one padded ``(L, …)`` family."""
        if not entries:
            raise ValueError("StackedPlanArrays: no per-layer entries")
        metas = tuple(dict(e["meta"]) for e in entries)
        for key in SHARED_META:
            vals = {m[key] for m in metas}
            if len(vals) != 1:
                raise ValueError(
                    f"StackedPlanArrays: per-layer plans disagree on "
                    f"{key!r} ({sorted(vals)}) — a site's layers must share "
                    f"one input/output quantizer to stack")
        lens = {c: tuple(int(e["arrays"][c].shape[0]) for e in entries)
                for c in COMPONENTS}
        arrays = {}
        for c in COMPONENTS:
            n_max = max(lens[c])
            rows = [np.pad(np.asarray(e["arrays"][c], dtype=np.int32),
                           (0, n_max - n))
                    for e, n in zip(entries, lens[c])]
            arrays[c] = jnp.asarray(np.stack(rows))
        meta_i = jnp.asarray(np.array(
            [[m["l"], m["w_lb"], m["w_hb"]] for m in metas], np.int32))
        # span rounded f64 -> f32 once, matching the unrolled path's
        # (y_hi - y_lo) python-float constant bit-for-bit
        meta_f = jnp.asarray(np.array(
            [[m["y_lo"], m["y_hi"] - m["y_lo"]] for m in metas],
            np.float32))
        m0 = metas[0]
        return StackedPlanArrays(
            n_layers=len(entries), w_in=m0["w_in"], w_out=m0["w_out"],
            x_lo=m0["x_lo"], x_hi=m0["x_hi"],
            any_lb=any(m["w_lb"] > 0 for m in metas),
            arrays=arrays, meta_i=meta_i, meta_f=meta_f, lens=lens,
            metas=metas)

    # -- serving forms -----------------------------------------------------
    def entry(self, packed: bool = False) -> dict:
        """The plain-dict form the runtime consumes (see module doc).

        ``packed=True`` returns the bit-packed slab form for the Pallas
        backend: each ``(L, n)`` component stack is packed along its last
        axis into int32 words at one uniform width per component
        (:mod:`repro.kernels.packing`), and the static per-component
        unpack parameters ride in ``meta["pack"]``.  The gather backend
        keeps consuming the raw form — its ``jnp.take`` math is untouched.
        """
        meta = {"w_in": self.w_in, "w_out": self.w_out,
                "x_lo": self.x_lo, "x_hi": self.x_hi,
                "any_lb": self.any_lb, "n_layers": self.n_layers}
        arrays = self.arrays
        if packed:
            arrays, pack = self.packed_arrays()
            meta["pack"] = pack
        return {
            "meta": meta,
            "arrays": arrays,
            "meta_i": self.meta_i,
            "meta_f": self.meta_f,
        }

    def packed_arrays(self) -> tuple[dict, dict]:
        """Bit-packed ``(L, n_words)`` component stacks + static unpack
        meta, memoized per instance (one host pack + device upload no
        matter how many serving forms are built)."""
        cached = getattr(self, "_packed", None)
        if cached is None:
            arrays, pack = {}, {}
            for c in COMPONENTS:
                words, p = pack_array(np.asarray(self.arrays[c]))
                arrays[c] = jnp.asarray(words)
                pack[c] = p
            cached = (arrays, pack)
            object.__setattr__(self, "_packed", cached)
        return cached

    def layer_entry(self, layer: int) -> dict:
        """Unstack one layer back to its unrolled ``{"meta", "arrays"}``
        entry (exact inverse of :meth:`from_entries` — the ragged-padding
        round-trip asserted in tests)."""
        return {
            "meta": dict(self.metas[layer]),
            "arrays": {c: self.arrays[c][layer, :self.lens[c][layer]]
                       for c in COMPONENTS},
        }

    def split_layers(self, sizes: tuple[int, ...]) -> list:
        """Re-chunk the stack into contiguous layer groups (the shape a
        layer-sharding placement hands each device): one
        ``StackedPlanArrays`` per group, each re-padded to its *local*
        maximum — exactly what a shard materializes.  ``sizes`` must sum
        to ``n_layers``."""
        if sum(sizes) != self.n_layers or any(s <= 0 for s in sizes):
            raise ValueError(
                f"split_layers: sizes {sizes} must be positive and sum to "
                f"n_layers={self.n_layers}")
        parts, start = [], 0
        for s in sizes:
            parts.append(StackedPlanArrays.from_entries(
                [self.layer_entry(i) for i in range(start, start + s)]))
            start += s
        return parts

    @staticmethod
    def concat_layers(parts: list) -> "StackedPlanArrays":
        """Inverse of :meth:`split_layers`: restack the chunks (global
        re-pad) — the ``lens``/``metas`` round-trip is asserted by the
        re-chunk property test in tests/test_stacked.py."""
        entries = [p.layer_entry(i) for p in parts
                   for i in range(p.n_layers)]
        return StackedPlanArrays.from_entries(entries)

    # -- accounting --------------------------------------------------------
    @property
    def nbytes(self) -> int:
        """Device bytes this stack uploads (padding included)."""
        n = sum(int(a.size) * a.dtype.itemsize for a in self.arrays.values())
        return n + int(self.meta_i.size) * 4 + int(self.meta_f.size) * 4

    @property
    def padding_frac(self) -> float:
        """Fraction of stacked table bytes that are ragged-pad dead weight."""
        true = sum(sum(self.lens[c]) for c in COMPONENTS)
        total = sum(int(a.size) for a in self.arrays.values())
        return float(1.0 - true / total) if total else 0.0

    @property
    def packed_nbytes(self) -> int:
        """Device bytes of the bit-packed slab form (meta tables included)
        — the footprint the Pallas backend actually uploads."""
        arrays, _ = self.packed_arrays()
        n = sum(int(a.size) * a.dtype.itemsize for a in arrays.values())
        return n + int(self.meta_i.size) * 4 + int(self.meta_f.size) * 4


@dataclasses.dataclass
class MultiSiteSlabs:
    """Every per-layer site family of a model as ONE ``(S, L, n)``
    bit-packed super-slab for the single-grid multi-site kernel
    (:func:`repro.kernels.lut_act.lut_act_multisite_pallas`).

    Where :class:`StackedPlanArrays` collapses L per-layer kernel
    *programs* into one layer-indexed kernel, this collapses the S
    per-site *launches* of a serving step into one grid: each component
    stack is bit-packed per (site, component) at its own width, padded to
    the cross-site word maximum, and stacked along a leading site axis;
    every per-site scalar the isolated kernels bake in as Python statics
    (quantizer levels, tabulation domain, pack widths) moves into traced
    ``(S, …)`` meta side tables indexed by the per-row-block site id:

    * ``meta_i`` ``(S, L, 3)`` int32 — per-(site, layer) ``l``/``w_lb``/
      ``w_hb`` (the stacked form's table, per site);
    * ``meta_f`` ``(S, L, 4)`` float32 — ``y_lo``/``y_span`` per (site,
      layer) plus the per-site ``x_lo``/``1/x_span``, every span
      pre-rounded f64 -> f32 host-side exactly like the stacked form so
      the traced quantizer stays bit-identical to the static one (the
      reciprocal, not the span: XLA strength-reduces the static kernels'
      constant divisions into reciprocal multiplies, and the traced math
      must replay that multiply bit-for-bit);
    * ``meta_q`` ``(S, 2)`` float32 — ``2^w_in - 1`` and
      ``1 / (2^w_out - 1)`` (levels exact in float32 for every supported
      width, the output reciprocal host-rounded like the domain one);
    * ``meta_p`` ``(S, C, 3)`` int32 — width/offset/per_word per (site,
      component) in :data:`~repro.kernels.packing.COMPONENTS` order.

    Sites must agree on ``n_layers`` (the scan they serve inside) and
    every component must pack at width <= ``MAX_PACK_WIDTH`` — the traced
    unpack's shift/mask math does not support the raw-int32 fallback.
    """

    sites: tuple
    n_layers: int
    any_lb: bool
    arrays: dict                 # component -> (S, L, n_words_max) int32
    meta_i: jax.Array            # (S, L, 3) int32
    meta_f: jax.Array            # (S, L, 4) float32
    meta_q: jax.Array            # (S, 2) float32
    meta_p: jax.Array            # (S, C, 3) int32
    site_meta: dict              # site -> python statics (for fused slicing)

    @staticmethod
    def from_stacks(stacks: dict) -> "MultiSiteSlabs":
        """Build from ``{site: StackedPlanArrays}`` (insertion order fixes
        the site-id assignment)."""
        if not stacks:
            raise ValueError("MultiSiteSlabs: no site stacks")
        n_layers = {s.n_layers for s in stacks.values()}
        if len(n_layers) != 1:
            raise ValueError(
                f"MultiSiteSlabs: sites disagree on n_layers "
                f"({sorted(n_layers)}) — they cannot share one layer scan")
        order = tuple(stacks)
        packed = {site: st.packed_arrays() for site, st in stacks.items()}
        for site, (_, pack) in packed.items():
            for c, p in pack.items():
                if p["width"] > MAX_PACK_WIDTH:
                    raise ValueError(
                        f"MultiSiteSlabs: site {site!r} component {c} "
                        f"needs width {p['width']} > {MAX_PACK_WIDTH} — "
                        f"serve it isolated instead")
        arrays = {}
        for c in COMPONENTS:
            w_max = max(int(packed[s][0][c].shape[1]) for s in order)
            rows = [np.pad(np.asarray(packed[s][0][c]),
                           ((0, 0), (0, w_max - packed[s][0][c].shape[1])))
                    for s in order]
            arrays[c] = jnp.asarray(np.stack(rows))
        meta_i = jnp.asarray(np.stack(
            [np.asarray(stacks[s].meta_i) for s in order]))
        # per-(site, layer) dequant meta + per-site domain, spans rounded
        # f64 -> f32 once (host-side), matching the static kernels' python
        # float constants bit-for-bit
        mf = []
        for s in order:
            st = stacks[s]
            # 1/x_span instead of x_span: XLA strength-reduces the static
            # kernels' divide-by-constant into a multiply by the f32
            # reciprocal, so the traced math must multiply by the SAME
            # host-rounded reciprocal to stay bit-identical (a traced
            # true division differs by 1 ulp on ~half the inputs)
            inv_span = np.float32(1.0) / np.float32(st.x_hi - st.x_lo)
            dom = np.tile(np.array(
                [[st.x_lo, inv_span]], np.float32), (st.n_layers, 1))
            mf.append(np.concatenate([np.asarray(st.meta_f), dom], axis=1))
        meta_f = jnp.asarray(np.stack(mf))
        meta_q = jnp.asarray(np.array(
            [[np.float32((1 << stacks[s].w_in) - 1),
              np.float32(1.0) / np.float32((1 << stacks[s].w_out) - 1)]
             for s in order], np.float32))
        meta_p = jnp.asarray(np.array(
            [[[packed[s][1][c]["width"], packed[s][1][c]["offset"],
               packed[s][1][c]["per_word"]] for c in COMPONENTS]
             for s in order], np.int32))
        site_meta = {
            s: {"w_in": stacks[s].w_in, "w_out": stacks[s].w_out,
                "x_lo": stacks[s].x_lo, "x_hi": stacks[s].x_hi,
                "any_lb": stacks[s].any_lb, "n_layers": stacks[s].n_layers,
                "pack": packed[s][1]}
            for s in order}
        return MultiSiteSlabs(
            sites=order, n_layers=next(iter(n_layers)),
            any_lb=any(st.any_lb for st in stacks.values()),
            arrays=arrays, meta_i=meta_i, meta_f=meta_f, meta_q=meta_q,
            meta_p=meta_p, site_meta=site_meta)

    def entry(self) -> dict:
        """The plain-dict form the runtime consumes
        (``repro.kernels.ops.lut_act_multi`` and the fused matmul's
        per-site static slicing)."""
        return {
            "meta": {"sites": self.sites, "n_layers": self.n_layers,
                     "any_lb": self.any_lb, "site_meta": self.site_meta},
            "arrays": self.arrays,
            "meta_i": self.meta_i,
            "meta_f": self.meta_f,
            "meta_q": self.meta_q,
            "meta_p": self.meta_p,
        }

    def site_stacked_entry(self, site: str) -> dict:
        """One site's slice of the super-slab as a packed *stacked* entry
        (``StackedPlanArrays.entry(packed=True)`` shape) — the form the
        fused matmul epilogue consumes.  Slicing happens inside the jitted
        program; the underlying buffers stay the shared super-slab."""
        sid = self.sites.index(site)
        sm = self.site_meta[site]
        return {
            "meta": {"w_in": sm["w_in"], "w_out": sm["w_out"],
                     "x_lo": sm["x_lo"], "x_hi": sm["x_hi"],
                     "any_lb": sm["any_lb"], "n_layers": sm["n_layers"],
                     "pack": sm["pack"]},
            "arrays": {c: self.arrays[c][sid] for c in COMPONENTS},
            "meta_i": self.meta_i[sid],
            "meta_f": self.meta_f[sid, :, :2],
        }


def multi_site_stacked_entry(entry: dict, site: str) -> dict:
    """:meth:`MultiSiteSlabs.site_stacked_entry` over the plain-dict
    ``entry()`` form (what the runtime holds)."""
    meta = entry["meta"]
    sid = meta["sites"].index(site)
    sm = meta["site_meta"][site]
    return {
        "meta": {"w_in": sm["w_in"], "w_out": sm["w_out"],
                 "x_lo": sm["x_lo"], "x_hi": sm["x_hi"],
                 "any_lb": sm["any_lb"], "n_layers": sm["n_layers"],
                 "pack": sm["pack"]},
        "arrays": {c: entry["arrays"][c][sid] for c in COMPONENTS},
        "meta_i": entry["meta_i"][sid],
        "meta_f": entry["meta_f"][sid, :, :2],
    }


def tables_nbytes(lut_tables: dict) -> int:
    """Total device bytes of every array in a ``lut_tables`` dict — the
    upload cost of a serving-table form (the table bytes
    ``launch/serve`` prints and :meth:`~repro.serve.plans.ServingPlans.table_bytes`
    returns)."""
    leaves = jax.tree.leaves(lut_tables)
    return sum(int(a.size) * a.dtype.itemsize
               for a in leaves if hasattr(a, "dtype"))
