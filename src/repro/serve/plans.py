"""Compressed-serving plans: network CompressReport -> decode-ready tables.

This is the layer that turns the engine's :class:`CompressReport` into
something the serving loop actually runs (ROADMAP: "wire CompressReport-
selected plans into serve/lut_act end-to-end"):

1. **Site enumeration** — every activation site of an architecture
   (per-layer MLP nonlinearity, MoE expert activation, RWKV channel-mix
   squared-ReLU) is tabulated + calibration-quantized into a
   :class:`~repro.core.TableSpec` (one per layer per site kind, the same
   granularity a per-layer-calibrated deployment would use).  Calibration
   comes in two strengths:

   * a **shared** raw sample array — every site gets the same care mask,
     so the engine's dedupe collapses the per-layer tables into one plan
     per site kind (the pre-calibration behavior);
   * a per-site :class:`~repro.calib.CalibrationSet` (captured observed-
     pattern masks, :mod:`repro.calib`) — every ``(layer, site)`` gets its
     *own* care mask and output quantization, which is the paper's
     don't-care freedom exercised per table.

2. **Dedupe + compression** — the specs go through
   :func:`~repro.core.engine.compress_network_report`, which shares
   duplicate ``(values, care)`` tables so each unique table is compressed
   once; per-site masks make tables genuinely distinct, so the hit-rate
   (``CompressReport.dedup_rate``) drops below the all-shared collapse.
3. **Materialization** — winning plans are packed into device-ready
   :class:`~repro.kernels.PlanArrays` and exported as the ``lut_tables``
   dict that :func:`repro.serve.decode_step`,
   :class:`repro.serve.ContinuousBatcher` and :mod:`repro.launch.serve`
   consume.  Per-site plans come in two execution forms
   (``plan_exec``):

   * ``"stacked"`` (default) — one padded ``(L, …)``
     :class:`~repro.serve.stacked.StackedPlanArrays` family per site
     kind; the layer stacks keep ``lax.scan`` (compact O(1)-in-depth
     HLO) and each scan step resolves its own table slab with the traced
     layer id;
   * ``"unrolled"`` — one entry per layer (``{"layers": [...]}``), which
     makes the nn layer stacks python-unroll
     (:func:`repro.nn.mlp.run_layers`) so each layer closes over its own
     arrays — O(L) compile time, kept as the reference/debug form.

   Both runtime backends — ``"gather"`` (GSPMD-shardable ``jnp.take``)
   and ``"pallas"`` (fused quantize/reconstruct/dequantize kernel) —
   bit-match under either calibration mode and either execution form
   (:func:`verify_backend_equivalence`, asserted in tests).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro import sites as site_registry
from repro.calib import CalibrationSet
from repro.configs.base import ArchConfig
from repro.core import (
    CompressConfig,
    CompressReport,
    PlanCache,
    compress_network_report,
)
from repro.core.table import TableSpec
from repro.kernels import PlanArrays
from repro.nn.lut_act import (
    LUTActivation,
    activation_table,
    lut_activation_from_plan,
)

from .generate import generate

# Engine search space for serving tables (same defaults as
# nn.lut_act.build_lut_activation).
DEFAULT_COMPRESS = dict(exiguity=250, m_candidates=(8, 16, 32, 64),
                        lb_candidates=(0, 1, 2, 3))

# Families whose layer stacks support per-layer tables
# (repro.nn.mlp.run_layers): all six — the stacked (L, …) form serves
# per-layer tables inside lax.scan, so even encdec's scanned decoder
# (the old fallback-to-site-level case) gets its own table per layer.
PER_LAYER_FAMILIES = ("dense", "moe", "vlm", "ssm", "hybrid", "encdec")


# Re-export: the base-activation mapping lives with the site registry now.
base_activation = site_registry.base_activation


def activation_sites(cfg: ArchConfig) -> list[tuple[str, str]]:
    """``(site, fn)`` kinds for one architecture config, in registry order.

    ``site`` is the table key the nn layer resolves at runtime
    (``repro.nn.mlp.site_tables``); which sites appear is decided by the
    :mod:`repro.sites` registry — the config's family, each spec's
    ``enabled`` gate, and the config's ``lut_sites`` scope selector
    (default ``"act"``: just the activation sites, the pre-registry
    behavior).
    """
    return [(spec.key, spec.fn_name(cfg))
            for spec in site_registry.active_sites(cfg)]


@dataclasses.dataclass
class SitePlan:
    """One site kind's served table(s).

    ``luts`` holds one entry (shared across every layer's site — the
    shared-calibration collapse) or one per layer (``per_layer=True``,
    per-site calibration).
    """

    site: str
    act: str
    luts: list[LUTActivation]
    n_sites: int          # how many per-layer sites this kind covers
    per_layer: bool = False
    first_layer: int = 0  # the layer of luts[0] (per-layer plans)

    @property
    def lut(self) -> LUTActivation:
        """The shared table (or layer 0's, for per-layer plans)."""
        return self.luts[0]

    @property
    def cost(self) -> int:
        """Total P-LUT cost of every distinct table served for this kind."""
        return sum(l.plan.plut_cost() for l in self.luts)

    @property
    def dontcare_frac(self) -> float:
        """Mean don't-care fraction over this kind's served tables."""
        return float(np.mean([l.dontcare_frac for l in self.luts]))

    def entry(self, form: str = "stacked", packed: bool = False) -> dict:
        """The site entry the nn layer consumes: ``{"meta", "arrays"}``
        (shared), ``{"layers": [...]}`` (per layer, unrolled execution)
        or ``{"stacked": {...}}`` (per layer, padded ``(L, …)`` stacks
        scanned with the in-loop layer id).  A site whose first hosting
        layer is not 0 (the expert site after leading dense layers) adds
        ``"first_layer"``: a layer id less that is the index into its
        per-layer tables.

        ``packed=True`` returns the bit-packed slab form (Pallas backend
        only — the gather evaluators consume raw int32).  Entries are
        memoized per ``(form, packed)``: repeated ``tables_for_model``
        calls reuse one set of device slabs instead of re-stacking and
        re-uploading (the `PlanCache` content-key idiom one level up —
        `PlanArrays.from_plan` is itself content-memoized)."""
        key = (form, packed)
        cache = self.__dict__.setdefault("_entry_cache", {})
        if key in cache:
            return cache[key]

        def one(lut: LUTActivation, pk: bool = packed) -> dict:
            pa = PlanArrays.from_plan(lut.plan, packed=pk)
            meta = lut.meta()
            if pa.pack is not None:
                meta = dict(meta, pack=pa.pack)
            return {"meta": meta, "arrays": pa.arrays}
        if not self.per_layer:
            out = one(self.lut)
        elif form == "stacked":
            out = {"stacked": self.stacked().entry(packed=packed)}
        elif form == "layers":
            out = {"layers": [one(l) for l in self.luts]}
        else:
            raise ValueError(
                f"SitePlan.entry: unknown form {form!r} "
                f"(expected 'stacked' or 'layers')")
        if self.per_layer and self.first_layer:
            out = dict(out, first_layer=self.first_layer)
        cache[key] = out
        return out

    def stacked(self):
        """This site's :class:`~repro.serve.stacked.StackedPlanArrays`
        (per-layer plans only), memoized — the packed/raw serving forms
        and the multi-site super-slab all derive from the one instance."""
        from .stacked import StackedPlanArrays

        st = self.__dict__.get("_stacked")
        if st is None:
            entries = [
                {"meta": l.meta(),
                 "arrays": PlanArrays.from_plan(l.plan).arrays}
                for l in self.luts]
            st = StackedPlanArrays.from_entries(entries)
            self.__dict__["_stacked"] = st
        return st


@dataclasses.dataclass
class ServingPlans:
    """Device-ready compressed-activation tables for one architecture."""

    arch: str
    family: str
    report: CompressReport
    sites: dict[str, SitePlan]
    backend: str = "gather"
    calib: str = "shared"        # "shared" | "per_site"
    plan_exec: str = "stacked"   # "stacked" | "unrolled" (per-layer plans)
    mesh: object | None = None   # default placement mesh (serve.sharded)

    _FORMS = {"stacked": "stacked", "unrolled": "layers"}

    def tables_for_model(self, backend: str | None = None,
                         plan_exec: str | None = None, mesh=None,
                         policy=None, packed: bool | None = None,
                         kernel: str | None = None) -> dict:
        """The ``lut_tables`` dict threaded through decode/prefill/batcher.

        ``plan_exec`` picks the per-layer execution form: ``"stacked"``
        (default — ``(L, …)`` padded stacks, layer stacks keep
        ``lax.scan``) or ``"unrolled"`` (one entry per layer, stacks
        python-unroll).  Shared plans are unaffected.

        ``packed`` selects bit-packed table slabs
        (:mod:`repro.kernels.packing`); the default packs exactly when
        the backend is ``"pallas"`` — the gather evaluators always get
        raw int32.

        ``kernel`` picks the Pallas launch strategy for per-layer stacked
        sites: ``"isolated"`` (default — one ``lut_act_stacked`` launch
        per site) or ``"fused"`` — all per-layer site families are built
        into one bit-packed ``(S, L, n)``
        :class:`~repro.serve.stacked.MultiSiteSlabs` super-slab served by
        the single-grid multi-site kernel (and statically sliced by the
        matmul-epilogue fusion under ``cfg.lut_fuse``).  ``"fused"``
        requires the Pallas backend, stacked execution, and no mesh (the
        fused hot path is the single-device serving fast path).

        With a ``mesh`` (argument, or the one the plans were built
        against), the arrays come back *placed*: committed per the
        :mod:`repro.serve.sharded` policy — small tables replicated,
        large stacked slabs layer-sharded along the data axis.
        """
        exec_ = plan_exec or self.plan_exec
        if exec_ not in self._FORMS:
            raise ValueError(
                f"tables_for_model: unknown plan_exec {exec_!r} "
                f"(expected 'stacked' or 'unrolled')")
        backend = backend or self.backend
        kernel = kernel or "isolated"
        if kernel not in ("isolated", "fused"):
            raise ValueError(
                f"tables_for_model: unknown kernel {kernel!r} "
                f"(expected 'isolated' or 'fused')")
        if packed is None:
            packed = backend == "pallas"
        if packed and backend != "pallas":
            raise ValueError(
                "tables_for_model: packed slabs are Pallas-only — the "
                "gather evaluators consume raw int32 arrays")
        mesh = mesh if mesh is not None else self.mesh
        if kernel == "fused":
            if backend != "pallas":
                raise ValueError(
                    "tables_for_model: kernel='fused' needs the Pallas "
                    "backend (the multi-site grid is a Pallas kernel)")
            if exec_ != "stacked":
                raise ValueError(
                    "tables_for_model: kernel='fused' needs "
                    "plan_exec='stacked' (the super-slab is layer-indexed "
                    "inside lax.scan)")
            if mesh:
                raise ValueError(
                    "tables_for_model: kernel='fused' is the single-device "
                    "fast path — build with mesh=False")
        form = self._FORMS[exec_]
        tables = {
            "backend": backend,
            "kernel": kernel,
            "sites": {k: sp.entry(form=form, packed=packed)
                      for k, sp in self.sites.items()},
        }
        if kernel == "fused":
            from .stacked import MultiSiteSlabs

            grouped = {k: sp.stacked() for k, sp in self.sites.items()
                       if sp.per_layer}
            if grouped:
                multi = MultiSiteSlabs.from_stacks(grouped)
                tables["multi"] = multi.entry()
                for k in grouped:
                    tables["sites"][k] = {"multi": k}
        if mesh:   # pass mesh=False to force unplaced single-device arrays
            from .sharded import place_tables

            tables, _ = place_tables(tables, mesh, policy)
        return tables

    def table_bytes(self, plan_exec: str | None = None,
                    backend: str | None = None,
                    packed: bool | None = None) -> int:
        """Device bytes of the serving tables in one execution form —
        prices the stacked padding overhead against the unrolled layout,
        and (``backend="pallas"``) the bit-packed slabs against the raw
        int32 baseline."""
        from .stacked import tables_nbytes

        return tables_nbytes(self.tables_for_model(
            backend=backend, plan_exec=plan_exec, mesh=False,
            packed=packed))

    def patched_config(self, cfg: ArchConfig) -> ArchConfig:
        return dataclasses.replace(cfg, lut_activation=True)

    @property
    def per_layer(self) -> bool:
        return any(sp.per_layer for sp in self.sites.values())

    @property
    def total_cost(self) -> int:
        """Summed P-LUT cost of every table the runtime actually holds."""
        return sum(sp.cost for sp in self.sites.values())

    def summary(self) -> str:
        parts = []
        for sp in self.sites.values():
            n_tabs = len(sp.luts)
            tabs = f"{n_tabs} per-layer tables" if sp.per_layer else (
                f"shared by {sp.n_sites} sites")
            parts.append(
                f"{sp.site}({sp.act}): {sp.cost} P-LUTs, "
                f"{sp.dontcare_frac:.0%} don't-care, {tabs}")
        return (f"{self.arch} [{self.family}] serving plans "
                f"[calib={self.calib}] — " + "; ".join(parts)
                + f" | engine: {self.report.summary()}")


@dataclasses.dataclass(frozen=True)
class _SpecMeta:
    """Per-TableSpec assembly record carried from spec building to plan
    materialization: the served site key, its scalar function, output
    quantization, whether the site is a per-layer one, and the (possibly
    site-specific) tabulation domain the LUT dequantizes over."""

    site: str
    act: str
    quant: dict
    per_layer: bool
    x_lo: float
    x_hi: float


def _shared_specs(cfg, site_specs, calibration, w_in, w_out, x_lo, x_hi):
    """Legacy shared-calibration path: tabulate + calibrate once per
    distinct ``(function, domain)`` — the per-layer specs are renamed
    views of the same table, so there is no reason to re-histogram the
    calibration array per layer just to feed tables the engine dedupe
    collapses."""
    cache: dict[tuple, tuple[TableSpec, dict]] = {}

    def tabulate(sp):
        act = sp.fn_name(cfg)
        lo, hi = sp.domain() or (x_lo, x_hi)
        key = (act, lo, hi)
        if key not in cache:
            cache[key] = activation_table(
                act, calibration, w_in=w_in, w_out=w_out,
                x_lo=lo, x_hi=hi, name=f"act_{act}")
        spec, quant = cache[key]
        return spec, quant, act, lo, hi

    specs: list[TableSpec] = []
    metas: list[_SpecMeta] = []
    for sp in site_specs:
        if sp.per_layer:
            continue
        spec, quant, act, lo, hi = tabulate(sp)
        specs.append(dataclasses.replace(spec, name=sp.key))
        metas.append(_SpecMeta(sp.key, act, quant, False, lo, hi))
    for layer in range(cfg.n_layers):
        for sp in site_specs:
            if not sp.per_layer or layer not in sp.layers(cfg):
                continue
            spec, quant, act, lo, hi = tabulate(sp)
            specs.append(dataclasses.replace(spec,
                                             name=f"L{layer}/{sp.key}"))
            metas.append(_SpecMeta(sp.key, act, quant, True, lo, hi))
    return specs, metas


def _per_site_specs(cfg, site_specs, calib: CalibrationSet, w_in, w_out,
                    x_lo, x_hi):
    """Per-site calibration path: one care mask (and output quantization)
    per ``(layer, site)`` from the captured CalibrationSet; falls back to
    the site-kind mask where no per-layer key exists (a layer-agnostic
    capture, e.g. an old artifact).  Network-global sites
    (``per_layer=False`` in the registry, e.g. the logit softcap) get one
    spec total under their bare key.  ``w_out`` may be a per-site-kind
    dict (the tuned-plan width override) — a site's layers must share one
    output width so their plans can stack."""
    specs: list[TableSpec] = []
    metas: list[_SpecMeta] = []
    layered = cfg.family in PER_LAYER_FAMILIES

    def add(sp, layer):
        lyr = layer if (layered and sp.per_layer) else None
        care = calib.mask_for(sp.key, lyr)
        if care is None:
            raise ValueError(
                f"build_serving_plans: calibration has no mask for "
                f"site {sp.key!r} (layer {lyr}); captured sites: "
                f"{calib.sites()}")
        act = sp.fn_name(cfg)
        lo, hi = sp.domain() or (x_lo, x_hi)
        w_out_site = w_out[sp.key] if isinstance(w_out, dict) else w_out
        name = sp.key if layer is None else f"L{layer}/{sp.key}"
        spec, quant = activation_table(
            act, care=care, w_in=w_in, w_out=w_out_site, x_lo=lo,
            x_hi=hi, name=name)
        specs.append(spec)
        metas.append(_SpecMeta(sp.key, act, quant, sp.per_layer, lo, hi))

    for sp in site_specs:
        if not sp.per_layer:
            add(sp, None)
    for layer in range(cfg.n_layers):
        for sp in site_specs:
            if sp.per_layer and layer in sp.layers(cfg):
                add(sp, layer)
    return specs, metas


def build_serving_plans(
    cfg: ArchConfig,
    calibration: np.ndarray | CalibrationSet,
    *,
    w_in: int | None = None,
    w_out: int | dict | None = None,
    x_lo: float = -8.0,
    x_hi: float = 8.0,
    compress_cfg: CompressConfig | None = None,
    workers: int | None = None,
    backend: str = "gather",
    plan_exec: str = "stacked",
    plan_cache: PlanCache | None = None,
    mesh=None,
    verbose: bool = False,
) -> ServingPlans:
    """Compress every activation site of ``cfg`` into serving tables.

    One :class:`TableSpec` is built per (layer, site kind).  With a shared
    calibration sample array the per-layer tables are identical and the
    engine's dedupe compresses each unique table once
    (``report.dedup_rate`` is (L-1)/L per site kind).  With a per-site
    :class:`~repro.calib.CalibrationSet` every site carries its own
    observed-pattern care mask, dedupe only merges genuinely identical
    ``(values, care)`` pairs, and the runtime serves one table per layer —
    by default as stacked ``(L, …)`` arrays the layer scans index in
    place (``plan_exec="stacked"``); ``plan_exec="unrolled"`` keeps the
    python-unrolled reference form.

    ``w_out`` may be a dict mapping registered site keys
    (:func:`repro.sites.all_sites`) to per-site output widths — the
    tuned-plan width override (:mod:`repro.tune`) — on the per-site
    calibration path only.  Keys that are not registered site kinds raise
    ``ValueError`` rather than being silently ignored.
    ``plan_cache`` (a :class:`~repro.core.PlanCache`) shares compression
    results across repeated builds (the autotune sweep).  ``mesh`` binds
    the plans to a placement mesh: every ``tables_for_model`` call then
    returns committed, policy-placed arrays (:mod:`repro.serve.sharded`).
    """
    per_site = isinstance(calibration, CalibrationSet)
    if per_site:
        # Masks are bound to the quantizer they were captured under.
        if calibration.w_in is None:
            raise ValueError(
                "build_serving_plans: CalibrationSet has no w_in — "
                "activation serving needs masks captured on the LUT input "
                "grid (repro.calib.capture_model)")
        w_in = calibration.w_in
        x_lo, x_hi = calibration.x_lo, calibration.x_hi
    else:
        w_in = w_in or cfg.lut_act_bits_in
    site_specs = site_registry.active_sites(cfg)
    if isinstance(w_out, dict):
        if not per_site:
            raise ValueError(
                "build_serving_plans: per-site w_out overrides need a "
                "per-site CalibrationSet (shared calibration serves one "
                "table per activation kind)")
        missing = {sp.key for sp in site_specs} - set(w_out)
        if missing:
            raise ValueError(
                f"build_serving_plans: per-site w_out has no entry for "
                f"site kind(s) {sorted(missing)} (got {sorted(w_out)})")
        registered = {sp.key for sp in site_registry.all_sites()}
        unknown = set(w_out) - registered
        if unknown:
            raise ValueError(
                f"build_serving_plans: per-site w_out has unknown site "
                f"kind(s) {sorted(unknown)}; registered kinds: "
                f"{sorted(registered)}")
    else:
        w_out = w_out or cfg.lut_act_bits_out
    if per_site:
        specs, metas = _per_site_specs(cfg, site_specs, calibration, w_in,
                                       w_out, x_lo, x_hi)
    else:
        specs, metas = _shared_specs(cfg, site_specs, calibration, w_in,
                                     w_out, x_lo, x_hi)
    ccfg = compress_cfg or CompressConfig(**DEFAULT_COMPRESS)
    report = compress_network_report(specs, ccfg, workers=workers,
                                     verbose=verbose, cache=plan_cache)
    layered = per_site and cfg.family in PER_LAYER_FAMILIES
    site_plans: dict[str, SitePlan] = {}
    for meta, spec, plan in zip(metas, specs, report.plans):
        site = meta.site
        site_layered = layered and meta.per_layer
        lut = None
        if site_layered or site not in site_plans:
            lut = lut_activation_from_plan(
                plan, spec, meta.quant, x_lo=meta.x_lo, x_hi=meta.x_hi,
                exiguity=ccfg.exiguity)
        if site in site_plans:
            site_plans[site].n_sites += 1
            if lut is not None:
                site_plans[site].luts.append(lut)
            continue
        site_plans[site] = SitePlan(
            site=site, act=meta.act, luts=[lut], n_sites=1,
            per_layer=site_layered,
            first_layer=(site_registry.site_spec(site).layers(cfg).start
                         if site_layered else 0))
    return ServingPlans(arch=cfg.name, family=cfg.family, report=report,
                        sites=site_plans, backend=backend,
                        plan_exec=plan_exec, mesh=mesh,
                        calib="per_site" if per_site else "shared")


def verify_backend_equivalence(
    cfg: ArchConfig,
    params,
    plans: ServingPlans,
    prompt: np.ndarray | dict,   # (B, T) int32 tokens, or a full batch dict
    n_new: int,
    max_seq: int | None = None,
    plan_exec: str | None = None,
    mesh=None,
    table_overrides: dict | None = None,
) -> list[list[int]]:
    """Decode ``n_new`` greedy tokens with the gather backend and the fused
    Pallas backend and assert they bit-match token-for-token.

    Both backends run identical integer reconstruction math and the same
    float dequantization expression — per layer, when the plans are
    per-site, in whichever execution form ``plans.plan_exec`` (or the
    ``plan_exec`` override) selects — so the served logits, and therefore
    every sampled token, must agree exactly.  ``prompt`` may be a full
    batch dict for families whose prefill needs extra inputs (vlm
    patches, encdec frames).

    With ``mesh``, each backend *additionally* runs through the sharded
    serving path (:class:`~repro.serve.sharded.ShardedServe`, policy-
    placed tables) and its greedy tokens are asserted **bit-identical**
    to that backend's single-device reference — comparing against the
    unsharded program (not merely the two sharded backends against each
    other) is what catches a mis-replicated table slab.  Per-step logits
    are also asserted bit-identical whenever the data axis leaves at
    least two examples per device; a one-example shard computes at
    different array shapes, where XLA may choose a scalar instead of a
    vectorized transcendental code path (an ulp-level reassociation the
    serving layer cannot forbid), so those cells assert a tight absolute
    tolerance instead — tokens stay hard-asserted everywhere.
    ``table_overrides`` maps a backend name to a pre-placed
    ``lut_tables`` dict used for its sharded run only (the mesh suite's
    deliberate-corruption negative test).

    Returns the (B, n_new) token lists on success; raises
    ``AssertionError`` on the first divergence.
    """
    cfg = plans.patched_config(cfg)
    if isinstance(prompt, dict):
        batch = {k: jnp.asarray(v) for k, v in prompt.items()}
    else:
        batch = {"tokens": jnp.asarray(prompt, jnp.int32)}
    b = batch["tokens"].shape[0]
    outs: dict[str, list[list[int]]] = {}
    for backend in ("gather", "pallas"):
        tables = plans.tables_for_model(backend=backend,
                                        plan_exec=plan_exec, mesh=False)
        ref = generate(cfg, params, batch, n_new, lut_tables=tables,
                       max_seq=max_seq, all_logits=True)
        outs[backend] = ref.tokens.tolist()
        if mesh is None:
            continue
        from .sharded import ShardedServe

        s_tables = (table_overrides or {}).get(backend)
        if s_tables is None:
            s_tables = plans.tables_for_model(backend=backend,
                                              plan_exec=plan_exec,
                                              mesh=mesh)
        serve = ShardedServe(cfg, mesh, s_tables)
        got = generate(cfg, serve.place_params(params),
                       serve.place_batch(batch), n_new, serve=serve,
                       max_seq=max_seq, all_logits=True)
        assert np.array_equal(got.tokens, ref.tokens), (
            f"sharded {backend} decode diverges from the single-device "
            f"reference: {got.tokens.tolist()} != {ref.tokens.tolist()}")
        n_data = 1
        for ax in ("pod", "data"):
            n_data *= int(mesh.shape.get(ax, 1))
        bits = n_data == 1 or (b % n_data == 0 and b // n_data >= 2)
        for i, (r_lg, s_lg) in enumerate(zip(ref.logits, got.logits)):
            if bits:
                assert np.array_equal(r_lg, s_lg), (
                    f"sharded {backend} logits not bit-identical to the "
                    f"single-device reference at step {i} "
                    f"(max |diff| {np.max(np.abs(r_lg - s_lg))})")
            else:
                assert np.allclose(r_lg, s_lg, rtol=0, atol=1e-4), (
                    f"sharded {backend} logits diverge from the "
                    f"single-device reference at step {i} beyond ulp "
                    f"tolerance (max |diff| {np.max(np.abs(r_lg - s_lg))})")
    # Fused hot path: matmul-epilogue LUT fusion (cfg.lut_fuse) over the
    # multi-site super-slab (kernel="fused", stacked exec) or the isolated
    # packed entries (unrolled exec) — asserted token-for-token
    # bit-identical to the gather reference like any other backend.
    exec_ = plan_exec or plans.plan_exec
    fused_kernel = "fused" if exec_ == "stacked" else "isolated"
    f_tables = plans.tables_for_model(backend="pallas", plan_exec=plan_exec,
                                      mesh=False, kernel=fused_kernel)
    f_cfg = dataclasses.replace(cfg, lut_fuse=True)
    f_out = generate(f_cfg, params, batch, n_new, lut_tables=f_tables,
                     max_seq=max_seq).tokens.tolist()
    for r, (a, bb) in enumerate(zip(outs["gather"], outs["pallas"])):
        assert a == bb, (
            f"backend divergence on request {r}: gather={a} pallas={bb}")
    for r, (a, bb) in enumerate(zip(outs["gather"], f_out)):
        assert a == bb, (
            f"fused-kernel divergence on request {r}: gather={a} "
            f"fused={bb}")
    return outs["gather"]
