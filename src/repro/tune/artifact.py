"""Tuned-plan artifacts: the autotuner's decision, bit-exact on disk.

A :class:`TunedPlan` is everything ``launch/serve --tuned-plan`` needs to
serve the tuner's selection *without recapture or recompression*: the
per-layer plan arrays themselves (int32, saved exactly), their
quantization metas, the chosen per-site knobs, the measured Pareto
frontier and the parity metrics behind the selection.  The serving forms
(stacked / unrolled, gather / pallas) are rebuilt from the stored
entries, so a loaded artifact decodes token-identically to the in-process
tuning run (asserted in ``tests/test_tune.py`` and by ``launch/tune``
itself).

One compressed ``.npz`` holds a JSON header (knobs, frontier, metrics,
per-entry metas — floats round-trip exactly through JSON's double
representation) plus one array entry per ``plan:{site}:{layer}:{field}``.
Writes are atomic and the payload is content-checksummed on save and
verified on load (:mod:`repro.ioutil`): a truncated or bit-flipped
artifact raises a clear :class:`~repro.ioutil.ArtifactError` naming the
file, instead of deserializing garbage tables into a running server.
"""
from __future__ import annotations

import dataclasses
import os

import numpy as np

from repro.configs.base import ArchConfig
from repro.ioutil import ArtifactError, load_checked_npz, save_checked_npz
from repro.serve.stacked import COMPONENTS as _FIELDS

_FORMAT = "repro-tuned-plan/v1"
_PLAN = "plan:"


@dataclasses.dataclass
class TunedPlan:
    """Loaded (or about-to-be-saved) tuned serving plan."""

    arch: str                       # cfg.name the plans were tuned for
    family: str
    n_layers: int
    backend: str                    # tuner's default backend
    plan_exec: str                  # tuner's default execution form
    sites: dict[str, list[dict]]    # site kind -> per-layer entries
    per_layer: dict[str, bool]      # site kind -> one entry per layer?
    knobs: dict                     # chosen knobs per site kind (+ widths)
    frontier: list[dict]            # measured Pareto frontier rows
    metrics: dict                   # parity metrics of the selection
    meta: dict = dataclasses.field(default_factory=dict)

    def tables_for_model(self, backend: str | None = None,
                         plan_exec: str | None = None,
                         packed: bool | None = None,
                         kernel: str | None = None) -> dict:
        """Rebuild the ``lut_tables`` dict straight from the stored
        entries — no capture, no engine.  ``packed``/``kernel`` mirror
        :meth:`repro.serve.plans.ServingPlans.tables_for_model`: packed
        bit-packed slabs default on for the Pallas backend, and
        ``kernel="fused"`` builds the per-layer sites into one multi-site
        super-slab (Pallas + stacked execution only)."""
        exec_ = plan_exec or self.plan_exec
        if exec_ not in ("stacked", "unrolled"):
            raise ValueError(
                f"TunedPlan.tables_for_model: unknown plan_exec {exec_!r} "
                f"(expected 'stacked' or 'unrolled')")
        backend = backend or self.backend
        kernel = kernel or "isolated"
        if packed is None:
            packed = backend == "pallas"
        if packed and backend != "pallas":
            raise ValueError(
                "TunedPlan.tables_for_model: packed slabs are Pallas-only")
        if kernel == "fused" and (backend != "pallas"
                                  or exec_ != "stacked"):
            raise ValueError(
                "TunedPlan.tables_for_model: kernel='fused' needs the "
                "Pallas backend and plan_exec='stacked'")

        def one(e: dict) -> dict:
            if not packed:
                return dict(e)
            from repro.kernels.packing import pack_component_dict

            arrays, pack = pack_component_dict(e["arrays"])
            return {"meta": dict(e["meta"], pack=pack), "arrays": arrays}

        from repro.serve.stacked import StackedPlanArrays

        sites: dict[str, dict] = {}
        stacks: dict[str, StackedPlanArrays] = {}
        for site, entries in self.sites.items():
            if not self.per_layer.get(site, True):
                sites[site] = one(entries[0])
            elif exec_ == "stacked":
                st = StackedPlanArrays.from_entries(entries)
                stacks[site] = st
                sites[site] = {"stacked": st.entry(packed=packed)}
            else:
                sites[site] = {"layers": [one(e) for e in entries]}
        tables = {"backend": backend, "kernel": kernel, "sites": sites}
        if kernel == "fused" and stacks:
            from repro.serve.stacked import MultiSiteSlabs

            tables["multi"] = MultiSiteSlabs.from_stacks(stacks).entry()
            for site in stacks:
                tables["sites"][site] = {"multi": site}
        return tables

    def patched_config(self, cfg: ArchConfig) -> ArchConfig:
        if cfg.name != self.arch:
            raise ValueError(
                f"TunedPlan: artifact was tuned for arch {self.arch!r} "
                f"but the launcher config is {cfg.name!r} — tuned plans "
                f"are bound to the model they were measured on")
        if cfg.n_layers != self.n_layers:
            raise ValueError(
                f"TunedPlan: artifact has {self.n_layers} layers per "
                f"site, config expects {cfg.n_layers}")
        return dataclasses.replace(cfg, lut_activation=True)

    @property
    def total_cost(self) -> int:
        return int(self.meta.get("cost", 0))

    def summary(self) -> str:
        m = self.metrics or {}
        sites = ", ".join(
            f"{k}({len(v)} tables)" for k, v in sorted(self.sites.items()))
        return (f"tuned plan [{self.arch}] {sites}; "
                f"cost {self.meta.get('cost')} P-LUTs "
                f"(default {self.meta.get('default_cost')}); "
                f"top-1 drop {m.get('top1_drop', float('nan')):.4f} "
                f"(budget {self.meta.get('budget')}); "
                f"{len(self.frontier)} frontier points")


def tuned_plan_from_outcome(cfg: ArchConfig, outcome,
                            extra_meta: dict | None = None) -> TunedPlan:
    """Freeze a :class:`~repro.tune.sweep.TuneOutcome` into an artifact."""
    from repro.kernels import PlanArrays

    sites: dict[str, list[dict]] = {}
    per_layer: dict[str, bool] = {}
    for kind, sp in outcome.plans.sites.items():
        entries = []
        for lut in sp.luts:
            pa = PlanArrays.from_plan(lut.plan)
            entries.append({
                "meta": dict(lut.meta()),
                "arrays": {f: np.asarray(pa.arrays[f], dtype=np.int32)
                           for f in _FIELDS},
            })
        sites[kind] = entries
        per_layer[kind] = sp.per_layer
    knobs = {k: {**p.to_dict(), "label": p.label()}
             for k, p in outcome.assignment.items()}
    meta = {
        "budget": outcome.budget,
        "budget_met": outcome.budget_met,
        "cost": outcome.cost,
        "default_cost": outcome.default.cost if outcome.default.ok else None,
        "default_table_bytes": (outcome.default.table_bytes
                                if outcome.default.ok else None),
        "table_bytes": outcome.plans.table_bytes(),
        "greedy_evals": outcome.greedy.get("evals", 0),
        **(extra_meta or {}),
    }
    return TunedPlan(
        arch=cfg.name, family=cfg.family, n_layers=cfg.n_layers,
        backend=outcome.plans.backend, plan_exec=outcome.plans.plan_exec,
        sites=sites, per_layer=per_layer, knobs=knobs,
        frontier=[r.to_dict() for r in outcome.frontier],
        metrics=outcome.metrics.to_dict(), meta=meta)


def tuned_plan_from_serving(cfg: ArchConfig, plans,
                            extra_meta: dict | None = None) -> TunedPlan:
    """Freeze built :class:`~repro.serve.plans.ServingPlans` into an
    artifact without an autotune sweep — the ``launch/serve --save-plan``
    path.  The stored entries are the exact device arrays the plans
    serve, so serving the artifact is token-identical to the serving
    that produced it."""
    from repro.kernels import PlanArrays

    sites: dict[str, list[dict]] = {}
    per_layer: dict[str, bool] = {}
    for kind, sp in plans.sites.items():
        entries = []
        for lut in sp.luts:
            pa = PlanArrays.from_plan(lut.plan)
            entries.append({
                "meta": dict(lut.meta()),
                "arrays": {f: np.asarray(pa.arrays[f], dtype=np.int32)
                           for f in _FIELDS},
            })
        sites[kind] = entries
        per_layer[kind] = sp.per_layer
    meta = {"cost": plans.total_cost, "source": "serving_plans",
            "calib": plans.calib, **(extra_meta or {})}
    return TunedPlan(
        arch=cfg.name, family=cfg.family, n_layers=cfg.n_layers,
        backend=plans.backend, plan_exec=plans.plan_exec,
        sites=sites, per_layer=per_layer, knobs={}, frontier=[],
        metrics={}, meta=meta)


def save_tuned_plan(path: str, tp: TunedPlan) -> str:
    """Write ``tp`` to ``path`` (``.npz`` appended if missing)."""
    header = {
        "format": _FORMAT,
        "arch": tp.arch,
        "family": tp.family,
        "n_layers": tp.n_layers,
        "backend": tp.backend,
        "plan_exec": tp.plan_exec,
        "per_layer": tp.per_layer,
        "knobs": tp.knobs,
        "frontier": tp.frontier,
        "metrics": tp.metrics,
        "meta": tp.meta,
        "site_metas": {site: [e["meta"] for e in entries]
                       for site, entries in tp.sites.items()},
    }
    payload: dict[str, np.ndarray] = {}
    for site, entries in tp.sites.items():
        for layer, e in enumerate(entries):
            for field in _FIELDS:
                payload[f"{_PLAN}{site}:{layer}:{field}"] = np.asarray(
                    e["arrays"][field], dtype=np.int32)
    return save_checked_npz(path, header, payload, kind="tuned-plan")


def load_tuned_plan(path: str) -> TunedPlan:
    """Read a :func:`save_tuned_plan` artifact back, bit-exactly."""
    if not path.endswith(".npz") and not os.path.exists(path):
        path = path + ".npz"
    header, data = load_checked_npz(path, kind="tuned-plan")
    if header.get("format") != _FORMAT:
        raise ArtifactError(
            f"{path}: unknown tuned-plan format "
            f"{header.get('format')!r} (expected {_FORMAT!r})")
    sites: dict[str, list[dict]] = {}
    for site, metas in header["site_metas"].items():
        entries = []
        for layer, meta in enumerate(metas):
            entries.append({
                "meta": dict(meta),
                "arrays": {
                    f: np.asarray(data[f"{_PLAN}{site}:{layer}:{f}"],
                                  dtype=np.int32)
                    for f in _FIELDS},
            })
        sites[site] = entries
    return TunedPlan(
        arch=header["arch"], family=header["family"],
        n_layers=header["n_layers"], backend=header["backend"],
        plan_exec=header["plan_exec"], sites=sites,
        per_layer=header.get("per_layer", {}),
        knobs=header.get("knobs", {}),
        frontier=header.get("frontier", []),
        metrics=header.get("metrics", {}),
        meta=header.get("meta", {}))
