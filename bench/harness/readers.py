"""Metric readers: one file per metric under ``metrics/``, found by name.

Each ``metrics/<name>.py`` defines ``read(run) -> float | None``.  A
reader that finds nothing to read returns ``None`` and the metric is
left out of the result line.  ``run`` is a :class:`Run`.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import math
from pathlib import Path


@dataclasses.dataclass
class Run:
    kind: str                   # the traffic's kind: offline | open_loop
    arch: object                # the configuration's architecture module
    m: dict                     # arch.dims of the configuration
    peaks: dict                 # harness.device.peaks of the chip
    mix: dict                   # the traffic file
    setup_s: float
    calib_s: float
    site_bytes: dict            # served tables of one hosting layer, by
                                # site (harness.system.site_bytes)
    window_s: float
    calls: list | None = None   # offline: harness.loops.Call
    loop: object = None         # open loop: harness.loops.OpenLoop
    trace: object = None        # harness.trace.Reduced of a traced run
    # the program's repro.obs counters at the window's close:
    # {metric: {labels: value}} (MetricsRegistry.snapshot)
    counters: dict = dataclasses.field(default_factory=dict)


def load(bench: Path, name: str):
    path = bench / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_all(bench: Path, specs: list, run: Run) -> dict:
    """``{name: {"value", "unit"}}`` of every spec whose reader found
    something."""
    out = {}
    for spec in specs:
        val = load(bench, spec["name"])(run)
        if val is not None:
            out[spec["name"]] = {"value": float(val), "unit": spec["unit"]}
    return out


def percentile(values, q: float) -> float:
    """Nearest-rank ``q``-quantile (0 < q <= 1); ``inf`` counts as a
    missed value."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    k = math.ceil(q * len(xs) - 1e-9) - 1
    return float(xs[max(0, min(len(xs) - 1, k))])
