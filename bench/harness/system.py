"""The system under test: what the benchmark calls in the program.

Everything the program is asked for goes through here: calibration
capture plus ReducedLUT compression, the offline entry
``repro.serve.generate.generate``, and ``ContinuousBatcher``.  The
benchmark's own spans wrap each call (``jax.profiler.TraceAnnotation``,
named ``bench:<layer call>``) so a trace can say what the host was doing
in each device gap.
"""
from __future__ import annotations

import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation


def span(name: str):
    return TraceAnnotation(f"bench:{name}")


def _nbytes(arrays) -> int:
    return sum(int(np.asarray(a).nbytes) for a in jax.tree.leaves(arrays))


def site_bytes(tables: dict) -> dict:
    """``{site: served table bytes per hosting layer}`` of every site in
    ``tables["sites"]``: a stacked entry's arrays over its ``n_layers``,
    an unrolled entry's over its layers, a shared table whole."""
    out = {}
    for key, entry in tables["sites"].items():
        if "stacked" in entry:
            st = entry["stacked"]
            out[key] = _nbytes(st["arrays"]) / st["meta"]["n_layers"]
        elif "layers" in entry:
            layers = entry["layers"]
            out[key] = sum(_nbytes(x["arrays"]) for x in layers) / len(layers)
        elif "arrays" in entry:
            out[key] = float(_nbytes(entry["arrays"]))
        else:
            raise ValueError(f"site {key}: no table bytes in an entry of "
                             f"keys {sorted(entry)}")
    return out


def calibrate(cfg, params, batch: dict, *, backend: str = "pallas",
              plan_exec: str = "stacked"):
    """Capture one calibration batch through the exact model and compress
    every layer's table at each site the configuration hosts.  Returns
    ``(lut_cfg, tables, site_bytes, seconds)``; ``site_bytes`` maps each
    served site to its tables' size for one hosting layer
    (:func:`site_bytes`)."""
    from repro.calib import (ActivationCapture, calibration_from_capture,
                             capture_model)
    from repro.serve import build_serving_plans

    t0 = time.perf_counter()
    with span("calibrate"):
        cap = ActivationCapture(w_in=cfg.lut_act_bits_in)
        capture_model(params, cfg, [batch], capture=cap)
        plans = build_serving_plans(cfg, calibration_from_capture(cap),
                                    backend="gather", plan_exec=plan_exec,
                                    workers=1)
        tables = plans.tables_for_model(backend=backend)
        jax.block_until_ready(tables)
    secs = time.perf_counter() - t0
    return plans.patched_config(cfg), tables, site_bytes(tables), secs


def generate(lut_cfg, params, tables, batch: dict, new_tokens: int):
    """One offline call through the program's entry point."""
    from repro.serve.generate import generate as program_generate

    with span("generate"):
        return program_generate(lut_cfg, params, batch, new_tokens,
                                lut_tables=tables)


class Batcher:
    """``ContinuousBatcher`` with the benchmark's spans around its calls
    into the model step, and a count of decode calls."""

    def __init__(self, lut_cfg, params, tables, *, slots: int, max_seq: int,
                 prefill: str = "replay"):
        from repro.serve import ContinuousBatcher

        self.b = ContinuousBatcher(lut_cfg, params, slots, max_seq,
                                   eos_token=-1, lut_tables=tables,
                                   prefill=prefill)
        self.decode_calls = 0
        self.replays = 0
        step, replay = self.b._step, self.b._replay

        def counted_step(*a):
            self.decode_calls += 1
            with span("decode_call"):
                return step(*a)

        def counted_replay(*a):
            self.replays += 1
            with span("replay"):
                return replay(*a)

        self.b._step, self.b._replay = counted_step, counted_replay

    def submit(self, arrival) -> object:
        from repro.serve import Request

        req = Request(rid=arrival.rid, prompt=list(arrival.prompt),
                      max_new=arrival.max_new)
        with span("submit"):
            self.b.submit(req)
        return req

    def tick(self) -> None:
        with span("tick"):
            self.b.step()

    @property
    def busy(self) -> bool:
        return bool(self.b.queue) or self.b.n_active > 0

    def active(self):
        return [s.req for s in self.b.slots if s.req is not None]
