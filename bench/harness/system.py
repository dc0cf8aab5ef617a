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


def calibrate(cfg, params, batch: dict, *, backend: str = "pallas",
              plan_exec: str = "stacked"):
    """Capture one calibration batch through the exact model and compress
    every layer's MLP activation table.  Returns ``(lut_cfg, tables,
    table_bytes, seconds)``; ``table_bytes`` is the served MLP tables'
    size for one layer."""
    from repro import sites
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
    site = tables["sites"][sites.MLP]
    entry = site.get("stacked", site)
    nbytes = sum(int(np.asarray(a).nbytes)
                 for a in jax.tree.leaves(entry.get("arrays", entry)))
    return plans.patched_config(cfg), tables, nbytes / cfg.n_layers, secs


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
