"""``generate()`` keeps its compiled programs across calls.

A second call with the same config, tables object and shapes runs the
programs the first one built: nothing is traced again, and the served
tokens and logits are the first call's, bit for bit.  Anything that
would trace a different program (new tables, another ``max_seq`` or
shape, the int8 cache, a replaced step function) builds anew.  Smoke
width qwen3, gather tables, on the CPU.
"""
from __future__ import annotations

import gc
import importlib
import weakref

import jax
import jax.monitoring
import numpy as np
import pytest

from repro import obs
from repro.calib import (calibration_from_capture, capture_model,
                         model_batch, synthetic_batches)
from repro.configs import get_config, smoke_config
from repro.nn import init_params
from repro.serve import FaultInjector, build_serving_plans

gen = importlib.import_module("repro.serve.generate")

NEW = 3
CALL_SPANS = ["lower.prefill", "compile.prefill", "prefill", "lower.decode",
              "compile.decode", "decode", "readback"]
_traced = [0]


def _on_event(name, *_, **__):
    if name == "/jax/core/compile/jaxpr_trace_duration":
        _traced[0] += 1


@pytest.fixture(scope="module")
def traces():
    """Number of jaxprs traced in this process since the module began
    (JAX's listeners cannot be removed: this one is registered once)."""
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    return lambda: _traced[0]


@pytest.fixture(scope="module")
def served():
    cfg = smoke_config(get_config("qwen3-0.6b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    cap = capture_model(params, cfg, synthetic_batches(
        cfg, 1, batch_size=2, seq_len=8, seed=1), w_in=8)
    plans = build_serving_plans(cfg, calibration_from_capture(cap), w_out=8)
    batch = model_batch(cfg, np.random.default_rng(0), 2, 6)
    return plans.patched_config(cfg), params, plans, batch


def _outcomes(tel) -> dict:
    c = tel.registry.counter("generate_programs_total")
    return {(p, o): c.value(program=p, outcome=o)
            for p in ("prefill", "decode", "replay")
            for o in ("hit", "miss")}


def _assert_same(a, b):
    np.testing.assert_array_equal(a.tokens, b.tokens)
    assert len(a.logits) == len(b.logits)
    for x, y in zip(a.logits, b.logits):
        np.testing.assert_array_equal(x, y)


def test_second_call_traces_nothing_and_serves_the_same(served, traces):
    cfg, params, plans, batch = served
    tables = plans.tables_for_model(backend="gather")
    gen.clear_programs()
    first = gen.generate(cfg, params, batch, NEW, lut_tables=tables,
                         all_logits=True)
    n = traces()
    with obs.Telemetry() as tel:
        second = gen.generate(cfg, params, batch, NEW, lut_tables=tables,
                              all_logits=True)
    assert traces() == n
    out = _outcomes(tel)
    assert out[("prefill", "hit")] == out[("decode", "hit")] == 1
    assert sum(v for (_, o), v in out.items() if o == "miss") == 0
    assert second.decode_program is first.decode_program
    _assert_same(first, second)


# each case: the second call's keyword changes, and which programs miss
MISSES = {
    "new_tables_equal_content": (
        lambda plans, batch: {"lut_tables": plans.tables_for_model(
            backend="gather")}, {"prefill", "decode"}),
    "max_seq": (lambda plans, batch: {"max_seq": 6 + NEW + 4},
                {"prefill", "decode"}),
    "batch_shape": (lambda plans, batch: {
        "batch": {k: v[:, :5] for k, v in batch.items()}},
        {"prefill", "decode"}),
    "kv_int8": (lambda plans, batch: {"kv_int8": True}, {"replay", "decode"}),
    "decode_step_replaced": (lambda plans, batch: {}, {"decode"}),
}


@pytest.mark.parametrize("case", list(MISSES))
def test_a_different_program_is_built_anew(served, traces, monkeypatch,
                                           case):
    cfg, params, plans, batch = served
    tables = plans.tables_for_model(backend="gather")
    change, missed = MISSES[case]
    kw = {"batch": batch, "lut_tables": tables, **change(plans, batch)}
    gen.clear_programs()
    gen.generate(cfg, params, batch, NEW, lut_tables=tables)
    if case == "decode_step_replaced":
        step = gen.decode_step
        monkeypatch.setattr(gen, "decode_step", lambda *a, **k: step(*a, **k))
    n = traces()
    with obs.Telemetry() as tel:
        got = gen.generate(cfg, params, kw.pop("batch"), NEW, **kw)
    assert traces() > n
    out = _outcomes(tel)
    assert {p for (p, o), v in out.items() if o == "miss" and v} == missed
    assert {p for (p, o), v in out.items() if o == "hit" and v} == (
        {"prefill", "decode"} - missed)
    if case in ("new_tables_equal_content", "decode_step_replaced"):
        want = gen.generate(cfg, params, batch, NEW, lut_tables=tables)
        _assert_same(got, want)


def test_kept_programs_stay_within_the_bound(served):
    cfg, params, _, batch = served
    gen.clear_programs()
    calls = gen.MAX_PROGRAMS // 2 + 1
    for i in range(calls):
        gen.generate(cfg, params, batch, NEW, max_seq=6 + NEW + i)
        assert len(gen._PROGRAMS) <= gen.MAX_PROGRAMS
    assert len(gen._PROGRAMS) == gen.MAX_PROGRAMS
    with obs.Telemetry() as tel:
        # the newest call's programs are kept, the oldest call's are not
        gen.generate(cfg, params, batch, NEW, max_seq=6 + NEW + calls - 1)
        gen.generate(cfg, params, batch, NEW, max_seq=6 + NEW)
    out = _outcomes(tel)
    assert out[("decode", "hit")] == 1 and out[("decode", "miss")] == 1


def test_cleared_programs_are_traced_again(served, traces):
    cfg, params, _, batch = served
    gen.clear_programs()
    gen.generate(cfg, params, batch, NEW)
    n = traces()
    gen.generate(cfg, params, batch, NEW)
    assert traces() == n
    gen.clear_programs()
    assert len(gen._PROGRAMS) == 0
    gen.generate(cfg, params, batch, NEW)
    assert traces() > n


def test_nothing_is_kept_under_a_trace_time_hook(served):
    """A fault injector, like a capture or a drift monitor, is called
    from inside the traced program: such a program is not kept."""
    cfg, params, _, batch = served
    gen.clear_programs()
    with FaultInjector(), obs.Telemetry() as tel:
        for _ in range(2):
            gen.generate(cfg, params, batch, NEW)
    assert len(gen._PROGRAMS) == 0
    out = _outcomes(tel)
    assert out[("prefill", "miss")] == out[("decode", "miss")] == 2


def test_kept_programs_hold_no_weights(served):
    cfg, _, _, batch = served
    gen.clear_programs()
    params = init_params(cfg, jax.random.PRNGKey(1))
    for _ in range(2):
        gen.generate(cfg, params, batch, NEW)
    leaf = weakref.ref(jax.tree.leaves(params)[0])
    del params
    gc.collect()
    assert len(gen._PROGRAMS) == 2
    assert leaf() is None


def test_spans_of_a_kept_program_are_marked_cached(served):
    cfg, params, plans, batch = served
    tables = plans.tables_for_model(backend="gather")
    gen.clear_programs()
    with obs.Telemetry(events=obs.EventLog()) as tel:
        for _ in range(2):
            gen.generate(cfg, params, batch, NEW, lut_tables=tables)
    begins = [r for r in tel.events.records if r["event"] == "span_begin"]
    calls = [r for r in begins if r["name"] == "generate"]
    assert len(calls) == 2
    for i, call in enumerate(calls):
        inner = [r for r in begins if r.get("parent") == call["span_id"]]
        assert [r["name"] for r in inner] == CALL_SPANS
        for r in inner:
            if r["name"].startswith(("lower.", "compile.")):
                assert r.get("cached", False) is (i == 1), r
