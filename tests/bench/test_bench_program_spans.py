"""The program's spans and named kernels, and the readers of them.

``repro.obs.span`` writes ``repro:<span>`` annotations into a profiler
trace; ``lower_ms``, ``compile_ms`` and ``idle_build_share.offline``
read those spans, ``lut_ms``, ``lut_roofline`` and ``lut_share`` read
the Pallas LUT kernels by their ``lut_`` names.  The readers are checked on hand-made reductions, on the
recorded v5e trace of a program that had neither (they find nothing
there), and on a trace of ``generate()`` taken here on the CPU with
telemetry off.
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))

from harness import model, readers, spans, trace  # noqa: E402

CALL_SPANS = ["lower.prefill", "compile.prefill", "prefill", "lower.decode",
              "compile.decode", "decode", "readback"]


def _read(name, run):
    return readers.load(BENCH, name)(run)


def _run(red, kind="offline", n_calls=2, arch=None, m=None, mix=None,
         peaks=None, site_bytes=None):
    calls = list(range(n_calls)) if kind == "offline" else None
    return readers.Run(kind=kind, arch=arch, m=m or {}, peaks=peaks or {},
                       mix=mix or {}, setup_s=0.0, calib_s=0.0,
                       site_bytes=site_bytes or {}, window_s=10.0,
                       calls=calls, trace=red)


def _op(name, s, e):
    return trace.Op(name, s, e, name)


def _reduced(host, ops=()):
    return trace.Reduced(window=(0.0, 10.0), ops=[list(ops)], host=host,
                         n_chips=1)


# Two calls: the first lowers for 1.0 + 0.5 s and compiles for 1.0 + 0.25
# s; the second lowers for 2.0 s and compiles for 1.0 s.  Chip 0 runs
# [1.5, 2.5] (overlapping the first call's prefill compile by 0.5 s) and
# [7.5, 9.0].
HOST = [
    ("bench:window", 0.0, 10.0),
    ("bench:generate", 0.0, 4.0),
    ("repro:generate", 0.0, 4.0),
    ("repro:lower.prefill", 0.0, 1.0),
    ("repro:compile.prefill", 1.0, 2.0),
    ("repro:prefill", 2.0, 2.5),
    ("repro:lower.decode", 2.5, 3.0),
    ("repro:compile.decode", 3.0, 3.25),
    ("repro:generate", 4.0, 9.0),
    ("repro:lower.prefill", 4.0, 6.0),
    ("repro:compile.prefill", 6.0, 7.0),
    ("repro:decode", 7.5, 9.0),
]
OPS = [_op("%fusion.1 = bf16[8] fusion(...)", 1.5, 2.5),
       _op('%lut_act_stacked.3 = bf16[8] custom-call(...), '
           'custom_call_target="tpu_custom_call"', 7.5, 8.0),
       _op('%lut_act_stacked.7 = bf16[8] custom-call(...), '
           'custom_call_target="tpu_custom_call"', 8.5, 9.0)]


def test_lower_and_compile_are_per_call_medians():
    run = _run(_reduced(HOST, OPS))
    assert spans.per_call_s(run.trace, spans.LOWER) == [1.5, 2.0]
    assert _read("lower_ms", run) == pytest.approx(1750.0)
    assert _read("compile_ms", run) == pytest.approx(
        1e3 * (1.25 + 1.0) / 2)


def test_a_call_with_no_lowering_counts_zero():
    host = HOST + [("repro:generate", 9.2, 9.8),
                   ("repro:prefill", 9.3, 9.5)]
    run = _run(_reduced(host, OPS), n_calls=3)
    assert spans.per_call_s(run.trace, spans.LOWER) == [1.5, 2.0, 0.0]
    assert _read("lower_ms", run) == pytest.approx(1500.0)
    assert _read("compile_ms", run) == pytest.approx(1000.0)


def test_idle_build_share_counts_overlaps_once():
    red = _reduced(HOST + [("repro:lower.decode", 0.5, 1.2)], OPS)
    # build spans' union: [0, 3.25] and [4, 7]; chip 0 is busy over
    # [1.5, 2.5] and [7.5, 9]: idle while building 2.25 + 3.0 s
    got = _read("idle_build_share.offline", _run(red))
    assert got == pytest.approx(100.0 * 5.25 / 10.0)
    assert got <= 100.0 * red.idle_share()
    assert _read("idle_share.offline", _run(red)) >= got


def test_idle_build_share_clips_to_the_window():
    host = [("repro:generate", -2.0, 3.0), ("repro:lower.prefill", -2.0, 1.0)]
    got = _read("idle_build_share.offline", _run(_reduced(host)))
    assert got == pytest.approx(10.0)


def test_lut_ms_is_named_kernel_time_per_call():
    red = _reduced(HOST, OPS + [_op('%closed_call.4 = bf16[8] custom-call('
                                    '...), custom_call_target='
                                    '"tpu_custom_call"', 0.0, 0.5)])
    assert _read("lut_ms", _run(red)) == pytest.approx(1e3 * 1.0 / 2)


def test_lut_roofline_and_share_read_the_lut_kernels():
    """Only the ``lut_`` kernels count, as in ``lut_ms``; the work is the
    architecture module's count of the MLP site at the served bytes."""
    conf = model.load_config("qwen3-0.6b", BENCH)
    arch = model.load_architecture(conf, BENCH)
    m = arch.dims(conf)
    mix = {"batch": 8, "prompt_len": 2048, "new_tokens": 16}
    peaks = {"hbm_bytes_per_s": 8.19e11}
    red = _reduced(HOST, OPS + [_op('%closed_call.4 = bf16[8] custom-call('
                                    '...), custom_call_target='
                                    '"tpu_custom_call"', 0.0, 0.5)])
    run = _run(red, arch=arch, m=m, mix=mix, peaks=peaks,
               site_bytes={"mlp": 1000.0})
    nbytes = 2 * arch.generate_lut(m, 8, 2048, 16, {"mlp": 1000.0})
    assert _read("lut_roofline", run) == pytest.approx(
        100.0 * nbytes / 8.19e11 / 1.0)
    # LUT 1.0 s of 2.5 s busy: [0, 0.5], [1.5, 2.5], [7.5, 8], [8.5, 9]
    assert _read("lut_share", run) == pytest.approx(100.0 * 1.0 / 2.5)


@pytest.mark.parametrize("name", ["lower_ms", "compile_ms",
                                  "idle_build_share.offline", "lut_ms",
                                  "lut_roofline", "lut_share"])
def test_readers_find_nothing_untraced_or_open_loop(name):
    assert _read(name, _run(None)) is None
    assert _read(name, _run(_reduced(HOST, OPS), kind="open_loop")) is None


@pytest.mark.parametrize("name", ["lower_ms", "compile_ms",
                                  "idle_build_share.offline", "lut_ms",
                                  "lut_roofline", "lut_share"])
def test_readers_find_nothing_in_a_program_without_spans(name):
    """The recorded trace predates the spans and the kernel names: each
    reader returns ``None`` there and does not raise."""
    red = trace.reduce_file(str(DATA / "trace_small.xplane.pb"))
    assert _read(name, _run(red)) is None


@pytest.fixture(scope="module")
def traced_generation():
    """Two ``generate()`` calls of a tiny dense model under the profiler,
    with no ``Telemetry`` entered."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro import obs
    from repro.calib import model_batch
    from repro.configs import get_config, smoke_config
    from repro.nn import init_params
    from repro.serve.generate import generate

    cfg = smoke_config(get_config("qwen3-0.6b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = model_batch(cfg, np.random.default_rng(0), 2, 6)
    assert obs.current() is None
    tracer = trace.Tracer(True)
    try:
        tracer.start()
        with TraceAnnotation(trace.WINDOW_SPAN):
            gens = [generate(cfg, params, batch, 3) for _ in range(2)]
        red = tracer.reduced_now()
    finally:
        tracer.cleanup()
    return gens, red


def test_generate_spans_nest_in_each_call(traced_generation):
    _, red = traced_generation
    calls = spans.calls(red)
    assert len(calls) == 2
    for lo, hi in calls:
        inside = sorted((s, e, n) for n, s, e in red.host
                        if n.startswith("repro:") and n != spans.CALL
                        and lo <= s and e <= hi)
        assert [n for _, _, n in inside] == [f"repro:{x}" for x in CALL_SPANS]
        for (_, e0, _), (s1, _, _) in zip(inside, inside[1:]):
            assert e0 <= s1          # one after the other, none overlapping
    assert not [n for n, s, e in red.host if n.startswith("repro:")
                and not any(lo <= s and e <= hi for lo, hi in calls)]


def test_span_readers_on_a_generate_trace(traced_generation):
    gens, red = traced_generation
    run = _run(red)
    compile_s = [g.prefill_compile_s + g.decode_compile_s for g in gens]
    # each compile span lies inside the timing of Generation's compile
    # seconds, and takes nearly all of it
    assert _read("compile_ms", run) <= 1e3 * max(compile_s)
    assert _read("compile_ms", run) >= 0.9 * 1e3 * min(compile_s)
    assert _read("lower_ms", run) > 0.0
    assert 0.0 < _read("idle_build_share.offline", run) <= 100.0
    assert _read("lut_ms", run) is None      # no LUT tables, no device
