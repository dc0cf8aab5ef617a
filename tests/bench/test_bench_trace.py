"""The reduction from a profiler trace to busy time, idle share, kernel
time and named idle gaps: on hand-made intervals, and on a small trace
recorded on a TPU v5e by ``bench/tools/inspect_trace.py`` (two greedy
``generate`` calls of qwen3-0.6b at batch 1, prompt 32, 3 new tokens,
under ``bench:`` spans; both calls compiled, so the longest idle gaps are
the TPU compiler's)."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
DATA = Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(BENCH))

from harness import readers, trace  # noqa: E402

RECORDED = DATA / "trace_small.xplane.pb"


def _op(name, s, e):
    return trace.Op(name, s, e, name)


def _reduced():
    ops = [_op("fusion.1", 0.0, 1.0), _op("lut_act_kernel", 0.5, 2.0),
           _op("lut_act_kernel", 3.0, 4.0)]
    host = [("bench:tick", 1.9, 3.2), ("bench:decode_call", 2.1, 2.9),
            ("PjitFunction(step)", 2.2, 2.8), ("bench:submit", 4.2, 4.9)]
    return trace.Reduced(window=(0.0, 5.0), ops=[ops], host=host, n_chips=1)


def test_busy_is_the_union_and_idle_its_rest():
    red = _reduced()
    assert red.busy_s() == pytest.approx(3.0)       # [0, 2] and [3, 4]
    assert red.window_s == 5.0
    assert red.idle_share() == pytest.approx(0.4)


def test_kernel_time_counts_overlaps_once():
    red = _reduced()
    assert red.op_time(lambda o: "lut_act" in o.meta) == pytest.approx(2.5)
    assert red.op_time(lambda o: True) == pytest.approx(red.busy_s())
    top = red.top_ops(2)
    # fusion.1 [0, 1] overlaps the kernel but does not contain it
    assert top[0] == ["lut_act_kernel", pytest.approx(2.5)]


def test_self_time_leaves_out_nested_ops():
    loop = _op('%while.3 = (s32[]) while(...)', 0.0, 10.0)
    body = [_op('%fusion.7 = bf16[8] fusion(...)', 1.0, 4.0),
            _op('%closed_call.2 = bf16[8] custom-call(...), '
                'custom_call_target="tpu_custom_call"', 5.0, 9.0)]
    red = trace.Reduced(window=(0.0, 10.0), ops=[[loop] + body], host=[],
                        n_chips=1)
    assert red.top_ops(3) == [["closed_call.2:tpu_custom_call", 4.0],
                              ["fusion.7", 3.0], ["while.3", 3.0]]


def test_gaps_are_named_by_what_the_host_did():
    red = _reduced()
    assert red.gaps() == [(2.0, 3.0), (4.0, 5.0)]
    named = red.named_gaps(10)
    assert named[0] == ["bench:decode_call > PjitFunction(step)",
                        pytest.approx(1.0)]
    assert named[1] == ["bench:submit", pytest.approx(1.0)]


def test_busy_averages_over_chips():
    red = _reduced()
    two = trace.Reduced(window=red.window, ops=[red.ops[0],
                                                [_op("x", 0.0, 5.0)]],
                        host=[], n_chips=2)
    assert two.busy_s() == pytest.approx(4.0)


def test_recorded_trace():
    red = trace.reduce_file(str(RECORDED))
    assert red.n_chips == 1
    assert 0.0 < red.busy_s() < red.window_s
    assert 0.0 < red.idle_share() < 1.0
    # the trace predates the kernels' names: its Mosaic kernels show only
    # as tpu_custom_call, which the LUT readers' ``lut_`` match leaves out
    secs = red.op_time(lambda o: "tpu_custom_call" in o.meta)
    assert 0.0 < secs < red.busy_s()
    lut = readers.load(BENCH, "lut_roofline")
    assert red.op_time(lut.__globals__["is_lut"]) == 0.0
    names = [n for n, _ in red.named_gaps(10)]
    assert names and all(n.startswith("bench:") for n in names)
    assert any(n.startswith("bench:generate") for n in names)
    top = red.top_ops(10)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0.0
