"""The open-loop schedule and loop: seeded arrivals, time to first
token from the due time, gaps from tick stamps, drain after the window.
A scripted batcher stands in for the program."""
from __future__ import annotations

import dataclasses
import math
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

from harness import loops, readers, traffic  # noqa: E402

MIX = traffic.load_traffic("chat", Path(__file__).resolve().parent / "data")


def test_schedule_is_seeded_and_keeps_its_work():
    a = traffic.open_loop(MIX, 1000, seed=2 ** 33 + 5, seconds=30.0)
    b = traffic.open_loop(MIX, 1000, seed=2 ** 33 + 5, seconds=30.0)
    c = traffic.open_loop(MIX, 1000, seed=7, seconds=30.0)
    assert [(x.due_s, x.prompt, x.max_new) for x in a] == [
        (x.due_s, x.prompt, x.max_new) for x in b]
    assert [x.prompt for x in a] != [x.prompt for x in c]
    # every seed holds the same work: lengths and gaps, in its own order
    assert len(a) == len(c) == math.floor(MIX["rate_per_s"] * 30.0)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in c)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in c)
    gaps = lambda s: sorted(np.round(np.diff([x.due_s for x in s]), 9))
    assert a[0].due_s == 0.0 and all(x.due_s < 30.0 for x in a)
    assert np.mean(np.diff([x.due_s for x in a])) == pytest.approx(
        1.0 / MIX["rate_per_s"], rel=0.1)
    assert len(set(gaps(a)) & set(gaps(c))) > len(a) // 2


def test_lengths_follow_the_mix():
    a = traffic.open_loop(MIX, 1000, seed=1, seconds=50.0)
    p = np.array([len(x.prompt) for x in a])
    o = np.array([x.max_new for x in a])
    spec = MIX["prompt_len"]
    assert p.min() >= spec["min"] and p.max() <= spec["max"]
    assert (p % spec["multiple"] == 0).all()
    assert abs(np.median(p) - spec["median"]) <= spec["multiple"]
    assert o.min() >= MIX["output_len"]["min"]
    assert o.max() <= MIX["output_len"]["max"]
    assert set(p) <= set(traffic.distinct_prompt_lengths(MIX))


@dataclasses.dataclass
class _Req:
    rid: int
    prompt: list
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    done: bool = False


class ScriptedBatcher:
    """Every tick sleeps ``tick_s``, admits what fits in ``slots`` and
    gives every active request one token."""

    def __init__(self, slots: int, tick_s: float):
        self.slots, self.tick_s = slots, tick_s
        self.queue, self.active_reqs = [], []
        self.decode_calls = 0

    def submit(self, arrival):
        req = _Req(arrival.rid, list(arrival.prompt), arrival.max_new)
        self.queue.append(req)
        return req

    def tick(self):
        time.sleep(self.tick_s)
        while self.queue and len(self.active_reqs) < self.slots:
            self.active_reqs.append(self.queue.pop(0))
        self.decode_calls += 1
        for r in self.active_reqs:
            r.out.append(1)
            r.done = len(r.out) >= r.max_new
        self.active_reqs = [r for r in self.active_reqs if not r.done]

    @property
    def busy(self):
        return bool(self.queue or self.active_reqs)

    def active(self):
        return list(self.active_reqs)


def _arrivals(dues, max_new=3):
    return [traffic.Arrival(rid=i, due_s=d, prompt=[1, 2], max_new=max_new)
            for i, d in enumerate(dues)]


def test_ttft_counts_from_the_due_time():
    # the second request falls due while the first tick runs; its time to
    # first token includes that wait
    bat = ScriptedBatcher(slots=4, tick_s=0.1)
    loop = loops.run_open_loop(bat, _arrivals([0.0, 0.01]), seconds=0.05,
                                 drain_s=5.0)
    first, second = loop.served
    assert second.submit_s >= 0.1
    assert second.token_s[0] - second.arrival.due_s >= 0.18
    assert first.token_s[0] == pytest.approx(0.1, abs=0.05)
    assert loop.late_s[1] >= 0.09


def test_gaps_are_tick_stamps_and_the_drain_counts():
    bat = ScriptedBatcher(slots=1, tick_s=0.03)
    loop = loops.run_open_loop(bat, _arrivals([0.0, 0.0, 0.05]),
                                 seconds=0.1, drain_s=5.0)
    assert all(s.done for s in loop.served)
    assert loop.drained_s > 0.0          # answered after the window
    for s in loop.served:
        gaps = np.diff(s.token_s)
        assert len(s.token_s) == 3
        assert (gaps >= 0.025).all() and (gaps < 0.2).all()
    # requests queue behind one slot: later ones wait longer
    ttft = [s.token_s[0] - s.arrival.due_s for s in loop.served]
    assert ttft[0] < ttft[1] < ttft[2] + 0.2
    run = readers.Run(kind="open_loop", arch=None, m={}, peaks={}, mix={},
                      setup_s=0, calib_s=0, site_bytes={},
                      window_s=loop.window_s,
                      loop=loop)
    p90 = readers.load(BENCH, "ttft_p90_ms")(run)
    assert p90 == pytest.approx(1e3 * max(ttft))
    itl = readers.load(BENCH, "itl_p95_ms")(run)
    all_gaps = [g for s in loop.served for g in np.diff(s.token_s)]
    assert itl == pytest.approx(1e3 * readers.percentile(all_gaps, 0.95))
    assert loop.ticks >= 1 and loop.decode_calls == loop.ticks


def test_a_request_never_answered_is_missing():
    bat = ScriptedBatcher(slots=1, tick_s=0.02)
    loop = loops.run_open_loop(bat, _arrivals([0.0, 0.0], max_new=400),
                                 seconds=0.05, drain_s=0.1)
    assert not loop.served[1].done and not loop.served[1].token_s
    run = readers.Run(kind="open_loop", arch=None, m={}, peaks={}, mix={},
                      setup_s=0, calib_s=0, site_bytes={},
                      window_s=loop.window_s,
                      loop=loop)
    assert readers.load(BENCH, "ttft_p90_ms")(run) == math.inf


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert readers.percentile(xs, 0.90) == 90
    assert readers.percentile(xs, 0.95) == 95
    assert readers.percentile([3.0], 0.5) == 3.0
    assert readers.percentile([1.0, math.inf], 0.9) == math.inf
