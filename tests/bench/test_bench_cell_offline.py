"""Whole runs of the harness at a miniature size on the CPU: an offline cell.

The harness's look for a chip is skipped; everything else runs as on the
chip: weights from the seed, calibration and compression, warm-up, the
window through the program's own entry points, the reference check.  A
sound run comes out correct; the fp8 control in the program's place, and
the timed path broken underneath (a token altered where it is produced,
a decode step that returns its cache unchanged), come out not correct.
The miniature's limits (``data/limits``) were set from its own readings
on the CPU: see ``PERF.md``.
"""
from __future__ import annotations

import importlib
import json
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "bench"))

from harness import check  # noqa: E402

CELL = "tiny.offline"
SEED = 2 ** 31 + 101


def _token_altered(orig):
    def step(*a, **k):
        logits, cache = orig(*a, **k)
        return jnp.roll(logits, 1, axis=-1), cache
    return step


def _state_unchanged(orig):
    def step(params, cfg, cache, *a, **k):
        logits, _ = orig(params, cfg, cache, *a, **k)
        return logits, cache
    return step


def test_sound_run_is_correct(harness_run):
    rc, out = harness_run("--workload", CELL, "--seed", SEED,
                          "--seconds", 2, "--trace", 0)
    res = json.loads(out[-1])
    assert rc == 0 and res["correct"] is True, res
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"offline_tok_s", "setup_s"}
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["count"] == 1


def test_traced_run_reports_per_layer(harness_run):
    rc, out = harness_run("--workload", CELL, "--seed", SEED + 1,
                          "--seconds", 1, "--trace", 1)
    res = json.loads(out[-1])
    assert rc == 0 and res["correct"] is True
    assert {"calib_s", "prefill_ms", "decode_step_ms", "mfu.offline"} <= set(res["metrics"])
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_window_counters_reach_readers(harness_run, kept_runs):
    """The window runs under a bare ``repro.obs.Telemetry``, which keeps
    the compiled programs: ``Run.counters`` holds every window call's
    prefill and decode program as a hit, and no miss."""
    rc, out = harness_run("--workload", CELL, "--seed", SEED,
                          "--seconds", 1, "--trace", 0)
    assert rc == 0 and json.loads(out[-1])["attempted"] > 0
    (run,) = kept_runs
    programs = run.counters["generate_programs_total"]
    assert programs == {'{outcome="hit",program="decode"}': len(run.calls),
                        '{outcome="hit",program="prefill"}': len(run.calls)}
    assert sum(programs.values()) == 2 * len(run.calls) > 0


def test_control_fails_the_limit(harness_run, tiny_root):
    rc, out = harness_run("--workload", CELL, "--seed", SEED,
                          "--seconds", 2, "--readings", 1)
    row = json.loads(out[0])
    limits = check.load_limits(tiny_root / "bench", CELL)
    ok, _ = check.judge(row, limits)
    assert ok, row
    ok, _ = check.judge({"max_logit_gap": row["control_max_logit_gap"]},
                        limits)
    assert not ok, row


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(harness_run, monkeypatch, fault):
    batching = importlib.import_module("repro.serve.batching")
    generate = importlib.import_module("repro.serve.generate")
    monkeypatch.setattr(generate, "decode_step",
                        fault(generate.decode_step))
    monkeypatch.setattr(batching, "decode_step",
                        fault(batching.decode_step))
    rc, out = harness_run("--workload", CELL, "--seed", SEED,
                          "--seconds", 2, "--trace", 0)
    res = json.loads(out[-1])
    assert rc == 0 and res["correct"] is False, res
