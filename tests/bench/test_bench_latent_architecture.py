"""DeepSeek-V2's benchmark modules through the whole harness, at a
miniature size on the CPU.

The repository's whole ``bench/`` is copied into a fresh checkout root
and only new files are added (``data/tiny_mla/``): a ``tiny-mla``
configuration, which names the benchmark's own ``deepseek_v2_lite``
architecture and reference modules (the ones the chip cell runs) at one
dense and two expert layers of 16 experts, 4 held, top 3; its traffic
mix; its limits; and manifest entries for its cell
(``data/tiny_mla.manifest.json``).  The harness serves it through
``generate()`` with stacked Pallas tables on the ``mlp`` and ``expert``
sites and judges it against the plain reference.  Its limits file holds
it to the mean gap, since a router near-tie makes the widest one swing
(``PERF.md``)."""
from __future__ import annotations

import hashlib
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
NEW = Path(__file__).resolve().parent / "data" / "tiny_mla"
ENTRIES = json.loads((NEW.parent / "tiny_mla.manifest.json").read_text())
sys.path.insert(0, str(ROOT / "bench"))

from harness import check, readers, trace  # noqa: E402

CELL = "tiny-mla.offline"
SEED = 2 ** 31 + 1616


def _files(top: Path) -> list:
    return [f for f in sorted(top.rglob("*"))
            if f.is_file() and "__pycache__" not in f.parts]


def _digests(top: Path) -> dict:
    return {str(f.relative_to(top)): hashlib.sha256(f.read_bytes()).hexdigest()
            for f in _files(top)}


@pytest.fixture(scope="module")
def mla_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("mla_root")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for src in _files(NEW):
        dst = root / "bench" / src.relative_to(NEW)
        assert not dst.exists(), dst
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copyfile(src, dst)
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    manifest["configs"] += ENTRIES["configs"]
    manifest["workloads"] += ENTRIES["workloads"]
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if metric["name"] in ENTRIES["metrics"]:
            metric["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


@pytest.fixture
def run_mla(mla_root, harness_at, kept_runs):
    def go(*args):
        rc, out = harness_at(mla_root, *args)
        return rc, out, kept_runs

    return go


def test_copied_files_are_unchanged(mla_root):
    copied = _digests(ROOT / "bench")
    here = _digests(mla_root / "bench")
    assert {k: here[k] for k in copied} == copied
    assert set(here) - set(copied) == {
        str(f.relative_to(NEW)) for f in _files(NEW)}


def test_sound_run_is_correct_and_counts_every_routed_pair(run_mla):
    rc, out, runs = run_mla("--workload", CELL, "--seed", SEED,
                            "--seconds", 2, "--trace", 0)
    res = json.loads(out[-1])
    assert rc == 0 and res["correct"] is True, res
    assert set(res["metrics"]) == {"offline_tok_s", "setup_s"}
    (run,) = runs
    m, mix = run.m, run.mix
    counted = run.counters["moe_assignments_total"]
    assert set(counted) == {f'{{expert="{e}"}}' for e in range(m["held"])
                            } | {'{expert="elsewhere"}'}
    pairs = ((m["L"] - m["dense"]) * m["k"] * mix["batch"]
             * (mix["prompt_len"] + mix["new_tokens"]) * len(run.calls))
    assert sum(counted.values()) == pairs


def test_traced_run_reads_both_sites(run_mla, monkeypatch):
    """The CPU's profile has no device plane, so one LUT kernel op is
    planted over the traced window: ``lut_roofline`` then reads the
    architecture's count of both sites' bytes over that time."""
    reduced_now = trace.Tracer.reduced_now

    def with_a_lut_op(self):
        red = reduced_now(self)
        lo, hi = red.window
        red.ops = [[trace.Op("%lut_act_stacked.1 = bf16[8] custom-call()",
                             lo, hi, "")]]
        red.n_chips = 1
        return red

    monkeypatch.setattr(trace.Tracer, "reduced_now", with_a_lut_op)
    rc, out, runs = run_mla("--workload", CELL, "--seed", SEED + 1,
                            "--seconds", 1, "--trace", 1)
    res = json.loads(out[-1])
    assert rc == 0 and res["correct"] is True, res
    assert {"mfu.offline", "lut_roofline", "lut_share", "prefill_ms",
            "decode_step_ms", "expert_skew.offline"} <= set(res["metrics"])
    (run,) = runs
    assert set(run.site_bytes) == {"mlp", "expert"}
    assert all(b > 0 for b in run.site_bytes.values())
    mix = run.mix
    nbytes = len(run.calls) * run.arch.generate_lut(
        run.m, mix["batch"], mix["prompt_len"], mix["new_tokens"],
        run.site_bytes)
    want = 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / run.trace.window_s
    assert res["metrics"]["lut_roofline"]["value"] == pytest.approx(want)
    held = [v for k, v in run.counters["moe_assignments_total"].items()
            if "elsewhere" not in k]
    assert res["metrics"]["expert_skew.offline"]["value"] == pytest.approx(
        max(held) * len(held) / sum(held))


def test_control_fails_the_limit(run_mla, mla_root):
    rc, out, _ = run_mla("--workload", CELL, "--seed", SEED,
                         "--seconds", 1, "--readings", 1)
    row = json.loads(out[0])
    limits = check.load_limits(mla_root / "bench", CELL)
    assert list(limits) == ["mean_logit_gap"]
    assert check.judge(row, limits)[0], row
    assert not check.judge({k: row["control_" + k] for k in limits},
                           limits)[0], row


@pytest.mark.parametrize("counts, want", [
    ({'{expert="0"}': 30.0, '{expert="1"}': 10.0,
      '{expert="elsewhere"}': 500.0}, 1.5),
    ({'{expert="0"}': 7.0, '{expert="1"}': 7.0, '{expert="2"}': 7.0}, 1.0),
    ({'{expert="elsewhere"}': 9.0}, None),
    ({}, None),
])
def test_expert_skew_reads_the_held_experts_only(counts, want):
    read = readers.load(ROOT / "bench", "expert_skew.offline")
    run = readers.Run(kind="offline", arch=None, m={}, peaks={}, mix={},
                      setup_s=0.0, calib_s=0.0, site_bytes={}, window_s=1.0,
                      counters={"moe_assignments_total": counts}
                      if counts else {})
    assert read(run) == (pytest.approx(want) if want is not None else None)
