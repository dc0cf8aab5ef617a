"""A new architecture is new files only.

The repository's whole ``bench/`` is copied into a fresh checkout root;
then only new files are added: a ``tiny-moe`` configuration (the
program's ``moe`` family at smoke size: 8 experts, top-2, one shared
expert, capacity factor 8 so that no token is dropped), its architecture
module, its plain reference, a traffic mix and its limits
(``data/tiny_moe/``), and manifest entries for its cell
(``data/tiny_moe.manifest.json``) beside the repository's own.  The
harness then serves it with stacked Pallas tables on the ``mlp`` and
``expert`` sites, and judges it against its own reference.  Its limits
file holds it to the mean gap, since a router's near-ties make the
widest one swing; the limit was set from ``--readings`` on the CPU
(``PERF.md``).
"""
from __future__ import annotations

import hashlib
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
NEW = Path(__file__).resolve().parent / "data" / "tiny_moe"
ENTRIES = json.loads((NEW.parent / "tiny_moe.manifest.json").read_text())
sys.path.insert(0, str(ROOT / "bench"))

from harness import check, trace  # noqa: E402

CELL = "tiny-moe.offline"
SEED = 2 ** 31 + 303


def _digests(top: Path) -> dict:
    return {str(f.relative_to(top)): hashlib.sha256(f.read_bytes()).hexdigest()
            for f in sorted(top.rglob("*")) if f.is_file()}


def _files(top: Path) -> list:
    return [f for f in sorted(top.rglob("*"))
            if f.is_file() and "__pycache__" not in f.parts]


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("moe_root")
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
def run_moe(moe_root, harness_at, kept_runs):
    """``go(*args) -> (rc, lines, runs)``: the harness from the new root,
    with each ``readers.Run`` it builds."""
    def go(*args):
        rc, out = harness_at(moe_root, *args)
        return rc, out, kept_runs

    return go


def test_copied_files_are_unchanged(moe_root):
    copied = _digests(ROOT / "bench")
    copied = {k: v for k, v in copied.items() if "__pycache__" not in k}
    here = _digests(moe_root / "bench")
    assert {k: here[k] for k in copied} == copied
    added = {str(f.relative_to(NEW)) for f in _files(NEW)}
    assert set(here) - set(copied) == added


def test_sound_run_is_correct(run_moe):
    rc, out, _ = run_moe("--workload", CELL, "--seed", SEED,
                         "--seconds", 2, "--trace", 0)
    res = json.loads(out[-1])
    assert rc == 0 and res["correct"] is True, res
    assert set(res["metrics"]) == {"offline_tok_s", "setup_s"}
    assert res["failed"] == 0 and res["attempted"] > 0


def test_traced_run_reads_both_sites(run_moe, monkeypatch):
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
    rc, out, runs = run_moe("--workload", CELL, "--seed", SEED + 1,
                            "--seconds", 1, "--trace", 1)
    res = json.loads(out[-1])
    assert rc == 0 and res["correct"] is True, res
    assert {"mfu.offline", "lut_roofline", "prefill_ms"} <= set(res["metrics"])
    (run,) = runs
    assert set(run.site_bytes) == {"mlp", "expert"}
    assert all(b > 0 for b in run.site_bytes.values())
    mix = run.mix
    nbytes = len(run.calls) * run.arch.generate_lut(
        run.m, mix["batch"], mix["prompt_len"], mix["new_tokens"],
        run.site_bytes)
    want = 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / run.trace.window_s
    assert res["metrics"]["lut_roofline"]["value"] == pytest.approx(want)


def test_control_fails_the_limit(run_moe, moe_root):
    rc, out, _ = run_moe("--workload", CELL, "--seed", SEED,
                         "--seconds", 2, "--readings", 1)
    row = json.loads(out[0])
    limits = check.load_limits(moe_root / "bench", CELL)
    assert list(limits) == ["mean_logit_gap"]
    ok, _ = check.judge(row, limits)
    assert ok, row
    ok, _ = check.judge({k: row["control_" + k] for k in limits}, limits)
    assert not ok, row


def test_judge_holds_the_numbers_its_limits_name():
    numbers = {"max_logit_gap": 0.5, "mean_logit_gap": 0.01}
    mean_only = {"mean_logit_gap": {"limit": 0.018}}
    assert check.judge(numbers, mean_only) == (
        True, {"mean_logit_gap": {"value": 0.01, "limit": 0.018}})
    both = dict(mean_only, max_logit_gap={"limit": 0.32})
    assert check.judge(numbers, both)[0] is False
    assert check.judge({"max_logit_gap": 0.1}, mean_only)[0] is False
    assert check.judge(numbers, None) == (
        False, {"max_logit_gap": {"value": 0.5, "limit": None}})
