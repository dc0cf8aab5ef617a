"""BENCHMARK.json against the files the benchmark reads by name."""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
DATA = Path(__file__).resolve().parent / "data"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
sys.path.insert(0, str(BENCH))

from harness import model  # noqa: E402

# (bench dir, configuration file): the benchmark's own, and the tests'
# miniatures, each beside the modules it names
CONFIG_FILES = ([(BENCH, ROOT / c["file"]) for c in MANIFEST["configs"]]
                + [(BENCH, DATA / "configs" / "tiny.json"),
                   (DATA / "tiny_moe",
                    DATA / "tiny_moe" / "configs" / "tiny-moe.json")])

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|projection|_dim$|"
                   r"_rank$|head|expansion|experts_per_tok|top_k)")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _metrics():
    return MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_top_level_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    for p in MANIFEST["paths"]:
        assert (ROOT / p).is_dir() and ".." not in p and not p.startswith("/")
    for word in MANIFEST["command"][1:]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in MANIFEST["paths"])


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_are_unique_and_allowed(kind):
    names = [e["name"] for e in MANIFEST[kind]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names), names


def test_units_sources_and_bounds():
    for m in _metrics():
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])


def test_every_cell_names_existing_files():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    used = set()
    pairs = set()
    for w in MANIFEST["workloads"]:
        assert w["config"] in configs, w
        used.add(w["config"])
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file(), w
        assert (BENCH / "limits" / f"{w['name']}.json").is_file(), w
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    assert used == set(configs)
    files = [c["file"] for c in configs.values()]
    assert len(files) == len(set(files))
    for c in configs.values():
        path = ROOT / c["file"]
        assert path.is_file() and c["file"].startswith("bench/")
        conf = json.loads(path.read_text())
        assert conf["name"] == c["name"]
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        assert (BENCH / "references" / f"{conf['reference']}.py").is_file()
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key


@pytest.mark.parametrize("bench,path", CONFIG_FILES,
                         ids=[p.stem for _, p in CONFIG_FILES])
def test_every_configuration_names_a_whole_architecture(bench, path):
    conf = json.loads(path.read_text())
    assert (bench / "architectures" / f"{conf['architecture']}.py").is_file()
    assert (bench / "references" / f"{conf['reference']}.py").is_file()
    arch = model.load_architecture(conf, bench)
    assert all(callable(getattr(arch, f)) for f in model.INTERFACE)
    assert {"L", "V"} <= set(arch.dims(conf))


def test_a_configuration_without_an_architecture_is_refused(tmp_path):
    conf = json.loads((BENCH / "configs" / "qwen3-0.6b.json").read_text())
    del conf["architecture"]
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "bare.json").write_text(json.dumps(conf))
    with pytest.raises(ValueError, match="architecture"):
        model.load_config("bare", tmp_path)


def test_every_metric_has_a_reader():
    for m in _metrics():
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def test_per_layer_metrics_move_a_reported_metric():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    cells = [w["name"] for w in MANIFEST["workloads"]]
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e, m
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    layers = {m["layer"] for m in MANIFEST["per_layer"]}
    assert all(1 <= len(x) <= 200 for x in layers)


def test_every_cell_reports_enough():
    cells = [w["name"] for w in MANIFEST["workloads"]]
    for cell in cells:
        e2e = [m["name"] for m in MANIFEST["end_to_end"]
               if cell in m.get("workloads", cells)]
        per = [m["name"] for m in MANIFEST["per_layer"]
               if cell in m.get("workloads", cells)]
        assert "setup_s" in e2e and len(e2e) >= 2 and per, cell


def test_limits_carry_their_readings():
    for w in MANIFEST["workloads"]:
        lim = json.loads((BENCH / "limits" / f"{w['name']}.json").read_text())
        for name, entry in lim.items():
            assert entry["lower"] < entry["limit"] < entry["upper"], name


def test_four_chip_cells_are_few():
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 2)


def test_manifest_is_small():
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
