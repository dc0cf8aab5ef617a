"""Equivalence tests: the batched/parallel engine vs the serial reference.

The engine must return plans with identical ``plut_cost()`` and
``reconstruct()`` output to ``compress_table_serial`` on every table —
including the degenerate shapes (all-care, all-don't-care, constant) —
and ``workers > 1`` must be deterministic and order-preserving.
"""
import numpy as np
import pytest

from repro.core import (
    CompressConfig,
    CompressReport,
    TableSpec,
    compress_network_report,
    compress_network_serial,
    compress_table,
    compress_table_serial,
    verify_care_exact,
)
from repro.core.cost_model import (
    adder_plut_cost,
    adder_plut_cost_batch,
    rom_plut_cost,
    rom_plut_cost_batch,
    shifter_plut_cost,
    shifter_plut_cost_batch,
)
from repro.core.engine import shutdown_pools
from repro.core.similarity import split_residualize, split_residualize_batch


def _grid_specs() -> list[TableSpec]:
    specs = []
    for seed in range(3):
        for frac in (0.0, 0.5):
            for smooth in (True, False):
                specs.append(TableSpec.random(
                    8, 5, frac, seed, smooth,
                    name=f"r{seed}_{frac}_{smooth}"))
    n = 1 << 8
    # constant table
    specs.append(TableSpec(np.full(n, 13, np.int64), 8, 5, name="const"))
    # all-don't-care table
    specs.append(TableSpec(
        np.arange(n, dtype=np.int64) % 32, 8, 5,
        care=np.zeros(n, bool), name="all_dc"))
    # single care entry
    care = np.zeros(n, bool)
    care[7] = True
    specs.append(TableSpec(
        np.arange(n, dtype=np.int64) % 32, 8, 5, care=care, name="one_care"))
    return specs


def _assert_equivalent(a, b, name=""):
    assert a.kind == b.kind, name
    assert a.plut_cost() == b.plut_cost(), name
    np.testing.assert_array_equal(a.reconstruct(), b.reconstruct(), err_msg=name)


@pytest.mark.parametrize("exiguity", [None, 0, 250])
def test_engine_matches_serial_on_grid(exiguity):
    cfg = CompressConfig(exiguity=exiguity)
    for spec in _grid_specs():
        a = compress_table_serial(spec, cfg)
        b = compress_table(spec, cfg)
        _assert_equivalent(a, b, spec.name)
        assert verify_care_exact(spec, b), spec.name


def test_engine_matches_serial_restricted_search_space():
    cfg = CompressConfig(exiguity=150, m_candidates=(8, 32),
                         lb_candidates=(0, 2))
    for seed in range(4):
        spec = TableSpec.random(9, 6, 0.4, seed, smooth=True)
        _assert_equivalent(
            compress_table_serial(spec, cfg), compress_table(spec, cfg))


def test_engine_matches_serial_bias_care_only_and_multisweep():
    cfg = CompressConfig(exiguity=100, bias_care_only=True, merge_sweeps=3)
    for seed in range(3):
        spec = TableSpec.random(8, 6, 0.6, seed, smooth=True)
        _assert_equivalent(
            compress_table_serial(spec, cfg), compress_table(spec, cfg))


def test_engine_tiny_table_no_candidates():
    """w_in=3 leaves no legal sub-table size; both paths return plain."""
    spec = TableSpec.random(3, 4, 0.0, 0)
    a = compress_table_serial(spec)
    b = compress_table(spec)
    assert a.kind == b.kind == "plain"
    _assert_equivalent(a, b)


# ---------------------------------------------------------------------------
# batched cost model == scalar cost model
# ---------------------------------------------------------------------------
def test_rom_cost_batch_matches_scalar():
    qs, ws = np.meshgrid(np.arange(0, 17), np.arange(0, 10))
    got = rom_plut_cost_batch(qs.ravel(), ws.ravel())
    want = [rom_plut_cost(int(q), int(w))
            for q, w in zip(qs.ravel(), ws.ravel())]
    np.testing.assert_array_equal(got, want)


def test_adder_shifter_cost_batch_match_scalar():
    w = np.arange(-2, 12)
    np.testing.assert_array_equal(
        adder_plut_cost_batch(w), [adder_plut_cost(int(x)) for x in w])
    d, s = np.meshgrid(np.arange(0, 9), np.arange(0, 9))
    got = shifter_plut_cost_batch(d.ravel(), s.ravel())
    want = [shifter_plut_cost(int(a), int(b))
            for a, b in zip(d.ravel(), s.ravel())]
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("bias_care_only", [False, True])
def test_split_residualize_batch_matches_scalar(bias_care_only):
    spec = TableSpec.random(9, 7, 0.5, 3, smooth=True)
    lbs = (0, 1, 2, 3)
    hb_all = spec.values[None, :] >> np.asarray(lbs)[:, None]
    for m in (8, 16):
        res_b, bias_b, care_b = split_residualize_batch(
            hb_all, spec.care_mask(), m, bias_care_only)
        for i, w_lb in enumerate(lbs):
            res, bias, care2d = split_residualize(
                spec.values >> w_lb, spec.care_mask(), m, bias_care_only)
            np.testing.assert_array_equal(res_b[i], res)
            np.testing.assert_array_equal(bias_b[i], bias)
            np.testing.assert_array_equal(care_b, care2d)


# ---------------------------------------------------------------------------
# network-level: reports, parallel determinism
# ---------------------------------------------------------------------------
def _network_specs(n=5, w_in=7):
    return [
        TableSpec.random(w_in, 5, 0.4 if i % 2 else 0.0, i, smooth=(i % 2 == 0),
                         name=f"net{i}")
        for i in range(n)
    ]


def test_report_structure_and_totals():
    specs = _network_specs()
    rep = compress_network_report(specs, CompressConfig(exiguity=250))
    assert isinstance(rep, CompressReport)
    assert len(rep.plans) == len(rep.tables) == len(specs)
    assert [t.name for t in rep.tables] == [s.name for s in specs]
    for plan, tab in zip(rep.plans, rep.tables):
        assert plan.kind == tab.kind
        assert plan.plut_cost() == tab.cost
        assert tab.cost <= tab.plain_cost
        assert tab.seconds >= 0
    assert rep.total_cost == sum(p.plut_cost() for p in rep.plans)
    assert 0.0 <= rep.saved_frac <= 1.0
    assert f"{len(specs)} tables" in rep.summary()
    rows = rep.to_rows()
    assert rows[0]["name"] == specs[0].name and "cost" in rows[0]


def test_report_winner_metadata_matches_plan():
    specs = _network_specs()
    rep = compress_network_report(specs, CompressConfig(exiguity=250))
    for plan, tab in zip(rep.plans, rep.tables):
        if tab.kind == "decomposed":
            assert tab.m == plan.m
            assert tab.w_lb == plan.w_lb
        else:
            assert tab.m is None and tab.w_lb == 0


def test_parallel_workers_identical_and_deterministic():
    specs = _network_specs(n=6)
    cfg = CompressConfig(exiguity=250)
    try:
        serial_plans = compress_network_serial(specs, cfg)
        rep_a = compress_network_report(specs, cfg, workers=2)
        rep_b = compress_network_report(specs, cfg, workers=2)
    finally:
        shutdown_pools()
    assert rep_a.workers == 2
    for sp, pa, pb in zip(serial_plans, rep_a.plans, rep_b.plans):
        _assert_equivalent(sp, pa)
        _assert_equivalent(pa, pb)
    assert [t.name for t in rep_a.tables] == [s.name for s in specs]
    assert [t.cost for t in rep_a.tables] == [t.cost for t in rep_b.tables]


def test_engine_pool_workers_stay_off_jax():
    """Spawned compression workers import ``repro.core.engine`` afresh; it
    must not pull in JAX, or a worker could claim the accelerator that
    the serving process holds."""
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    code = ("import sys, repro.core.engine; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    proc = subprocess.run([sys.executable, "-c", code], timeout=120,
                          env=dict(os.environ, PYTHONPATH=src))
    assert proc.returncode == 0
