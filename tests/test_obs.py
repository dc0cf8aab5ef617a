"""Unified telemetry layer: metrics registry, checksummed event log,
the don't-care drift monitor, and its serving invariants — token
identity with telemetry on, zero traced ops with it off."""
import contextlib
import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.calib import (
    CalibrationSet,
    calibration_from_capture,
    capture_model,
    model_batch,
    synthetic_batches,
)
from repro.configs import get_config, smoke_config
from repro.ioutil import ArtifactError
from repro.nn import init_params
from repro.obs.metrics import (
    Histogram,
    MetricsRegistry,
    exponential_buckets,
)
from repro.serve import build_serving_plans, decode_step, prefill
from repro.serve.batching import ContinuousBatcher, Request


# =========================================================================
# metrics registry
# =========================================================================
def test_counter_and_gauge_labels():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    c.inc(site="mlp")
    c.inc(2, site="mlp")
    c.inc(site="ffn")
    assert c.value(site="mlp") == 3 and c.value(site="ffn") == 1
    assert c.total() == 4
    with pytest.raises(ValueError, match="negative"):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(5)
    g.set(2)
    assert g.value() == 2  # last set wins
    # get-or-create returns the same object; kind mismatch is an error
    assert reg.counter("reqs_total") is c
    with pytest.raises(ValueError, match="already registered"):
        reg.histogram("reqs_total")


def test_histogram_buckets_and_percentiles():
    h = Histogram("lat", buckets=exponential_buckets(0.001, 2.0, 10))
    assert h.percentile(0.5) == 0.0  # empty: defined, not NaN
    for v in (0.001, 0.002, 0.002, 0.004, 100.0):
        h.observe(v)
    h.observe(float("nan"))  # skipped
    assert h.count() == 5
    assert h.percentile(0.5) == 0.002
    assert h.percentile(1.0) == float("inf")  # overflow bucket
    snap = h.snapshot()[""]
    assert snap["count"] == 5 and snap["p95"] is None  # inf -> JSON null


def test_prometheus_render():
    reg = MetricsRegistry()
    reg.counter("a_total", "things").inc(3, kind="x")
    reg.histogram("b_seconds",
                  buckets=exponential_buckets(0.1, 2.0, 2)).observe(0.15)
    text = reg.render_prometheus()
    assert "# TYPE a_total counter" in text
    assert 'a_total{kind="x"} 3' in text
    assert 'b_seconds_bucket{le="+Inf"} 1' in text
    assert "b_seconds_count 1" in text


# =========================================================================
# event log
# =========================================================================
def test_event_log_roundtrip(tmp_path):
    path = str(tmp_path / "run.jsonl")
    ev = obs.EventLog(path)
    ev.emit("hello", n=1)
    with ev.span("outer", tag="t"):
        ev.emit("inner")
        with ev.span("nested"):
            pass
    ev.close(note="done")
    records = obs.read_events(path)
    assert records[0]["schema"] == obs.OBS_SCHEMA
    assert records[-1]["event"] == "obs_end"
    assert records[-1]["n_records"] == len(records)
    by_event = {}
    for r in records:
        by_event.setdefault(r["event"], []).append(r)
    # the inner event carries its enclosing span id
    outer = by_event["span_begin"][0]
    assert by_event["hello"][0]["n"] == 1
    assert by_event["inner"][0]["span"] == outer["span_id"]
    # nested span records its parent and the matching end has a duration
    nested = by_event["span_begin"][1]
    assert nested["parent"] == outer["span_id"]
    ends = {r["span_id"]: r for r in by_event["span_end"]}
    assert ends[outer["span_id"]]["dur_s"] >= 0
    # seq is dense and every crc validates (read_events already checked)
    assert [r["seq"] for r in records] == list(range(len(records)))


def test_event_log_sampling_accounts_for_drops():
    ev = obs.EventLog(sample=3)
    for _ in range(10):
        ev.emit("tick", sampled=True)
        ev.emit("swap")  # unsampled events are never thinned
    ev.close()
    ticks = [r for r in ev.records if r["event"] == "tick"]
    swaps = [r for r in ev.records if r["event"] == "swap"]
    assert len(swaps) == 10
    assert len(ticks) == 4  # occurrences 0, 3, 6, 9
    # every dropped occurrence is accounted on a surviving record
    assert sum(r.get("sampled_dropped", 0) for r in ticks) == 10 - 4
    assert all(r["sampled_every"] == 3
               for r in ticks if "sampled_dropped" in r)


def test_event_log_detects_corruption(tmp_path):
    path = str(tmp_path / "run.jsonl")
    ev = obs.EventLog(path)
    ev.emit("a", value=123)
    ev.emit("b")
    ev.close()
    lines = open(path).read().splitlines()

    # bit-flip one field value -> CRC mismatch
    bad = str(tmp_path / "bad.jsonl")
    open(bad, "w").write(
        "\n".join(l.replace("123", "124") for l in lines) + "\n")
    with pytest.raises(ArtifactError, match="CRC mismatch"):
        obs.read_events(bad)

    # missing footer -> strict fails, non-strict inspects the partial log
    part = str(tmp_path / "part.jsonl")
    open(part, "w").write("\n".join(lines[:-1]) + "\n")
    with pytest.raises(ArtifactError, match="no obs_end footer"):
        obs.read_events(part)
    assert len(obs.read_events(part, strict=False)) == len(lines) - 1

    # spliced-out middle line -> footer count mismatch
    spliced = str(tmp_path / "spliced.jsonl")
    open(spliced, "w").write("\n".join(lines[:1] + lines[2:]) + "\n")
    with pytest.raises(ArtifactError, match="truncated or spliced"):
        obs.read_events(spliced)

    # no header -> unknown schema
    headless = str(tmp_path / "headless.jsonl")
    open(headless, "w").write("\n".join(lines[1:]) + "\n")
    with pytest.raises(ArtifactError, match="obs header"):
        obs.read_events(headless)


# =========================================================================
# don't-care monitor (unit)
# =========================================================================
def _toy_calib():
    """16-bin quantizer over [-8, 8]: lower half care, upper half not."""
    mask = np.zeros(16, bool)
    mask[:8] = True
    hist = np.zeros(16, np.int64)
    hist[:8] = 10
    return CalibrationSet({"mlp": mask}, w_in=4, x_lo=-8.0, x_hi=8.0,
                          hists={"mlp": hist})


def test_monitor_counts_dontcare_hits():
    mon = obs.DontCareMonitor(_toy_calib())
    care = jnp.linspace(-7.5, -1.0, 20)      # codes in the care half
    dontcare = jnp.linspace(1.0, 7.5, 20)    # codes in the rewritten half
    mon.observe("mlp", None, care)
    assert mon.hits["mlp"] == 0 and mon.lookups["mlp"] == 20
    mon.observe("mlp", None, dontcare)
    assert mon.hits["mlp"] == 20 and mon.lookups["mlp"] == 40
    row = mon.drift()["mlp"]
    assert row["served_dontcare_frac"] == 0.5
    assert row["calib_dontcare_frac"] == 0.0  # all calib mass was in care
    assert row["excess"] == 0.5


def test_monitor_ignores_nonfinite():
    mon = obs.DontCareMonitor(_toy_calib())
    x = jnp.asarray([2.0, jnp.inf, -jnp.inf, jnp.nan, 3.0])
    mon.observe("mlp", None, x)
    assert mon.lookups["mlp"] == 2 and mon.hits["mlp"] == 2


def test_monitor_output_passthrough():
    """wrap() must never change the wrapped activation's output."""
    mon = obs.DontCareMonitor(_toy_calib())
    x = jnp.linspace(-6.0, 6.0, 64)
    fn = mon.wrap("mlp", None, jnp.tanh)
    with mon:
        np.testing.assert_array_equal(np.asarray(fn(x)),
                                      np.asarray(jnp.tanh(x)))
    assert mon.lookups["mlp"] == 64
    # unknown sites pass through without even a wrapper
    assert mon.wrap("rope_table", None, jnp.tanh) is jnp.tanh


def test_monitor_traced_layer_inside_scan():
    """The per-layer attribution survives a traced in-scan layer id (the
    serving configuration: stacked plans keep lax.scan, the layer index
    rides the debug callback as an operand)."""
    masks = {"L0/mlp": np.ones(16, bool),      # nothing rewritten at L0
             "L1/mlp": np.zeros(16, bool)}     # everything rewritten at L1
    calib = CalibrationSet(masks, w_in=4, x_lo=-8.0, x_hi=8.0)
    mon = obs.DontCareMonitor(calib)
    x = jnp.linspace(-7.0, 7.0, 32)

    def body(carry, lyr):
        mon.observe("mlp", lyr, x)
        return carry, ()

    with mon:
        jax.jit(lambda: jax.lax.scan(body, 0, jnp.arange(2)))()
    mon.flush()
    assert mon.lookups == {"L0/mlp": 32, "L1/mlp": 32}
    assert mon.hits["L0/mlp"] == 0 and mon.hits["L1/mlp"] == 32


def test_suppressed_hides_monitor():
    """obs.suppressed() makes the active monitor invisible at trace
    time — the escape hatch step loops use to compile the plain,
    callback-free program while a monitor context is entered."""
    mon = obs.DontCareMonitor(_toy_calib())
    with mon:
        assert obs.monitor_active()
        with obs.suppressed():
            assert not obs.monitor_active()
            from repro.obs import drift as obs_drift
            assert obs_drift.current() is None
        assert obs.monitor_active()
    assert not obs.monitor_active()


def test_batcher_sampled_drift_monitoring():
    """sample_every=N serving: the batcher runs the monitored step
    program on every Nth tick only.  Tokens must match the unmonitored
    run exactly, and the sampled monitor must observe a strict subset
    of the traffic a full-rate monitor sees."""
    cfg = smoke_config(get_config("qwen3-0.6b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(1, cfg.vocab_size, 5 + i)))
               for i in range(3)]

    def run(monitor):
        b = ContinuousBatcher(cfg, params, batch_size=2, max_seq=32,
                              eos_token=-1)
        for i, p in enumerate(prompts):
            b.submit(Request(rid=i, prompt=list(p), max_new=6))
        with monitor if monitor is not None else contextlib.nullcontext():
            done = b.run()
        if monitor is not None:
            monitor.flush()
        return {r.rid: list(r.out) for r in done}

    base = run(None)
    full_mon = obs.DontCareMonitor(_toy_calib())
    assert run(full_mon) == base
    full = sum(full_mon.lookups.values())
    samp_mon = obs.DontCareMonitor(_toy_calib(), sample_every=3)
    assert run(samp_mon) == base
    samp = sum(samp_mon.lookups.values())
    assert full > 0 and 0 < samp < full


# =========================================================================
# model-level drift: in-distribution ~0, out-of-distribution > 0
# =========================================================================
@pytest.fixture(scope="module")
def drift_model():
    # float32 so the capture pass (unrolled layers) and the monitored
    # pass (scanned layers) compute bit-identical pre-activations — see
    # the scan-vs-unroll note in test_stacked.py
    cfg = dataclasses.replace(smoke_config(get_config("qwen3-0.6b")),
                              dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    batches = synthetic_batches(cfg, 2, batch_size=2, seq_len=8, seed=1)
    cap = capture_model(params, cfg, batches, w_in=8)
    return cfg, params, calibration_from_capture(cap)


def _served_dontcare_frac(cfg, params, calib, batches) -> float:
    from repro.calib import ActivationCapture
    from repro.nn.transformer import decoder_forward

    mon = obs.DontCareMonitor(calib)
    # A throwaway capture context unrolls the layer stacks, so this
    # replay runs the exact program the calibration pass ran — any
    # don't-care hit is distribution drift, not a scan-vs-unroll float
    # reassociation flipping a bin boundary (the scanned/traced-layer
    # path has its own test above).
    with ActivationCapture(w_in=calib.w_in), mon:
        for batch in batches:
            out, _, _ = decoder_forward(
                params, cfg, np.asarray(batch["tokens"], np.int32))
            jax.block_until_ready(out)
    rows = mon.drift()
    assert rows, "monitor observed no lookups"
    hits = sum(r["dontcare_hits"] for r in rows.values())
    lookups = sum(r["lookups"] for r in rows.values())
    return hits / lookups


def test_drift_in_distribution_vs_ood(drift_model):
    """Replaying the calibration traffic reports exactly zero don't-care
    hits — every observed bin is care at min_count=1 and the monitor's
    quantizer is bin-identical to the capture's — while traffic the
    calibration never saw lands in rewritten bins.  This is the retune
    trigger signal."""
    cfg, params, calib = drift_model
    in_frac = _served_dontcare_frac(
        cfg, params, calib,
        synthetic_batches(cfg, 2, batch_size=2, seq_len=8, seed=1))
    ood_frac = _served_dontcare_frac(
        cfg, params, calib,
        synthetic_batches(cfg, 2, batch_size=2, seq_len=8, seed=9))
    assert in_frac == 0.0, in_frac
    assert ood_frac > 0.0, ood_frac


# =========================================================================
# serving invariants
# =========================================================================
@pytest.fixture(scope="module")
def served_model():
    cfg = smoke_config(get_config("qwen3-0.6b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    batches = synthetic_batches(cfg, 2, batch_size=2, seq_len=8, seed=1)
    calib = capture_model(params, cfg, batches, w_in=8)
    calib = calibration_from_capture(calib)
    plans = build_serving_plans(cfg, calib, w_out=8)
    return plans.patched_config(cfg), params, plans, calib


def _decode_tokens(cfg, params, tables, batch, n_new):
    t = batch["tokens"].shape[1]
    max_seq = t + n_new
    lg, cache = jax.jit(lambda p, x: prefill(
        p, cfg, x, max_seq=max_seq, lut_tables=tables))(params, batch)
    step = jax.jit(lambda p, c, tk, pos: decode_step(
        p, cfg, c, tk, pos, lut_tables=tables))
    tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
    out = []
    for i in range(n_new):
        out.append(np.asarray(tok)[:, 0].tolist())
        lg, cache = step(params, cache, tok, jnp.asarray(t + i))
        tok = jnp.argmax(lg[:, -1], -1).astype(jnp.int32)[:, None]
    return out


@pytest.mark.parametrize("backend", ["gather", "pallas"])
def test_token_identity_under_telemetry(served_model, backend):
    """Serving with the full telemetry stack (event log + drift monitor)
    on is token-for-token identical to serving with it off — the monitor
    observes, it never transforms."""
    cfg, params, plans, calib = served_model
    tables = plans.tables_for_model(backend=backend)
    rng = np.random.default_rng(7)
    batch = {k: jnp.asarray(v)
             for k, v in model_batch(cfg, rng, 2, 5).items()}
    plain = _decode_tokens(cfg, params, tables, batch, 3)
    tel = obs.Telemetry(events=obs.EventLog(),
                        monitor=obs.DontCareMonitor(calib))
    with tel:
        monitored = _decode_tokens(cfg, params, tables, batch, 3)
        tel.monitor.flush()
        assert sum(tel.monitor.lookups.values()) > 0  # it really watched
    assert monitored == plain
    # the drift rows were exported into the event log on exit
    assert any(r["event"] == "drift" for r in tel.events.records)


def test_disabled_telemetry_adds_zero_traced_ops(served_model):
    """Lowering the decode step without telemetry must contain no host
    callbacks; the same trace under an active monitor must contain them
    (the off-path really is one None check)."""
    cfg, params, plans, calib = served_model
    tables = plans.tables_for_model(backend="gather")
    rng = np.random.default_rng(8)
    batch = {k: jnp.asarray(v)
             for k, v in model_batch(cfg, rng, 2, 5).items()}
    cache_args = jax.eval_shape(
        lambda p, x: prefill(p, cfg, x, max_seq=8, lut_tables=tables),
        params, batch)[1]
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_args)
    tok = jnp.zeros((2, 1), jnp.int32)

    def lower():
        return jax.jit(lambda p, c, tk, pos: decode_step(
            p, cfg, c, tk, pos, lut_tables=tables)).lower(
            params, cache, tok, jnp.asarray(5)).as_text()

    assert "callback" not in lower()
    with obs.DontCareMonitor(calib):
        assert "callback" in lower()


def test_disabled_telemetry_spans_add_no_records_or_traced_ops(
        served_model):
    """With no telemetry entered, ``obs.span`` writes no event record and
    adds no op to the decode step, whether it wraps the lowering or sits
    inside the traced function."""
    cfg, params, plans, _ = served_model
    tables = plans.tables_for_model(backend="pallas")
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda p, x: prefill(p, cfg, x, max_seq=8,
                                            lut_tables=tables),
                       params, {"tokens": jnp.ones((2, 5), jnp.int32)})[1])
    tok = jnp.zeros((2, 1), jnp.int32)

    def step(p, c, tk, pos):
        return decode_step(p, cfg, c, tk, pos, lut_tables=tables)

    def spanned_step(p, c, tk, pos):
        with obs.span("traced"):
            return step(p, c, tk, pos)

    def lower(fn):       # the program, without the module's name
        return jax.jit(fn).lower(params, cache, tok, jnp.asarray(
            5)).as_text().split("\n", 1)[1]

    log = obs.EventLog()
    obs.Telemetry(events=log)            # built, never entered
    plain = lower(step)
    with obs.span("lower.decode", batch=2):
        around = lower(step)
    assert obs.current() is None
    assert around == plain == lower(spanned_step)
    assert "callback" not in plain
    assert [r["event"] for r in log.records] == ["obs_start"]


def _host_event_names(tmp_path, fn) -> list:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    return [ev.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_spans_reach_the_event_log_and_the_profile(tmp_path):
    """With telemetry on, a span writes its ``span_begin``/``span_end``
    records as before (monotonic ``t``, ``dur_s``) and, under the
    profiler, a ``repro:<name>`` annotation too."""
    tel = obs.Telemetry(events=obs.EventLog())

    def work():
        with tel:
            with obs.span("outer", tag="t") as sid:
                with obs.span("inner"):
                    obs.event("step", n=1)
        assert sid == "s0"

    names = _host_event_names(tmp_path, work)
    assert "repro:outer" in names and "repro:inner" in names
    recs = tel.events.records
    assert [r["event"] for r in recs] == [
        "obs_start", "span_begin", "span_begin", "step", "span_end",
        "span_end", "obs_end"]
    outer, inner = recs[1], recs[2]
    assert set(outer) == {"seq", "t", "event", "span_id", "name", "tag",
                          "crc"}
    assert (outer["name"], outer["tag"], outer["span_id"]) == (
        "outer", "t", "s0")
    assert inner["parent"] == "s0" and recs[3]["span"] == inner["span_id"]
    assert [r["span_id"] for r in recs[4:6]] == [inner["span_id"], "s0"]
    assert all(r["dur_s"] >= 0 for r in recs[4:6])
    ts = [r["t"] for r in recs]
    assert ts == sorted(ts)
    assert isinstance(recs[0]["wall_time"], float)


def test_event_log_records_metrics_footer(tmp_path):
    """Telemetry.finish lands the metrics snapshot in the footer and the
    Prometheus dump on disk, on every exit path."""
    path = str(tmp_path / "t.jsonl")
    tel = obs.Telemetry(events=obs.EventLog(path), prom_path=path + ".prom")
    with pytest.raises(SystemExit):
        with tel:
            obs.count("things_total", 3)
            obs.observe("lat_s", 0.25)
            raise SystemExit(2)
    records = obs.read_events(path)  # footer present despite SystemExit
    metrics = records[-1]["metrics"]
    assert metrics["things_total"][""] == 3
    assert metrics["lat_s"][""]["count"] == 1
    assert "things_total 3" in open(path + ".prom").read()


def test_obs_report_cli(tmp_path, capsys):
    from repro.launch.obs import main as obs_main

    path = str(tmp_path / "r.jsonl")
    tel = obs.Telemetry(events=obs.EventLog(path))
    with tel:
        with obs.span("work"):
            obs.event("step", n=1)
        tel.event("drift", site="L0/mlp", lookups=10, dontcare_hits=1,
                  served_dontcare_frac=0.1, calib_dontcare_frac=0.0,
                  excess=0.1)
    assert obs_main([path]) == 0
    out = capsys.readouterr().out
    assert "== timeline ==" in out and "> work" in out
    assert "L0/mlp" in out and "drift" in out

    # a corrupted log is a hard failure
    lines = open(path).read().splitlines()
    open(path, "w").write("\n".join(lines)[:-30])
    assert obs_main([path]) == 1


def test_structured_logger_mirrors_to_events(capsys):
    from repro.obs.log import log

    log.info("plain", "no telemetry active")  # print-only, must not raise
    tel = obs.Telemetry(events=obs.EventLog())
    with tel:
        log.info("prefill", "prefill 2x8: 0.5s", seconds=0.5)
        log.error("boom", "something failed")
    out = capsys.readouterr()
    assert "prefill 2x8: 0.5s" in out.out
    assert "something failed" in out.err
    recs = {r["event"]: r for r in tel.events.records}
    assert recs["prefill"]["seconds"] == 0.5
    assert recs["prefill"]["level"] == "info"
    assert recs["boom"]["level"] == "error"
