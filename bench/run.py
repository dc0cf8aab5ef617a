#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); the configuration names its
architecture module (``bench/architectures/<name>.py``: sizes, weights,
work counts) and its plain reference (``bench/references/<name>.py``);
the metrics are read by ``bench/metrics/<name>.py`` and the correctness
limits sit in ``bench/limits/<cell>.json``.  Set-up makes the weights
from the seed, calibrates and compresses the LUT tables, and warms every
shape the window uses; then the window runs for ``--seconds``
(``--trace 1``: at most 15 s, under the profiler), under a bare
``repro.obs.Telemetry`` whose counters the readers get.  After the
window the program's state is freed and the plain reference checks a
sample of what was served.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``), and ``checks`` last: each number compared with its
limit.  The same numbers are the last lines of standard error.  Without
a TPU, or with fewer chips than the cell asks for, the run exits 3 and
prints no result.

``--readings N`` reads the compared numbers of the program and of the
control (the reference with fp8 projections) on seeds ``seed ..
seed+N-1`` in one process, one JSON line per seed: the readings limits
are set from.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# The compile cache sits at a fixed path inside the checkout (the path is
# part of the cache key), the one ``repro.launch.compile_cache`` uses.
CACHE = ".jax_cache"
TRACE_SECONDS = 15.0

if __name__ == "__main__":     # before JAX is imported
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / CACHE)
for _p in (str(BENCH), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import check, device, loops, model, readers  # noqa: E402
from harness import system, trace, traffic  # noqa: E402


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--readings", type=int, default=0,
                    help="read program and control numbers on this many "
                         "seeds instead of a measured run")
    return ap.parse_args(argv)


class Cell:
    """One cell's files, resolved from the manifest."""

    def __init__(self, root: Path, bench: Path, name: str):
        self.manifest = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        self.spec = cells[name]
        self.name = name
        self.bench = bench
        self.conf = model.load_config(self.spec["config"], bench)
        self.mix = traffic.load_traffic(self.spec["traffic"], bench)
        self.arch = model.load_architecture(self.conf, bench)
        self.m = self.arch.dims(self.conf)
        self.vocab = self.m["V"]

    def metric_specs(self, per_layer: bool) -> list:
        key = "per_layer" if per_layer else "end_to_end"
        return [s for s in self.manifest[key]
                if self.name in s.get("workloads", [self.name])]


class Setup:
    """Weights, tables and (open loop) the warmed batcher of one seed."""

    def __init__(self, cell: Cell, seed: int, seconds: float,
                 rate: float | None = None):
        import jax

        mix, conf = cell.mix, cell.conf
        cfg = cell.arch.arch_config(conf)
        with system.span("weights"):
            self.params = jax.block_until_ready(
                cell.arch.program_params(conf, seed))
        serving = conf.get("serving", {})
        (self.lut_cfg, self.tables, self.site_bytes,
         self.calib_s) = system.calibrate(
            cfg, self.params,
            traffic.calibration_batch(mix, cell.vocab, seed),
            backend=serving.get("backend", "pallas"),
            plan_exec=serving.get("plan_exec", "stacked"))
        self.bat = None
        self.arrivals = None
        with system.span("warm_up"):
            if mix["kind"] == "offline":
                loops.warm_offline(self.lut_cfg, self.params, self.tables,
                                     mix, cell.vocab, seed)
            else:
                self.arrivals = traffic.open_loop(mix, cell.vocab, seed,
                                                  seconds, rate)
                self.bat = system.Batcher(
                    self.lut_cfg, self.params, self.tables,
                    slots=mix["slots"], max_seq=mix["max_seq"],
                    prefill=mix.get("prefill", "replay"))
                loops.warm_open_loop(self.bat, mix, self.arrivals,
                                       cell.vocab)

    def free(self) -> None:
        import jax

        self.params = self.tables = self.bat = None
        gc.collect()
        jax.clear_caches()


def window(cell: Cell, st: Setup, seed: int, seconds: float,
           tracer: trace.Tracer, counter: loops.CompileCounter):
    """Run the window under a bare ``repro.obs.Telemetry`` (counters in
    memory; no event log, no monitor); returns ``(calls, loop, window_s,
    counters)``, ``counters`` the registry's snapshot at the close."""
    from repro import obs

    tel = obs.Telemetry()
    counters = {}

    def start():
        counter.start()
        tracer.start()

    def close():
        counter.stop()
        tracer.stop()
        counters.update(tel.registry.snapshot())

    with tel:
        if cell.mix["kind"] == "offline":
            calls, win = loops.run_offline(
                st.lut_cfg, st.params, st.tables, cell.mix, cell.vocab,
                seed, seconds, on_start=start)
            close()
            return calls, None, win, counters
        loop = loops.run_open_loop(st.bat, st.arrivals, seconds,
                                     cell.mix.get("drain_s", 60.0),
                                     on_start=start, on_close=close)
    return None, loop, loop.window_s, counters


def sample_of(cell: Cell, seed: int, calls, loop):
    if calls is not None:
        return check.offline_sample(calls, cell.mix, cell.vocab, seed)
    return check.open_loop_sample(loop.served, cell.mix, seed)


def readings(cell: Cell, args) -> int:
    """The compared numbers of the program and of the control."""
    rows = []
    for i in range(args.readings):
        seed = args.seed + i
        st = Setup(cell, seed, args.seconds)
        calls, loop, _, _ = window(cell, st, seed, args.seconds,
                                   trace.Tracer(False),
                                   loops.CompileCounter())
        st.free()
        sample = sample_of(cell, seed, calls, loop)
        row = {"seed": seed, **check.compare(cell.conf, cell.bench, seed,
                                             sample, control=True)}
        if loop is not None:
            row["finished"] = sum(s.done for s in loop.served)
            row["attempted"] = len(loop.served)
        rows.append(row)
        print(json.dumps(row), flush=True)
    worst = {k: max(r[k] for r in rows) for k in rows[0]
             if k.endswith("_logit_gap")}
    least = {k: min(r[k] for r in rows) for k in rows[0]
             if k.startswith("control_") and k.endswith("_logit_gap")}
    print(json.dumps({"cell": cell.name, "seeds": len(rows),
                      "largest": worst, "smallest_control": least}))
    return 0


def main(argv=None, *, root: Path = ROOT, bench: Path | None = None,
         require_tpu: bool = True, peaks: dict | None = None) -> int:
    args = _parse(argv)
    bench = bench or root / "bench"
    cell = Cell(root, bench, args.workload)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / CACHE)
    jax.config.update("jax_compilation_cache_dir", str(root / CACHE))
    enable_compile_cache()
    try:
        devices = (device.require_chips(cell.spec["chips"]) if require_tpu
                   else jax.devices()[:cell.spec["chips"]])
    except device.NoChip as e:
        say(f"bench: {e}")
        return 3
    pk = peaks or device.peaks(devices[0].device_kind)
    if args.readings:
        return readings(cell, args)

    started = device.process_start_time()
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
    in_setup = loops.CompileCounter()
    in_setup.start()
    st = Setup(cell, args.seed, seconds)
    setup_s = time.time() - started
    in_setup.stop()
    tracer = trace.Tracer(bool(args.trace))
    counter = loops.CompileCounter()
    try:
        calls, loop, win, counters = window(cell, st, args.seed, seconds,
                                            tracer, counter)
        dev = device.record(devices)
        reduced = tracer.reduced_now() if args.trace else None
    finally:
        tracer.cleanup()
    calib_s, site_bytes = st.calib_s, st.site_bytes
    st.free()

    t_ref = time.perf_counter()
    sample = sample_of(cell, args.seed, calls, loop)
    numbers = (check.compare(cell.conf, bench, args.seed, sample)
               if sample is not None else {})
    ref_s = time.perf_counter() - t_ref
    correct, checks = check.judge(numbers,
                                  check.load_limits(bench, cell.name))

    if calls is not None:
        attempted, failed = len(calls) * cell.mix["batch"], 0
    else:
        attempted = len(loop.served)
        failed = sum(not s.done for s in loop.served)
    correct = correct and failed == 0
    run = readers.Run(
        kind=cell.mix["kind"], arch=cell.arch, m=cell.m, peaks=pk,
        mix=cell.mix, setup_s=setup_s, calib_s=calib_s,
        site_bytes=site_bytes, window_s=win, calls=calls, loop=loop,
        trace=reduced, counters=counters)
    metrics = readers.read_all(bench, cell.metric_specs(bool(args.trace)),
                               run)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"] = reduced.busy_s()
        dev["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops(10),
                               "idle_gaps": reduced.named_gaps(10)}
    result["checks"] = checks

    say(f"bench: {cell.name} seed {args.seed}: window {win:.3f} s, "
        f"set-up {setup_s:.3f} s (calibration {calib_s:.3f} s), "
        f"reference {ref_s:.3f} s")
    say(f"bench: compiles in set-up: {in_setup.counts}; in the window: "
        f"{counter.counts}")
    say(f"bench: served table bytes a layer {site_bytes}; counters at "
        f"the window's close {counters}")
    if calls is not None:
        say(f"bench: {len(calls)} calls, {attempted} sequences")
        say("bench: calls (s, call/prefill/decode/rest): " + " ".join(
            f"{c.t1 - c.t0:.4f}/{c.prefill_s:.4f}/{c.decode_s:.4f}/"
            f"{c.t1 - c.t0 - c.prefill_s - c.decode_s:.4f}" for c in calls))
    else:
        late = sorted(loop.late_s)
        say(f"bench: {attempted} requests due, "
            f"{attempted - failed} answered, drained "
            f"{loop.drained_s:.3f} s after the window; generator late by "
            f"at most {late[-1] if late else 0.0:.6f} s")
    say(f"bench: compared {numbers}")
    for name, c in checks.items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
