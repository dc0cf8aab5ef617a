#!/usr/bin/env python3
"""Record a short profiler trace of a cell's timed path and print what is
in it: each plane and line, and the device ops that took most time with
their stats.  The trace file is kept under ``--out``.

    python3 bench/tools/inspect_trace.py --workload <cell> --seed <n> \
        --calls 2 --out <dir>

For an offline cell it traces ``--calls`` calls of the cell's shapes,
or with ``--batch``/``--prompt-len``/``--new-tokens`` smaller ones (a
small trace for the reduction's tests).  Run on the chip.
"""
from __future__ import annotations

import argparse
import glob
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
    Path(__file__).resolve().parents[2] / ".jax_cache")

import run  # noqa: E402
from harness import system, trace, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=2)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--prompt-len", type=int)
    ap.add_argument("--new-tokens", type=int)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    from jax.profiler import ProfileData

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = run.Cell(run.ROOT, run.BENCH, args.workload)
    mix = dict(cell.mix)
    for key in ("batch", "prompt_len", "new_tokens"):
        if getattr(args, key) is not None:
            mix[key] = getattr(args, key)
    cell.mix = mix
    st = run.Setup(cell, args.seed, 1.0)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out), profiler_options=opts)
    with system.span("window"):
        for i in range(args.calls):
            batch = traffic.offline_batch(mix, cell.vocab, args.seed, i)
            system.generate(st.lut_cfg, st.params, st.tables, batch,
                            mix["new_tokens"])
    jax.profiler.stop_trace()
    path = sorted(glob.glob(str(out / "plugins/profile/*/*.xplane.pb")))[-1]
    print(f"trace {path} ({os.path.getsize(path)} bytes)")
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"plane {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            if plane.name.startswith("/device:") and evs:
                tot: dict = {}
                first: dict = {}
                for ev in evs:
                    tot[ev.name] = tot.get(ev.name, 0) + ev.duration_ns
                    first.setdefault(ev.name, ev)
                for name, ns in sorted(tot.items(), key=lambda kv: -kv[1])[:25]:
                    ev = first[name]
                    stats = {k: (v if not isinstance(v, str) else v[:160])
                             for k, v in ev.stats}
                    print(f"    {ns / 1e6:10.3f} ms  {name[:80]}  {stats}")
    red = trace.reduce_file(path)
    print(f"reduced: window {red.window_s:.6f} s busy {red.busy_s():.6f} s "
          f"idle {red.idle_share():.4f}")
    print(f"top ops {red.top_ops(10)}")
    print(f"gaps {red.named_gaps(10)}")
    ops = red.ops[0] if red.ops else []
    print(f"device ops in window: {len(ops)}; first {ops[:1]}, last "
          f"{ops[-1:]}; window {red.window}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
