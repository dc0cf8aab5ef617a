#!/usr/bin/env python3
"""Find an open-loop cell's knee: offer each rate for a window and report
the queue at the window's close.

    python3 bench/tools/knee_sweep.py --workload qwen3-0.6b.chat \
        --seed 1 --seconds 30 --rates 1.0,1.5,2.0,2.5,3.0

One process sets the cell up once (weights, tables, a batcher warmed for
every prompt length the mix can draw), then runs the window at each rate
in turn, draining between rates.  The knee is the highest rate whose
queue does not grow over the window.  Run on the chip; one JSON line per
rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
    Path(__file__).resolve().parents[2] / ".jax_cache")

import run  # noqa: E402
from harness import loops, readers, traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    rates = [float(r) for r in args.rates.split(",")]
    cell = run.Cell(run.ROOT, run.BENCH, args.workload)
    mix = cell.mix
    st = run.Setup(cell, args.seed, args.seconds, max(rates))
    bat = st.bat
    # warm every prompt length the mix can draw, at any rate
    every = [traffic.Arrival(rid=0, due_s=0.0, prompt=[1] * n, max_new=1)
             for n in traffic.distinct_prompt_lengths(mix)]
    loops.warm_open_loop(bat, mix, every, cell.vocab)
    for i, rate in enumerate(rates):
        arrivals = traffic.open_loop(mix, cell.vocab, args.seed + 1 + i,
                                     args.seconds, rate)
        at_close = {}

        def close():
            at_close["queued"] = len(bat.b.queue)
            at_close["active"] = bat.b.n_active

        loop = loops.run_open_loop(bat, arrivals, args.seconds,
                                     mix.get("drain_s", 60.0),
                                     on_close=close)
        while bat.busy:                       # drain fully before the next
            bat.tick()
        bat.b.finished.clear()
        done_in = sum(1 for s in loop.served
                      if s.done and s.token_s
                      and s.token_s[-1] <= loop.window_s)
        ttft = [(s.token_s[0] - s.arrival.due_s) if s.token_s
                else float("inf") for s in loop.served]
        gaps = [b - a for s in loop.served
                for a, b in zip(s.token_s, s.token_s[1:])]
        print(json.dumps({
            "rate": rate, "sent": len(arrivals),
            "finished_in_window": done_in, **at_close,
            "drained_s": loop.drained_s,
            "ttft_p50_ms": 1e3 * readers.percentile(ttft, 0.5),
            "ttft_p90_ms": 1e3 * readers.percentile(ttft, 0.9),
            "itl_p95_ms": (1e3 * readers.percentile(gaps, 0.95)
                           if gaps else None),
            "ticks": loop.ticks,
            "calls_per_tick": loop.decode_calls / max(1, loop.ticks),
            "late_max_s": max(loop.late_s) if loop.late_s else 0.0}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
