"""Benchmark entry point. One section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (scaffold contract).
Env: REPRO_BENCH_SCALE=small|paper (default small); paper scale reruns
the full Table-1 model sizes and takes much longer.
"""
from __future__ import annotations

import time


def main() -> None:
    from . import fig3, table2
    from .common import bench_scale

    print(f"# ReducedLUT benchmarks (scale={bench_scale()})")
    rows: list[tuple[str, float, str]] = []

    print("## Table 2: P-LUT utilization / accuracy (paper SS5.2)")
    t0 = time.time()
    t2, timing = table2.run()
    for r in t2:
        name = f"table2_{r['model']}_{r['method']}" + (
            f"_ex{r['exiguity']}" if r["exiguity"] else "")
        derived = (f"pluts={r['pluts']};test_acc={r['test_acc']:.4f};"
                   f"train_acc={r['train_acc']:.4f}")
        if "vs_baseline" in r:
            derived += f";vs_baseline={r['vs_baseline']}"
        if "vs_compressedlut" in r:
            derived += f";vs_compressedlut={r['vs_compressedlut']}"
        rows.append((name, r["seconds"] * 1e6, derived))
    for t in timing:
        rows.append((
            f"table2_engine_{t['model']}_w{t['workers']}",
            t["engine_s"] * 1e6,
            f"serial_s={t['serial_s']};speedup={t['speedup']};"
            f"identical={t['identical']}",
        ))
    print(f"  [table2 {time.time() - t0:.0f}s]")

    print("## Fig 3: exiguity sweep")
    f3 = fig3.run("jsc-2l")
    for r in f3:
        rows.append((
            f"fig3_jsc-2l_ex{r['exiguity']}", r["seconds"] * 1e6,
            f"pluts={r['pluts']};test_acc={r['test_acc']:.4f}",
        ))

    print("## Beyond-paper variants (bias_care_only / multi-sweep)")
    from . import beyond
    for r in beyond.run("jsc-2l"):
        rows.append((f"beyond_{r['model']}_{r['variant']}",
                     r["seconds"] * 1e6, f"pluts={r['pluts']}"))

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
