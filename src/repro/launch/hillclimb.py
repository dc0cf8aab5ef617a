"""Perf hillclimbing driver (EXPERIMENTS.md SSPerf).

Runs named variants of the three selected (arch x shape) cells, re-lowers
and re-analyzes each, and records the roofline terms next to the cached
baselines.  Each variant is an explicit hypothesis — see EXPERIMENTS.md
for the hypothesis -> change -> before/after -> verdict log.

Usage:
  PYTHONPATH=src python -m repro.launch.hillclimb [--only rwkv6-3b]
"""
import argparse
import json
import os
import time

import numpy as np


def run_variant(arch, shape, name, *, microbatch=None, fast_stream=False,
                kv_dtype="bfloat16", lut_act=False, grad_compress=False,
                wkv_chunk=None, seq_parallel=False):
    import jax
    from repro.configs import get_config
    from repro.launch.dryrun import SHAPES, _train_lowered
    from repro.launch.mesh import make_production_mesh
    from repro.nn.layers import set_fast_stream
    from repro.nn.sharding import set_seq_parallel
    from repro.nn.ssm import set_wkv_chunk
    from repro.roofline import analyze_compiled, model_flops_per_step
    from repro.train import TrainConfig

    cfg = get_config(arch)
    info = SHAPES[shape]
    mesh = make_production_mesh()
    set_fast_stream(fast_stream)
    set_seq_parallel(seq_parallel)
    if wkv_chunk:
        set_wkv_chunk(wkv_chunk)
    try:
        t0 = time.time()
        if info["kind"] == "train":
            tcfg = TrainConfig(
                microbatch=microbatch, remat=True,
                grad_compress=grad_compress,
            )
            lowered = _train_lowered(cfg, mesh, info["seq"], info["batch"],
                                     tcfg)
        else:
            from repro.nn.transformer import init_params
            from repro.serve.kvcache import cache_specs
            from repro.train.step import make_serve_step

            lut_tables = None
            if lut_act:
                from repro.nn.lut_act import build_lut_activation
                import dataclasses

                calib = np.random.default_rng(0).normal(size=200000) * 2.5
                lut = build_lut_activation(
                    "relu2" if cfg.activation == "relu2" else "silu",
                    calib, w_in=10, w_out=10, x_lo=-8.0, x_hi=8.0)
                lut_tables = lut.tables_for_model()
                cfg = dataclasses.replace(cfg, lut_activation=True)
            step, jit_step = make_serve_step(cfg, mesh, kv_dtype=kv_dtype,
                                             lut_tables=lut_tables)
            params = jax.eval_shape(
                lambda: init_params(cfg, jax.random.PRNGKey(0)))
            cache = cache_specs(cfg, info["batch"], info["seq"], kv_dtype)
            tokens = jax.ShapeDtypeStruct((info["batch"], 1), np.int32)
            pos = jax.ShapeDtypeStruct((), np.int32)
            lowered = jit_step(info["batch"], info["seq"]).lower(
                params, cache, tokens, pos)
        compiled = lowered.compile()
        terms = analyze_compiled(compiled)
        res = {
            "arch": arch, "shape": shape, "variant": name,
            "status": "ok",
            "compile_s": round(time.time() - t0, 1),
            "roofline": terms.as_dict(),
            "model_flops": model_flops_per_step(
                get_config(arch), info["batch"], info["seq"], info["kind"]),
            "n_chips": 256,
        }
        print(f"  [{arch} {shape} {name}] compute={terms.compute_s:.3e} "
              f"memory={terms.memory_s:.3e} coll={terms.collective_s:.3e} "
              f"dominant={terms.dominant}")
    except Exception as e:  # noqa: BLE001
        import traceback
        res = {"arch": arch, "shape": shape, "variant": name,
               "status": "error", "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-1500:]}
        print(f"  [{arch} {shape} {name}] ERROR {res['error'][:120]}")
    finally:
        set_fast_stream(False)
        set_seq_parallel(False)
        set_wkv_chunk(64)
    out_dir = "experiments/hillclimb"
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{arch}__{shape}__{name}.json"),
              "w") as f:
        json.dump(res, f, indent=1)
    return res


EXPERIMENTS = [
    # H1 — worst roofline fraction: rwkv6-3b train_4k (baseline 0.0146)
    ("rwkv6-3b", "train_4k", "v1_micro4", dict(microbatch=4)),
    ("rwkv6-3b", "train_4k", "v2_micro4_fast",
     dict(microbatch=4, fast_stream=True)),
    ("rwkv6-3b", "train_4k", "v3_micro2_fast",
     dict(microbatch=2, fast_stream=True)),
    # iter2: pairwise decay tensor traffic is linear in the WKV chunk
    ("rwkv6-3b", "train_4k", "v4_chunk16", dict(wkv_chunk=16)),
    ("rwkv6-3b", "train_4k", "v5_chunk8", dict(wkv_chunk=8)),
    # closing iterations (stopping rule: 3 consecutive <5%)
    ("rwkv6-3b", "train_4k", "v6_chunk4", dict(wkv_chunk=4)),
    # H2 — most collective-bound: deepseek-67b train_4k (coll 58.7s)
    ("deepseek-67b", "train_4k", "v1_micro8", dict(microbatch=8)),
    ("deepseek-67b", "train_4k", "v2_micro8_fast",
     dict(microbatch=8, fast_stream=True)),
    # iter3: Megatron sequence parallelism — AR -> RS + AG
    ("deepseek-67b", "train_4k", "v3_sp", dict(seq_parallel=True)),
    ("deepseek-67b", "train_4k", "v4_sp_fast",
     dict(seq_parallel=True, fast_stream=True)),
    # H3 — paper-representative: nemotron decode_32k serving path
    ("nemotron-4-15b", "decode_32k", "v1_fast", dict(fast_stream=True)),
    ("nemotron-4-15b", "decode_32k", "v2_fast_int8",
     dict(fast_stream=True, kv_dtype="int8")),
    ("nemotron-4-15b", "decode_32k", "v3_fast_int8_lut",
     dict(fast_stream=True, kv_dtype="int8", lut_act=True)),
]


def main() -> None:
    # 512 virtual CPU devices for the production mesh; set before the
    # first device query, which is when XLA reads the flag
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--skip-cached", action="store_true")
    args = ap.parse_args()
    for arch, shape, name, kw in EXPERIMENTS:
        if args.only and args.only not in arch:
            continue
        path = f"experiments/hillclimb/{arch}__{shape}__{name}.json"
        if args.skip_cached and os.path.exists(path):
            with open(path) as f:
                if json.load(f).get("status") == "ok":
                    print(f"  [cached] {arch} {shape} {name}")
                    continue
        run_variant(arch, shape, name, **kw)


if __name__ == "__main__":
    main()
