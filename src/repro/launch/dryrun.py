"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

``main`` gives the CPU backend 512 virtual devices before the first device
query (XLA reads ``XLA_FLAGS`` when its client starts, so importing this
module changes nothing).  For each cell we build the production mesh, lower
the appropriate step (train_step / prefill / serve_step) against
ShapeDtypeStruct inputs — no allocation — compile it, and record
memory_analysis / cost_analysis / collective bytes for EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b \
      --shape train_4k [--multi-pod] [--out experiments/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import os
import time
import traceback

import jax
import numpy as np

from repro.configs import ARCH_NAMES, get_config
from repro.launch.mesh import make_production_mesh
from repro.roofline import analyze_compiled, model_flops_per_step

SHAPES = {
    "train_4k": dict(kind="train", seq=4096, batch=256),
    "prefill_32k": dict(kind="prefill", seq=32768, batch=32),
    "decode_32k": dict(kind="decode", seq=32768, batch=128),
    "long_500k": dict(kind="decode", seq=524288, batch=1),
}


def cell_supported(cfg, shape: str) -> tuple[bool, str]:
    if shape == "long_500k" and not cfg.supports_long_context:
        return False, "full attention at 524k decode is O(T) cache: skipped per assignment (noted in DESIGN.md)"
    return True, ""


def _train_lowered(cfg, mesh, seq, batch, tcfg=None):
    from repro.train import TrainConfig, abstract_train_state, input_batch_specs
    from repro.train.step import make_train_step

    dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp *= mesh.shape[a]
    tcfg = tcfg or TrainConfig(microbatch=max(1, batch // dp), remat=True)
    step, jit_step, state_sh = make_train_step(cfg, tcfg, mesh)
    specs = input_batch_specs(cfg, batch, seq)
    state = abstract_train_state(cfg, tcfg)
    return jit_step(specs).lower(state, specs)


def _prefill_lowered(cfg, mesh, seq, batch):
    from repro.nn.transformer import init_params, param_specs
    from repro.train.step import input_batch_specs, make_prefill
    from repro.nn.sharding import named_sharding

    fn = make_prefill(cfg, mesh)
    specs = input_batch_specs(cfg, batch, seq)
    specs.pop("labels")
    pspecs = param_specs(cfg, mesh, fsdp=False)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    bsh = {
        k: named_sharding(mesh, "dp", *(None,) * (len(v.shape) - 1),
                          shape=v.shape)
        for k, v in specs.items()
    }
    return jax.jit(fn, in_shardings=(pspecs, bsh)).lower(params, specs)


def _decode_lowered(cfg, mesh, seq, batch, lut_tables=None):
    from repro.nn.transformer import init_params
    from repro.serve.kvcache import cache_specs
    from repro.train.step import make_serve_step

    step, jit_step = make_serve_step(cfg, mesh, lut_tables=lut_tables)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = cache_specs(cfg, batch, seq)
    tokens = jax.ShapeDtypeStruct((batch, 1), np.int32)
    pos = jax.ShapeDtypeStruct((), np.int32)
    return jit_step(batch, seq).lower(params, cache, tokens, pos)


def _lut_plan(cfg, mesh):
    """Shared-calibration serving plans for LUT-aware decode dry-runs:
    returns ``(patched_cfg, lut_tables, placement_report)`` where the
    report prices the tables *per device* on this mesh (replicated slabs
    cost full bytes everywhere; layer-sharded stacks cost 1/|data| each)."""
    from repro.serve import build_serving_plans
    from repro.serve.sharded import plan_placement_report

    calib = np.random.default_rng(0).normal(size=100000) * 3
    plans = build_serving_plans(cfg, calib)
    tables = plans.tables_for_model(mesh=False)
    return (plans.patched_config(cfg), tables,
            plan_placement_report(tables, mesh))


def dryrun_cell(arch: str, shape: str, multi_pod: bool,
                tcfg=None, quiet: bool = False,
                lut_act: bool = False) -> dict:
    cfg = get_config(arch)
    info = SHAPES[shape]
    ok, why = cell_supported(cfg, shape)
    result = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": info["kind"],
    }
    if not ok:
        result["status"] = "skipped"
        result["reason"] = why
        return result
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        lut_tables = None
        if lut_act and info["kind"] == "decode":
            cfg, lut_tables, report = _lut_plan(cfg, mesh)
            result["lut_tables"] = report
            if not quiet:
                print(f"  lut tables: {report['replicated_bytes']} B "
                      f"replicated + {report['sharded_bytes']} B "
                      f"layer-sharded = {report['per_device_bytes']} B "
                      f"per device")
        if info["kind"] == "train":
            lowered = _train_lowered(cfg, mesh, info["seq"], info["batch"],
                                     tcfg)
        elif info["kind"] == "prefill":
            lowered = _prefill_lowered(cfg, mesh, info["seq"], info["batch"])
        else:
            lowered = _decode_lowered(cfg, mesh, info["seq"], info["batch"],
                                      lut_tables=lut_tables)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

        mem = compiled.memory_analysis()
        mem_d = {}
        if mem is not None:
            for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                         "temp_size_in_bytes", "generated_code_size_in_bytes",
                         "alias_size_in_bytes"):
                v = getattr(mem, attr, None)
                if v is not None:
                    mem_d[attr] = int(v)
        terms = analyze_compiled(compiled)
        n_chips = int(np.prod(list(mesh.shape.values())))
        result.update({
            "status": "ok",
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "memory": mem_d,
            "roofline": terms.as_dict(),
            "model_flops": model_flops_per_step(
                cfg, info["batch"], info["seq"], info["kind"]),
            "n_chips": n_chips,
        })
        if not quiet:
            print(f"  lower {t_lower:.1f}s compile {t_compile:.1f}s "
                  f"dominant={terms.dominant} "
                  f"compute={terms.compute_s:.2e}s "
                  f"memory={terms.memory_s:.2e}s "
                  f"coll={terms.collective_s:.2e}s")
    except Exception as e:  # noqa: BLE001 — report failures per cell
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["trace"] = traceback.format_exc()[-2000:]
    return result


def main() -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--lut-act", action="store_true",
                    help="decode cells serve shared-calibration LUT plans "
                         "and report per-device table bytes "
                         "(replicated vs layer-sharded)")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = []
    archs = ARCH_NAMES if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}"
                if args.lut_act:
                    tag += "__lut"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    with open(path) as f:
                        prev = json.load(f)
                    if prev.get("status") in ("ok", "skipped"):
                        print(f"[cached] {tag}: {prev['status']}")
                        cells.append(prev)
                        continue
                print(f"[dryrun] {tag}")
                res = dryrun_cell(arch, shape, mp, lut_act=args.lut_act)
                cells.append(res)
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                print(f"  -> {res['status']}"
                      + (f" ({res.get('error')})"
                         if res["status"] == "error" else ""))
    n_ok = sum(1 for c in cells if c["status"] == "ok")
    n_skip = sum(1 for c in cells if c["status"] == "skipped")
    n_err = len(cells) - n_ok - n_skip
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped, {n_err} errors "
          f"/ {len(cells)} cells")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
