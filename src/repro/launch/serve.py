"""Production serving launcher: batched prefill + greedy decode.

Offline this serves any --arch at smoke scale on the host; on a cluster
the same step functions lower onto the production mesh (see dryrun.py for
the compile-only proof at 256/512 chips).  Supports the int8 KV cache and
ReducedLUT-compressed activations (the paper feature).

  PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b \
      --batch 4 --prompt-len 48 --new-tokens 16 [--kv-int8] [--lut-act] \
      [--lut-backend gather|pallas] [--plan-exec stacked|unrolled] \
      [--calib-steps N] [--calib-path P] [--tuned-plan T]

``--lut-act`` serves engine-selected plans: every activation site of the
network is compressed through the batched engine (duplicate tables shared
— see the dedupe hit-rate it prints) and the decode loop evaluates the
resulting plan arrays.  By default all sites share one synthetic
calibration set; ``--calib-steps N`` instead streams N batches through
the exact model and derives *per-site* observed-pattern don't-care masks
(repro.calib), so each layer serves its own table — by default as one
stacked ``(L, …)`` array family the layer scan indexes in place
(``--plan-exec stacked``; ``unrolled`` keeps the python-unrolled
reference with its O(L) compile time).  ``--calib-path`` loads a saved
calibration artifact when present and saves the captured one otherwise,
so restarts skip recapture.

``--tuned-plan`` serves a :mod:`repro.tune` artifact (the output of
``launch/tune``): the autotuner's Pareto-selected per-site plans are
loaded bit-exactly from disk — no capture and no compression run at all —
and decode token-identically to the in-process tuning run.
"""
from __future__ import annotations

import argparse
import os
import time
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.obs.log import log
from repro.calib import (
    capture_calibration,
    load_calibration,
    model_batch,
    save_calibration,
    synthetic_batches,
)
from repro.configs import ARCH_NAMES, get_config, smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.nn import init_params
from repro.serve import ShardedServe, build_serving_plans, generate


def main() -> None:
    ap = _build_parser()
    args = ap.parse_args()
    enable_compile_cache()
    tel = None
    if args.obs_log:
        tel = obs.Telemetry(
            events=obs.EventLog(args.obs_log, sample=args.obs_sample),
            prom_path=args.obs_log + ".prom")
    # the with-block guarantees the JSONL footer + Prometheus dump land
    # even on sys.exit/ap.error paths inside _main
    with tel if tel is not None else nullcontext():
        _main(ap, args, tel)


def _build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="phi4-mini-3.8b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--lut-act", action="store_true")
    ap.add_argument("--lut-backend", choices=("gather", "pallas"),
                    default="gather")
    ap.add_argument("--plan-exec", choices=("stacked", "unrolled"),
                    default="stacked",
                    help="per-layer table execution: stacked (L, ...) "
                         "arrays inside lax.scan (default) or the "
                         "python-unrolled reference")
    ap.add_argument("--lut-fuse", action="store_true",
                    help="fuse the LUT hot path (pallas backend, single "
                         "device): bit-packed multi-site table slabs, "
                         "single-grid multi-site kernel, and the LUT "
                         "activation applied in the MLP/FFN matmul "
                         "epilogue (cfg.lut_fuse) — token-identical to "
                         "the unfused path by the bit-identity contract")
    ap.add_argument("--lut-sites", choices=("act", "all"), default="act",
                    help="LUT site scope: act (the activation sites only, "
                         "the default) or all (every registered site — "
                         "softmax exp, norm rsqrt, logit softcap, rope)")
    ap.add_argument("--logit-softcap", type=float, default=None,
                    help="tanh soft-cap the final logits at this scale "
                         "(enables the network-global softcap LUT site)")
    ap.add_argument("--calib-steps", type=int, default=0,
                    help="capture N batches for per-site don't-care masks "
                         "(0 = shared synthetic calibration)")
    ap.add_argument("--calib-path", default=None,
                    help="calibration artifact (.npz): loaded if present, "
                         "else saved after capture")
    ap.add_argument("--calib-min-count", type=int, default=1,
                    help="min observations for a bin to stay care")
    ap.add_argument("--calib-smoothing", type=int, default=0,
                    help="laplace-style neighbor-smoothing radius (bins)")
    ap.add_argument("--tuned-plan", default=None,
                    help="tuned-plan artifact (.npz) from launch/tune: "
                         "serve its plans directly, skipping capture and "
                         "compression (implies --lut-act)")
    ap.add_argument("--save-plan", default=None, metavar="PATH",
                    help="freeze the built serving plans into a tuned-plan "
                         "artifact at PATH")
    ap.add_argument("--mesh", default=None, metavar="DP,TP",
                    help="serve on a (data, model) host mesh, e.g. 2,2 — "
                         "data-parallel batch x bit-exact tensor-parallel "
                         "model with placed LUT tables; DP*TP devices must "
                         "be visible (on the CPU: XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--mesh-mode", choices=("gspmd", "shard_map"),
                    default="gspmd",
                    help="sharded program form: gspmd partitioner "
                         "(default; layer-sharded table slabs) or a "
                         "fully-manual top-level shard_map (replicated "
                         "tables, lax.scan kept inside the region)")
    ap.add_argument("--obs-log", default=None, metavar="PATH",
                    help="write the structured telemetry event log "
                         "(repro-obs/v1 JSONL) to PATH; a Prometheus "
                         "text dump lands at PATH.prom on exit; with "
                         "calibrated LUT serving the don't-care drift "
                         "monitor is attached (token-identical output)")
    ap.add_argument("--obs-sample", type=int, default=1, metavar="N",
                    help="keep every Nth high-frequency tick event in "
                         "the obs log (counters and gauges are never "
                         "sampled; drops are accounted on the surviving "
                         "records)")
    ap.add_argument("--obs-drift-every", type=int, default=128,
                    metavar="N",
                    help="run the drift-monitored decode step on every "
                         "Nth batcher tick only (1 = count every step); "
                         "the monitor's callbacks are optimization "
                         "barriers in the jitted step, so sampling is "
                         "what keeps enabled-mode serving within the "
                         "5%% decode-overhead budget — the drift "
                         "fraction is a ratio and stays unbiased")
    ap.add_argument("--full", action="store_true")
    return ap


def _main(ap, args, tel) -> None:
    mesh = None
    if args.mesh:
        try:
            dp, tp = (int(v) for v in args.mesh.split(","))
        except ValueError:
            ap.error(f"--mesh expects DP,TP (e.g. 2,2), got {args.mesh!r}")
        try:
            mesh = make_host_mesh(dp, tp)
        except ValueError as e:
            ap.error(f"--mesh: {e}")
        if args.kv_int8 and args.mesh_mode == "shard_map":
            ap.error("--kv-int8 prefill replay is served in gspmd mesh "
                     "mode only (drop --kv-int8 or use --mesh-mode gspmd)")

    if args.lut_fuse:
        if args.lut_backend != "pallas":
            ap.error("--lut-fuse needs --lut-backend pallas (the fused "
                     "hot path is a Pallas kernel)")
        if mesh is not None:
            ap.error("--lut-fuse is the single-device fast path — drop "
                     "--mesh (the sharded program keeps the gather-"
                     "shardable unfused form)")
    lut_kernel = "fused" if (args.lut_fuse
                             and args.plan_exec == "stacked") else None

    cfg = get_config(args.arch)
    if not args.full:
        cfg = smoke_config(cfg)
    if (args.lut_sites != "act" or args.logit_softcap is not None
            or args.lut_fuse):
        import dataclasses

        cfg = dataclasses.replace(cfg, lut_sites=args.lut_sites,
                                  logit_softcap=args.logit_softcap,
                                  lut_fuse=args.lut_fuse)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    b, t = args.batch, args.prompt_len
    batch = {k: jnp.asarray(v)
             for k, v in model_batch(cfg, rng, b, t).items()}

    lut_tables = None
    plan_source = None   # ServingPlans built in-process, for --save-plan
    if args.tuned_plan:
        from repro.tune import load_tuned_plan

        if not (os.path.exists(args.tuned_plan)
                or os.path.exists(args.tuned_plan + ".npz")):
            ap.error(f"--tuned-plan: no artifact at {args.tuned_plan!r} — "
                     f"run launch/tune (or launch/serve --save-plan) to "
                     f"produce one")
        try:
            tp = load_tuned_plan(args.tuned_plan)
        except ValueError as e:   # includes ArtifactError (corrupt file)
            ap.error(f"--tuned-plan: {e}")
        cfg = tp.patched_config(cfg)   # binds artifact to this arch/depth
        lut_tables = tp.tables_for_model(backend=args.lut_backend,
                                         plan_exec=args.plan_exec,
                                         kernel=lut_kernel)
        log.info("tuned_plan", tp.summary(), path=args.tuned_plan)
        from repro.serve import tables_nbytes

        log.info("plan_exec",
                 f"plan exec: {args.plan_exec} "
                 f"({tables_nbytes(lut_tables)} table bytes, loaded from "
                 f"{args.tuned_plan} — no recapture/recompression)",
                 plan_exec=args.plan_exec,
                 table_bytes=tables_nbytes(lut_tables))
    elif args.lut_act:
        if args.calib_steps > 0 or args.calib_path:
            calib = None
            # save_calibration appends .npz when missing — honor both
            # spellings so warm restarts actually find the artifact
            if args.calib_path and (os.path.exists(args.calib_path)
                                    or os.path.exists(args.calib_path
                                                      + ".npz")):
                calib = load_calibration(args.calib_path)
                log.info("calib_loaded",
                         f"loaded calibration: {calib.summary()}")
            if calib is None:
                steps = max(1, args.calib_steps)
                batches = synthetic_batches(cfg, steps, batch_size=b,
                                            seq_len=t, seed=1)
                t0 = time.time()
                calib = capture_calibration(
                    params, cfg, batches,
                    min_count=args.calib_min_count,
                    smoothing=args.calib_smoothing)
                log.info("calib_captured",
                         f"captured {steps} calibration batches in "
                         f"{time.time() - t0:.2f}s: {calib.summary()}",
                         steps=steps, seconds=round(time.time() - t0, 3))
                if args.calib_path:
                    saved = save_calibration(args.calib_path, calib)
                    log.info("calib_saved",
                             f"saved calibration -> {saved}", path=saved)
            if tel is not None and calib.w_in is not None:
                tel.attach_monitor(obs.DontCareMonitor(
                    calib, sample_every=args.obs_drift_every))
        else:
            calib = rng.normal(size=100000) * 3
        with obs.span("build_plans", backend=args.lut_backend,
                      plan_exec=args.plan_exec):
            plans = build_serving_plans(cfg, calib,
                                        backend=args.lut_backend,
                                        plan_exec=args.plan_exec)
        plan_source = plans
        cfg = plans.patched_config(cfg)
        lut_tables = plans.tables_for_model(kernel=lut_kernel)
        log.info("plans_built", plans.summary())
        if plans.per_layer:
            from repro.serve import tables_nbytes

            log.info("plan_exec",
                     f"plan exec: {args.plan_exec} "
                     f"({tables_nbytes(lut_tables)} table bytes)",
                     plan_exec=args.plan_exec,
                     table_bytes=tables_nbytes(lut_tables))

    if args.save_plan:
        if plan_source is None:
            ap.error("--save-plan needs --lut-act plans built in-process "
                     "(a --tuned-plan artifact already is one)")
        from repro.tune import save_tuned_plan, tuned_plan_from_serving

        frozen = save_tuned_plan(args.save_plan,
                                 tuned_plan_from_serving(cfg, plan_source))
        log.info("plan_saved", f"saved tuned plan -> {frozen}",
                 path=frozen)

    serve = None
    if mesh is not None:
        serve = ShardedServe(cfg, mesh, lut_tables, mode=args.mesh_mode)
        params = serve.place_params(params)
        batch = serve.place_batch(batch)
        log.info("mesh_serving",
                 f"mesh {dict(mesh.shape)} mode={args.mesh_mode}; "
                 f"table placement:", mode=args.mesh_mode)
        for site, info in serve.placement.items():
            log.info("table_placement",
                     f"  {site}: {info['placement']} "
                     f"({info['bytes']} B, "
                     f"{info['per_device_bytes']} B/dev)",
                     site=site, placement=info["placement"],
                     bytes=info["bytes"])
    if args.kv_int8 and cfg.family in ("dense", "moe", "vlm"):
        log.info("kv_int8",
                 "int8 KV cache enabled (decode writes quantized entries)")

    gen = generate(cfg, params, batch, args.new_tokens,
                   lut_tables=None if serve is not None else lut_tables,
                   serve=serve, kv_int8=args.kv_int8)
    log.info("prefill",
             f"prefill {b}x{t}: compile {gen.prefill_compile_s:.2f}s, "
             f"run {gen.prefill_s:.2f}s",
             seconds=round(gen.prefill_s, 3),
             compile_s=round(gen.prefill_compile_s, 3))
    n = args.new_tokens * b
    log.info("decode",
             f"decode {args.new_tokens} tokens x {b} requests: compile "
             f"{gen.decode_compile_s:.2f}s, run {gen.decode_s:.2f}s "
             f"({n / gen.decode_s if gen.decode_s else 0.0:.1f} tok/s)",
             seconds=round(gen.decode_s, 3),
             compile_s=round(gen.decode_compile_s, 3),
             tok_s=round(n / gen.decode_s, 2) if gen.decode_s else 0.0)
    req0 = [int(v) for v in gen.tokens[0]]
    log.info("request_tokens", f"request 0: {req0}", rid=0, tokens=req0)


if __name__ == "__main__":
    main()
