"""Greedy generation: one prefill, then a greedy decode loop.

The one serving loop that ``repro.launch.serve``, the backend-equivalence
harness (:func:`repro.serve.plans.verify_backend_equivalence`) and
``chip_smoke.py`` share, single-device or through a
:class:`~repro.serve.sharded.ShardedServe`.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from .decode import decode_step, prefill, prefill_replay
from .kvcache import init_cache


@dataclasses.dataclass
class Generation:
    """One greedy prefill + decode run and where its time went.

    Compile and run times are separate: each program is compiled ahead of
    time (``lower().compile()``), and every run time ends in
    ``block_until_ready``.
    """

    tokens: np.ndarray          # (B, new_tokens) int32 greedy tokens
    # float32 (B, V) logits at the last position: the prefill's, then each
    # kept decode step's (the first one, or all with ``all_logits``)
    logits: list
    prefill_compile_s: float
    prefill_s: float
    decode_compile_s: float
    decode_s: float             # all new_tokens decode steps
    decode_program: object      # the compiled decode step (``as_text()``)

    @property
    def prefill_logits(self) -> np.ndarray:
        return self.logits[0]

    @property
    def step_logits(self) -> np.ndarray | None:
        """Logits of the first decode step (``None`` with no steps)."""
        return self.logits[1] if len(self.logits) > 1 else None


def _compiled(lowered, program: str):
    t0 = time.perf_counter()
    with obs.span(f"compile.{program}"):
        exe = lowered.compile()
    return exe, time.perf_counter() - t0


def generate(cfg, params, batch: dict, new_tokens: int, *,
             lut_tables: dict | None = None, serve=None,
             kv_int8: bool = False, max_seq: int | None = None,
             all_logits: bool = False) -> Generation:
    """Greedy-serve ``batch``: prefill, then ``new_tokens`` decode steps.

    Single-device with ``lut_tables`` closed over, or through ``serve``
    (a :class:`~repro.serve.ShardedServe` holding its placed tables).
    With ``kv_int8`` the prefill cache is re-homed into an int8 cache by
    replaying the prompt through the quantizing decode write path; the
    replay counts as prefill time.  ``max_seq`` defaults to the prompt
    (patch prefix included) plus ``new_tokens``.  ``all_logits`` keeps
    every decode step's logits, not only the first one's.

    The host work is in ``repro.obs`` spans (profiler annotations named
    ``repro:<span>``): ``generate`` around the call; ``lower.prefill``,
    ``lower.decode`` (tracing and lowering each program) and
    ``compile.prefill``, ``compile.decode`` (``Lowered.compile()``: a
    compile, or a persistent-cache load); ``prefill``, ``decode``; and
    ``readback`` (tokens and kept logits to the host).
    """
    tokens = batch["tokens"]
    b, t = tokens.shape
    with obs.span("generate", batch=b, prompt_len=t,
                  new_tokens=new_tokens):
        if cfg.family == "vlm" and "patches" in batch:
            t += batch["patches"].shape[1]  # the patch prefix fills the cache
        max_seq = max_seq or t + new_tokens
        with obs.span("lower.prefill"):
            if serve is None:
                extra = ()
                pre_low = jax.jit(lambda p, x: prefill(
                    p, cfg, x, max_seq=max_seq,
                    lut_tables=lut_tables)).lower(params, batch)
            else:
                extra = (serve.table_operands,)
                pre_low = serve.lower_prefill(params, batch, max_seq)
        pre_exe, pre_compile_s = _compiled(pre_low, "prefill")
        t0 = time.perf_counter()
        with obs.span("prefill", batch=b, prompt_len=t):
            logits, cache = jax.block_until_ready(
                pre_exe(params, batch, *extra))
            if kv_int8 and cfg.family in ("dense", "moe", "vlm"):
                cache_q = init_cache(cfg, b, max_seq, kv_dtype="int8")
                if serve is not None:
                    cache_q = serve.place_cache(cache_q)
                    logits, cache = serve.replay(params, cache_q, tokens)
                else:
                    logits, cache = jax.jit(lambda p, c, tk: prefill_replay(
                        p, cfg, c, tk, 0, lut_tables=lut_tables))(
                        params, cache_q, tokens)
                jax.block_until_ready(cache)
        pre_s = time.perf_counter() - t0
        kept = [logits[:, -1]]

        tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        pos0 = np.int32(t)
        with obs.span("lower.decode"):
            if serve is None:
                dec_low = jax.jit(lambda p, c, tk, pos: decode_step(
                    p, cfg, c, tk, pos, lut_tables=lut_tables)).lower(
                    params, cache, tok, pos0)
            else:
                dec_low = serve.lower_decode(params, cache, tok, pos0)
        dec_exe, dec_compile_s = _compiled(dec_low, "decode")
        outs = []
        t0 = time.perf_counter()
        with obs.span("decode", batch=b, new_tokens=new_tokens):
            for i in range(new_tokens):
                outs.append(tok)
                logits, cache = dec_exe(params, cache, tok, np.int32(t + i),
                                        *extra)
                tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
                if i == 0 or all_logits:
                    kept.append(logits[:, -1])
            jax.block_until_ready((tok, cache))
        dec_s = time.perf_counter() - t0
        with obs.span("readback"):
            toks = (np.concatenate([np.asarray(o) for o in outs], axis=1)
                    if outs else np.zeros((b, 0), np.int32))
            kept = [np.asarray(lg, np.float32) for lg in kept]
        return Generation(
            tokens=toks, logits=kept,
            prefill_compile_s=pre_compile_s, prefill_s=pre_s,
            decode_compile_s=dec_compile_s, decode_s=dec_s,
            decode_program=dec_exe)
